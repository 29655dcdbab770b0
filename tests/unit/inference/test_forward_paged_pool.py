"""The KV pool carried through the layer scan and written in place (ISSUE 28)
against the oracle for pool contents: a twin of ``transformer.paged_forward``,
the one paged driver (ISSUE 29), that hands each layer its own pool as the
scan's ``xs`` and stacks the written pools as ``ys``, the form the program had,
with the kernel's rank-4 call.

Same rows to the same places in the same precision: logits and pools must be
equal bit for bit, after a chunked prefill followed by decode steps, for the
padded and the compacted form, inside a burst body, through every family's
callables, and under ``tp_axis`` on two host devices.

The Pallas writer of a step's rows (ISSUE 32), interpreted, against the scatter
it stands in for on the chip is ``test_kv_writer.py``'s.
"""

import ast
import inspect
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import deepspeed_tpu
from deepspeed_tpu.compat import shard_map
from deepspeed_tpu.inference.v2 import tp as tp_rules
from deepspeed_tpu.models import (bloom, deepseek_v2, falcon, gptj, llama, mistral, mixtral, olmoe,
                                  opt, phi, qwen, transformer)
from deepspeed_tpu.models.transformer import flat_chunk_indices, flat_slots, paged_chunk_indices
from deepspeed_tpu.ops.attention import paged as paged_mod
from deepspeed_tpu.ops.attention.paged import paged_attention
from deepspeed_tpu.parallel import MeshTopology

NB, BS, MAXB = 14, 4, 4
PROMPTS = (9, 5, 2)  # tokens; prefilled in chunks of at most 4, then decoded


def sliced_paged_forward(layers, tokens, n_tokens, start_pos, block_tables, kv_cache, *,
                         block_size, live_token_bound, last_rows, embed, qkv, finish, head,
                         window=None, alibi_slopes=None):
    """``transformer.paged_forward`` with the pool as the layer scan's xs and ys
    (every position's logits: the twin's callers leave ``last_rows`` False)."""
    assert not last_rows
    n, t = tokens.shape
    num_blocks = kv_cache["k"].shape[1]
    slots = flat_slots(n, t, live_token_bound)
    if slots is None:
        safe_pos, live, lengths, blk, off = paged_chunk_indices(
            tokens, n_tokens, start_pos, block_tables, num_blocks, block_size)
        to_padded = from_padded = lambda a: a
    else:
        row, col, live, safe_pos, blk, off = (a[None] for a in flat_chunk_indices(
            n_tokens, start_pos, block_tables, num_blocks, block_size, slots))
        lengths = start_pos + n_tokens
        tokens = tokens[row, col]
        drop_row = jnp.where(live, row, n)[0]
        to_padded = lambda a: jnp.zeros((n, t) + a.shape[2:], a.dtype).at[
            drop_row, col[0]].set(a[0], mode="drop")
        from_padded = lambda a: a[row, col]

    x = embed(tokens, safe_pos)
    scale = 1.0 / np.sqrt(kv_cache["k"].shape[-1])
    head_idx = jnp.arange(kv_cache["k"].shape[2])[None, None, :]

    def layer(x, inp):
        lp, kpool, vpool = inp
        q, k, v, kept = qkv(lp, x, safe_pos)
        kpool = kpool.at[blk[:, :, None], head_idx, off[:, :, None]].set(k)
        vpool = vpool.at[blk[:, :, None], head_idx, off[:, :, None]].set(v)
        attn = from_padded(paged_attention(
            to_padded(q), kpool, vpool, block_tables, lengths, start_pos, n_tokens,
            block_size=block_size, softmax_scale=scale, window=window, alibi_slopes=alibi_slopes))
        return finish(lp, x, kept, attn, live), (kpool, vpool)

    x, (new_k, new_v) = jax.lax.scan(layer, x, (layers, kv_cache["k"], kv_cache["v"]))
    return to_padded(head(x)), {"k": new_k, "v": new_v}


def swap_in_the_twin(monkeypatch):
    """Every family reaches the driver as ``transformer.paged_forward``, looked
    up at call time.  Returns the list the twin appends to on each trace."""
    traced = []

    def twin(*args, **kw):
        traced.append(1)
        return sliced_paged_forward(*args, **kw)
    monkeypatch.setattr(transformer, "paged_forward", twin)
    return traced


def drawn(module, cfg, dtype):
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                    module.init_params(cfg, jax.random.PRNGKey(1)))
    rng = np.random.default_rng(3)
    # distinct live blocks per sequence; the last block is the trash block
    tables = jnp.asarray(rng.permutation(NB - 1)[:len(PROMPTS) * MAXB].reshape(-1, MAXB),
                         jnp.int32)
    return params, module.init_paged_cache(cfg, NB, BS, dtype=dtype), tables, rng


def serve(forward, kv, rng, decode_steps=2):
    """A chunked prefill of PROMPTS then decode steps through ``forward(kv,
    tokens, n_tokens, start_pos) -> (logits, kv)``: every logits array, then
    the pools."""
    seen, pos = [], np.zeros(len(PROMPTS), np.int32)
    while (left := np.asarray(PROMPTS) - pos).any():
        n_tokens = np.minimum(left, 4)
        tokens = jnp.asarray(rng.integers(0, 100, (len(PROMPTS), 4)), jnp.int32)
        logits, kv = forward(kv, tokens, jnp.asarray(n_tokens, jnp.int32), jnp.asarray(pos))
        seen.append(logits)
        pos = pos + n_tokens
    for _ in range(decode_steps):
        tokens = jnp.asarray(rng.integers(0, 100, (len(PROMPTS), 1)), jnp.int32)
        logits, kv = forward(kv, tokens, jnp.ones(len(PROMPTS), jnp.int32), jnp.asarray(pos))
        seen.append(logits)
        pos = pos + 1
    return seen + [kv["k"], kv["v"]]


def assert_bit_equal(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)))
    assert float(jnp.abs(got[-1].astype(jnp.float32)).sum()) > 0  # something was written


FAMILIES = {
    "llama-padded": (llama, llama.LlamaConfig.tiny(layers=3), None),
    "llama-compacted": (llama, llama.LlamaConfig.tiny(layers=3), 8),
    "mistral-window-compacted": (mistral, mistral.MistralConfig.tiny(layers=3, window=6), 8),
    "mixtral-sparse-ffn": (mixtral, mixtral.MixtralConfig.tiny(layers=3), None),
    "olmoe-sparse-ffn-and-qk-norm": (olmoe, olmoe.OlmoeConfig.tiny(layers=3), 8),
    "qwen-qkv-biases-compacted": (qwen, qwen.QwenConfig.tiny(layers=3), 8),
    "phi-parallel-residual-compacted": (phi, phi.PhiConfig.tiny(layers=3), 8),
    "falcon-mqa-padded": (falcon, falcon.FalconConfig.tiny(layers=3), None),
    "gptj-interleaved-rotary-compacted": (gptj, gptj.GPTJConfig.tiny(layers=3), 8),
    "opt-learned-positions-padded": (opt, opt.OPTConfig.tiny(layers=3), None),
    "bloom-alibi-compacted": (bloom, bloom.BloomConfig.tiny(layers=3), 8),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_carried_pool_equals_the_sliced_scan(monkeypatch, family, dtype):
    module, cfg, bound = FAMILIES[family]
    kw = {} if bound is None else {"live_token_bound": bound}

    def run():
        params, kv, tables, rng = drawn(module, cfg, dtype)
        fwd = jax.jit(lambda kv, tokens, n_tokens, start_pos: module.forward_paged(
            cfg, params, tokens, n_tokens, start_pos, tables, kv, block_size=BS, **kw))
        return serve(fwd, kv, rng)

    got = run()
    traced = swap_in_the_twin(monkeypatch)
    assert_bit_equal(got, run())
    assert traced


def test_dead_slots_land_in_each_layers_trash_block():
    """A padded chunk's dead slots write the last block of their own layer and
    no live block of any layer."""
    cfg = llama.LlamaConfig.tiny(layers=3)
    params, kv, tables, rng = drawn(llama, cfg, jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 100, (3, 4)), jnp.int32)
    _, kv = llama.forward_paged(cfg, params, tokens, jnp.asarray([4, 1, 0], jnp.int32),
                                jnp.zeros(3, jnp.int32), tables, kv, block_size=BS)
    written = np.asarray(jnp.abs(kv["k"]).sum(axis=(2, 3, 4)) > 0)  # [L, NB]
    want = np.zeros((3, NB), bool)
    want[:, [int(tables[0, 0]), int(tables[1, 0]), NB - 1]] = True
    np.testing.assert_array_equal(written, want)


@pytest.mark.parametrize("family", ["llama-padded", "olmoe-sparse-ffn-and-qk-norm"])
def test_carried_pool_inside_a_burst_body(monkeypatch, family):
    """The pool as the carry of an outer scan of three decode steps (the fused
    burst's form) after a chunked prefill: tokens, logits and pools."""
    module, cfg, _ = FAMILIES[family]

    def run():
        params, kv, tables, rng = drawn(module, cfg, jnp.bfloat16)
        fwd = jax.jit(lambda kv, tokens, n_tokens, start_pos: module.forward_paged(
            cfg, params, tokens, n_tokens, start_pos, tables, kv, block_size=BS))
        *_, k, v = serve(fwd, kv, rng, decode_steps=0)
        ones = jnp.ones(len(PROMPTS), jnp.int32)

        @jax.jit
        def burst(kv, tok0, start0):
            def body(carry, _):
                kv, tok, start = carry
                logits, kv = module.forward_paged(cfg, params, tok[:, None], ones, start, tables,
                                                  kv, block_size=BS)
                nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                return (kv, nxt, start + 1), (nxt, logits)
            (kv, _, _), (toks, logits) = jax.lax.scan(body, (kv, tok0, start0), None, length=3)
            return toks, logits, kv

        toks, logits, kv = burst({"k": k, "v": v}, jnp.asarray([7, 8, 9], jnp.int32),
                                 jnp.asarray(PROMPTS, jnp.int32))
        return [toks, logits, kv["k"], kv["v"]]

    got = run()
    traced = swap_in_the_twin(monkeypatch)
    assert_bit_equal(got, run())
    assert traced


@pytest.mark.parametrize("family", ["llama-compacted", "olmoe-sparse-ffn-and-qk-norm"])
def test_carried_pool_under_tp_axis_on_two_devices(monkeypatch, family):
    """Inside ``shard_map`` over a tensor axis of two host devices, the pool
    sharded on its heads (axis 2 of ``[L, NB, KV, bs, Dh]``)."""
    module, cfg, bound = FAMILIES[family]
    kw = {} if bound is None else {"live_token_bound": bound}
    topo = MeshTopology.from_axis_dict({"tensor": 2, "data": -1})

    def run():
        params, kv, tables, rng = drawn(module, cfg, jnp.float32)
        p_specs = tp_rules.param_specs(module, params, 2, cfg)
        kv_specs = tp_rules.kv_pool_spec(kv, 2)
        assert tuple(kv_specs["k"])[:3] == (None, None, "tensor")
        params = tp_rules.place(topo, params, p_specs)
        kv = tp_rules.place(topo, kv, kv_specs)

        def inner(params, kv, tokens, n_tokens, start_pos):
            return module.forward_paged(cfg, params, tokens, n_tokens, start_pos, tables, kv,
                                        block_size=BS, tp_axis="tensor", **kw)
        rep = PartitionSpec()
        fwd = jax.jit(shard_map(inner, mesh=topo.mesh, in_specs=(p_specs, kv_specs, rep, rep, rep),
                                out_specs=(rep, kv_specs), check_vma=False))
        return serve(lambda kv, *a: fwd(params, kv, *a), kv, rng)

    got = run()
    assert got[-1].shape == (cfg.num_layers, NB, cfg.num_kv_heads, BS,
                             cfg.hidden_size // cfg.num_heads)
    traced = swap_in_the_twin(monkeypatch)
    assert_bit_equal(got, run())
    assert traced


TEN = (llama, mistral, mixtral, olmoe, qwen, phi, falcon, gptj, opt, bloom)


def test_one_paged_driver_one_kernel_call_site_and_one_signature():
    """ISSUE 29's structure: nothing under ``deepspeed_tpu/`` asks a
    ``forward_paged`` for its signature, ``models/`` calls the paged kernel and
    each set of chunk indices from one place, no family's ``forward_paged``
    scans layers or writes a pool itself, and the ten share one signature."""
    package = pathlib.Path(deepspeed_tpu.__file__).parent
    calls = {"paged_attention": [], "paged_chunk_indices": [], "flat_chunk_indices": []}
    for path in sorted(package.rglob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = getattr(node.func, "id", getattr(node.func, "attr", None))
            if name in calls and path.parent.name == "models":
                calls[name].append(f"{path.name}:{node.lineno}")
            if name == "signature":
                assert "forward_paged" not in ast.unparse(node), f"{path}:{node.lineno}"
    assert {name: len(sites) for name, sites in calls.items()} == {
        "paged_attention": 1, "paged_chunk_indices": 1, "flat_chunk_indices": 1}, calls
    assert all(site.startswith("transformer.py:") for sites in calls.values() for site in sites)
    want = inspect.signature(llama.forward_paged)
    assert list(want.parameters)[-5:] == ["block_size", "tp_axis", "gather_logits",
                                          "live_token_bound", "last_rows"]
    for module in TEN:
        source = inspect.getsource(module.forward_paged)
        assert "lax.scan" not in source and ".at[" not in source, module.__name__
        assert "transformer.paged_forward(" in source, module.__name__
        got = inspect.signature(module.forward_paged)
        assert [(p.name, p.kind, p.default) for p in got.parameters.values()] == [
            (p.name, p.kind, p.default) for p in want.parameters.values()], module.__name__


# ------------------------------------------------------------------ ISSUE 40
# The paged kernel takes q from, and returns its output to, the flat token axis:
# a compacted chunk pass builds nothing of the padded [N, T, H, Dh] size around it.
def _scan_bodies(jaxpr):
    """Every equation inside a ``scan`` of the jaxpr, however deep (the Pallas
    kernels' own bodies apart: their values are tiles in VMEM)."""
    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside:
                yield eqn
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple)) else [value]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from walk(inner, inside or eqn.primitive.name == "scan")
    return walk(jaxpr, False)


@pytest.mark.parametrize("family", ["mistral-7b-widths", "deepseek-v2-latent", "olmoe-mha"])
def test_a_compacted_chunk_program_holds_nothing_of_the_padded_size_in_its_layer_scan(monkeypatch,
                                                                                      family):
    """``forward_paged`` at ``[32, 256]`` with 256 live tokens at most, traced as
    the chip would run it (kernels on: tracing a ``pallas_call`` needs no chip,
    and the dense fallback may pad as it likes): no value inside the layer scan
    is as large as a quarter of the padded ``[N, T, H, Dh]`` q, and the kernel's
    q is the flat axis laid KV-major.  The shapes are static, so the padding
    cannot come back unseen."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, t, bound, bs, nb, maxb = 32, 256, 256, 128, 8, 20
    module, cfg = {
        "mistral-7b-widths": lambda: (mistral, mistral.MistralConfig(
            vocab_size=512, hidden_size=4096, intermediate_size=14336, num_layers=2, num_heads=32,
            num_kv_heads=8, max_seq_len=32768, sliding_window=4096)),
        "deepseek-v2-latent": lambda: (deepseek_v2, deepseek_v2.DeepseekV2Config.tiny(local_experts=4)),
        "olmoe-mha": lambda: (olmoe, olmoe.OlmoeConfig.tiny(layers=2)),
    }[family]()
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), module.init_params(cfg, jax.random.PRNGKey(0))))
    kv = jax.eval_shape(lambda: module.init_paged_cache(cfg, nb, bs, dtype=jnp.bfloat16))
    ints = [jax.ShapeDtypeStruct(s, jnp.int32) for s in ((n, t), (n, ), (n, ), (n, maxb))]

    def trace(bound):
        return jax.make_jaxpr(lambda p, kv, tokens, n_tokens, start_pos, tables: module.forward_paged(
            cfg, p, tokens, n_tokens, start_pos, tables, kv, block_size=bs,
            live_token_bound=bound))(params, kv, *ints)

    calls = [e for e in _scan_bodies(trace(bound).jaxpr) if e.primitive.name == "pallas_call"
             and e.params["name"] == "paged_attention"]
    assert calls
    for call in calls:
        q = next(v.aval for v in call.invars if v.aval.ndim == 3 and v.aval.dtype != jnp.int32)
        hq = cfg.num_heads
        kvh = q.shape[0]
        group, align = hq // kvh, 16 // np.gcd(hq // kvh, 16)
        rows = paged_mod.step_tile(t, hq, kvh, q.shape[2], bs, q.dtype, jnp.bfloat16,
                                   getattr(module, "paged_value_dim", lambda c: None)(cfg))[1]
        assert q.shape[1] == (bound + n * (align - 1) + -(-rows // group)) * group
        padded = n * t * hq * q.shape[2]
        assert q.size < padded // 4
    pools = {(leaf.shape[0] * leaf.shape[1], ) + leaf.shape[2:] for leaf in jax.tree_util.tree_leaves(kv)}
    sizes = {}
    for eqn in _scan_bodies(trace(bound).jaxpr):
        for out in eqn.outvars:  # the carried pool apart, which the writer hands on whole
            if getattr(out.aval, "size", 0) >= padded // 4 and out.aval.shape not in pools:
                sizes[eqn.primitive.name] = out.aval.shape
    assert not sizes, sizes
    # the search does find the padded bucket where a program runs it: the same trace with no bound
    assert any(getattr(out.aval, "size", 0) >= padded for eqn in _scan_bodies(trace(None).jaxpr)
               for out in eqn.outvars)
