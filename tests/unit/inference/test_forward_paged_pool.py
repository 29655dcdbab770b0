"""The KV pool carried through the layer scan and written in place (ISSUE 28)
against the oracle for pool contents: a twin of ``transformer.paged_forward``,
the one paged driver (ISSUE 29), that hands each layer its own pool as the
scan's ``xs`` and stacks the written pools as ``ys``, the form the program had,
with the kernel's rank-4 call.

Same rows to the same places in the same precision: logits and pools must be
equal bit for bit, after a chunked prefill followed by decode steps, for the
padded and the compacted form, inside a burst body, through every family's
callables, and under ``tp_axis`` on two host devices.

Below them (ISSUE 32) the Pallas writer of a step's rows, interpreted, against
the scatter it stands in for on the chip.
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
from deepspeed_tpu.ops import _pallas
from deepspeed_tpu.ops.attention import kv_write as kvw
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


# ------------------------------------------------------------------ ISSUE 32
# The Pallas writer (ops/attention/kv_write.py, interpreted here) against the
# scatter it replaces on the chip: the same rows in the same places, every
# other byte of every live block the parent's.  Only the trash blocks may
# differ: the scatter parks a dead slot's row there, the writer writes nothing.
W_MAXB, W_LAYERS = 6, 2


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(_pallas, "INTERPRET", True)


def written_both_ways(n_tokens, start_pos, t, bound, *, kvh=8, width=128, dtype=jnp.bfloat16,
                      bs=32, leaves=2, layer=1):
    """One layer's write of a ``[n, t]`` bucket (compacted where ``bound`` says
    so) into random pools by the writer and by the scatter: ``(got, want,
    before, trash rows, plan)``."""
    rng = np.random.default_rng(len(n_tokens) * 1000 + t)
    n = len(n_tokens)
    nb = n * W_MAXB + 1
    tables = jnp.asarray(rng.permutation(nb - 1)[:n * W_MAXB].reshape(n, W_MAXB), jnp.int32)
    n_tokens, start_pos = jnp.asarray(n_tokens, jnp.int32), jnp.asarray(start_pos, jnp.int32)
    pools = [jnp.asarray(rng.standard_normal((W_LAYERS * nb, kvh, bs, width)), dtype)
             for _ in range(leaves)]
    slots = flat_slots(n, t, bound)
    if slots is None:
        _, _, _, blk, off = paged_chunk_indices(jnp.zeros((n, t), jnp.int32), n_tokens, start_pos,
                                                tables, nb, bs)
    else:
        *_, blk, off = (a[None] for a in flat_chunk_indices(n_tokens, start_pos, tables, nb, bs,
                                                            slots))
    rows = [jnp.asarray(rng.standard_normal(blk.shape + (kvh, width)), dtype) for _ in pools]
    first = jnp.int32(layer * nb)
    plan = kvw.write_plan(pools, n_tokens, start_pos, tables, t=t, slots=slots)
    assert plan is not None and plan.table.shape == (5, kvw.work_bound(n, t, slots,
                                                                       kvw.tile_rows(pools)))
    got = jax.jit(lambda pools, rows: kvw.kv_write(pools, rows, first, blk, off, plan))(pools, rows)
    want = kvw.kv_write(pools, rows, first, blk, off, None)
    return got, want, pools, [l * nb + nb - 1 for l in range(W_LAYERS)], plan


def assert_same_outside_trash(got, want, before, trash):
    assert len(got) == len(want) == len(before)
    for g, w, b in zip(got, want, before):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w, b = (np.asarray(a.astype(jnp.float32)) for a in (g, w, b))
        live = np.ones(g.shape[0], bool)
        live[trash] = False
        np.testing.assert_array_equal(g[live], w[live])
        np.testing.assert_array_equal(g[~live], b[~live])  # the writer leaves the trash alone


# bf16 pools of 32-row blocks: a tile is 16 rows, a block two tiles
WRITES = {
    "decode-odd-and-even-offsets": ([1, 1, 1, 1], [5, 16, 31, 42], 1, None),
    "decode-in-a-bounded-bucket": ([1, 1, 1], [0, 33, 63], 1, 256),
    "decode-with-a-dead-row": ([1, 0, 1], [7, 3, 64], 1, None),
    "padded-chunk-mid-tile-to-mid-tile": ([20, 1, 0, 7], [7, 40, 0, 30], 24, None),
    "compacted-chunk-mid-tile-to-mid-tile": ([20, 1, 0, 7], [7, 40, 0, 30], 24, 32),
    "run-crosses-a-block-boundary": ([9], [28], 16, None),
    "run-covers-whole-tiles": ([48, 16], [16, 32], 48, 64),
    "run-starts-at-a-tile-and-ends-mid-tile": ([21], [32], 24, None),
    "compacted-with-dead-slots-behind": ([3, 2], [14, 31], 8, 8),
    "nothing-live": ([0, 0], [0, 5], 4, None),
}


@pytest.mark.parametrize("case", list(WRITES))
def test_the_writer_puts_the_scatters_rows_in_the_scatters_places(interpreted, case):
    n_tokens, start_pos, t, bound = WRITES[case]
    got, want, before, trash, plan = written_both_ways(n_tokens, start_pos, t, bound)
    assert_same_outside_trash(got, want, before, trash)
    # one entry a touched tile: a run of c tokens from position p lies in these
    tiles = sum((p + c - 1) // 16 - p // 16 + 1 for c, p in zip(n_tokens, start_pos) if c)
    assert int(plan.count[0]) == tiles
    if not tiles:  # nothing but what was there, the trash blocks too
        assert_bit_equal(got, before)


@pytest.mark.parametrize("kvh,width,leaves,dtype", [
    (1, 128, 2, jnp.bfloat16), (8, 128, 2, jnp.bfloat16), (16, 128, 2, jnp.bfloat16),
    (1, 640, 1, jnp.bfloat16), (8, 128, 2, jnp.float32), (1, 640, 1, jnp.float32),
    (2, 128, 2, jnp.float32)],
    ids=["mqa", "mistral-8kv", "olmoe-16kv", "latent-one-leaf", "f32-tile-of-8", "latent-f32",
         "a-tp-shard-of-2"])
def test_the_writer_reads_heads_width_leaves_and_tile_off_its_operands(interpreted, kvh, width,
                                                                       leaves, dtype):
    """A compacted chunk beside decode rows, every run unaligned, for each pool
    the families make: the tile's height is the dtype's (16 rows of bf16, 8 of
    float32) and nothing is passed for it."""
    got, want, before, trash, _ = written_both_ways(
        [21, 1, 0, 1, 9], [11, 40, 0, 63, 28], 24, 40, kvh=kvh, width=width, dtype=dtype,
        leaves=leaves)
    assert kvw.tile_rows(before) == (16 if dtype == jnp.bfloat16 else 8)
    assert_same_outside_trash(got, want, before, trash)


def test_a_block_that_is_not_whole_tiles_keeps_the_scatter(interpreted):
    pools = [jnp.zeros((W_LAYERS * 5, 2, 24, 128), jnp.bfloat16)] * 2  # 24 rows: a tile and a half
    ints = jnp.zeros(2, jnp.int32)
    assert kvw.tile_rows(pools) is None
    assert kvw.write_plan(pools, ints, ints, jnp.zeros((2, 2), jnp.int32), t=1, slots=None) is None


def scatter_instead(monkeypatch):
    """The parent's write under the same interpreted paged kernel."""
    monkeypatch.setattr(kvw, "write_plan", lambda *a, **k: None)


W_BS, W_NB = 16, 14  # a bf16 tile a block; the last block is the trash


def bounded_steps(forward, kv, rng):
    """Three chunk steps of at most 8 live tokens (what ``live_token_bound=8``
    promises) and a decode step through ``forward(kv, tokens, n_tokens,
    start_pos)``: every logits array, then the live blocks of every leaf."""
    seen, pos = [], np.zeros(len(PROMPTS), np.int32)
    for n_tokens, t in [((4, 3, 1), 4), ((4, 2, 1), 4), ((1, 0, 0), 4), ((1, 1, 1), 1)]:
        tokens = jnp.asarray(rng.integers(0, 100, (len(PROMPTS), t)), jnp.int32)
        logits, kv = forward(kv, tokens, jnp.asarray(n_tokens, jnp.int32), jnp.asarray(pos))
        seen.append(logits)
        pos = pos + np.asarray(n_tokens)
    return seen + [leaf[:, :W_NB - 1] for leaf in jax.tree_util.tree_leaves(kv)]


@pytest.mark.parametrize("family", ["llama-compacted", "llama-padded", "deepseek-v2-latent"])
def test_the_writer_through_a_familys_forward(interpreted, monkeypatch, family):
    """Chunked prefill then decode through ``forward_paged`` with the writer
    and with the scatter, the paged kernel interpreted in both: every logit
    and every live block of every leaf."""
    if family == "deepseek-v2-latent":
        module, cfg, bound = deepseek_v2, deepseek_v2.DeepseekV2Config.tiny(local_experts=4), 8
    else:
        module, cfg, bound = FAMILIES[family]
    kw = {} if bound is None else {"live_token_bound": bound}

    def run():
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                        module.init_params(cfg, jax.random.PRNGKey(1)))
        rng = np.random.default_rng(3)
        tables = jnp.asarray(rng.permutation(W_NB - 1)[:len(PROMPTS) * MAXB].reshape(-1, MAXB),
                             jnp.int32)
        kv = module.init_paged_cache(cfg, W_NB, W_BS, dtype=jnp.bfloat16)
        fwd = jax.jit(lambda kv, tokens, n_tokens, start_pos: module.forward_paged(
            cfg, params, tokens, n_tokens, start_pos, tables, kv, block_size=W_BS, **kw))
        return bounded_steps(fwd, kv, rng)

    got = run()
    scatter_instead(monkeypatch)
    assert_bit_equal(got, run())


def test_the_writer_inside_a_two_step_burst_body(interpreted, monkeypatch):
    """The pool as the carry of an outer scan of two decode steps, the writer
    inside it: tokens, logits and live blocks the scatter's."""
    module, cfg, _ = FAMILIES["llama-padded"]

    def run():
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                        module.init_params(cfg, jax.random.PRNGKey(1)))
        rng = np.random.default_rng(5)
        tables = jnp.asarray(rng.permutation(W_NB - 1)[:3 * MAXB].reshape(-1, MAXB), jnp.int32)
        kv = jax.tree_util.tree_map(
            lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
            module.init_paged_cache(cfg, W_NB, W_BS, dtype=jnp.bfloat16))
        ones = jnp.ones(3, jnp.int32)

        @jax.jit
        def burst(kv, tok0, start0):
            def body(carry, _):
                kv, tok, start = carry
                logits, kv = module.forward_paged(cfg, params, tok[:, None], ones, start, tables,
                                                  kv, block_size=W_BS)
                nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                return (kv, nxt, start + 1), (nxt, logits)
            (kv, _, _), (toks, logits) = jax.lax.scan(body, (kv, tok0, start0), None, length=2)
            return toks, logits, kv

        # a burst that crosses a tile's end (15 -> 16), one mid-tile, one at a block's start
        toks, logits, kv = burst(kv, jnp.asarray([7, 8, 9], jnp.int32),
                                 jnp.asarray([15, 4, 32], jnp.int32))
        return [toks, logits, kv["k"][:, :W_NB - 1], kv["v"][:, :W_NB - 1]]

    got = run()
    scatter_instead(monkeypatch)
    assert_bit_equal(got, run())


def test_the_writer_under_tp_axis_on_two_devices(interpreted, monkeypatch):
    """Inside ``shard_map`` over a tensor axis of two host devices the writer
    sees the local KV heads of a pool sharded on them."""
    module, cfg, bound = FAMILIES["llama-compacted"]
    topo = MeshTopology.from_axis_dict({"tensor": 2, "data": -1})

    def run():
        params = module.init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.default_rng(3)
        tables = jnp.asarray(rng.permutation(W_NB - 1)[:len(PROMPTS) * MAXB].reshape(-1, MAXB),
                             jnp.int32)
        kv = module.init_paged_cache(cfg, W_NB, 8, dtype=jnp.float32)  # a float32 tile a block
        p_specs = tp_rules.param_specs(module, params, 2, cfg)
        kv_specs = tp_rules.kv_pool_spec(kv, 2)
        params = tp_rules.place(topo, params, p_specs)
        kv = tp_rules.place(topo, kv, kv_specs)

        def inner(params, kv, tokens, n_tokens, start_pos):
            return module.forward_paged(cfg, params, tokens, n_tokens, start_pos, tables, kv,
                                        block_size=8, tp_axis="tensor", live_token_bound=bound)
        rep = PartitionSpec()
        fwd = jax.jit(shard_map(inner, mesh=topo.mesh, in_specs=(p_specs, kv_specs, rep, rep, rep),
                                out_specs=(rep, kv_specs), check_vma=False))
        return bounded_steps(lambda kv, *a: fwd(params, kv, *a), kv, rng)

    got = run()
    scatter_instead(monkeypatch)
    assert_bit_equal(got, run())


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
