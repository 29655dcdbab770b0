"""The KV pool carried through the layer scan and written in place (ISSUE 28)
against the oracle for pool contents: a twin of ``llama.forward_paged`` that
hands each layer its own pool as the scan's ``xs`` and stacks the written
pools as ``ys``, the form the program had, with the kernel's rank-4 call.

Same rows to the same places in the same precision: logits and pools must be
equal bit for bit, after a chunked prefill followed by decode steps, for the
padded and the compacted form, inside a burst body, through Mixtral's and
OLMoE's seams, and under ``tp_axis`` on two host devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from deepspeed_tpu.compat import shard_map
from deepspeed_tpu.inference.v2 import tp as tp_rules
from deepspeed_tpu.models import llama, mistral, mixtral, olmoe
from deepspeed_tpu.models.transformer import (apply_rotary, flat_chunk_indices, flat_slots,
                                              paged_chunk_indices, rms_norm, rotary_tables,
                                              swiglu_mlp)
from deepspeed_tpu.ops.attention.paged import paged_attention
from deepspeed_tpu.parallel import MeshTopology

NB, BS, MAXB = 14, 4, 4
PROMPTS = (9, 5, 2)  # tokens; prefilled in chunks of at most 4, then decoded


def sliced_forward_paged(config, params, tokens, n_tokens, start_pos, block_tables, kv_cache, *,
                         block_size, window=None, tp_axis=None, gather_logits=True,
                         live_token_bound=None, ffn=None, qk_norm=None):
    """``llama.forward_paged`` with the pool as the layer scan's xs and ys."""
    n, t = tokens.shape
    Dh = config.hidden_size // config.num_heads
    cos, sin = rotary_tables(Dh, config.max_seq_len, config.rope_theta)
    num_blocks = kv_cache["k"].shape[1]
    slots = flat_slots(n, t, live_token_bound)
    if slots is None:
        b, tchunk = n, t
        safe_pos, live, lengths, blk, off = paged_chunk_indices(
            tokens, n_tokens, start_pos, block_tables, num_blocks, block_size)
        to_padded = from_padded = lambda a: a
    else:
        b, tchunk = 1, slots
        row, col, live, safe_pos, blk, off = (a[None] for a in flat_chunk_indices(
            n_tokens, start_pos, block_tables, num_blocks, block_size, slots))
        lengths = start_pos + n_tokens
        tokens = tokens[row, col]
        drop_row = jnp.where(live, row, n)[0]
        to_padded = lambda a: jnp.zeros((n, t) + a.shape[2:], a.dtype).at[
            drop_row, col[0]].set(a[0], mode="drop")
        from_padded = lambda a: a[row, col]

    x = params["embed"][tokens].astype(kv_cache["k"].dtype)
    H = params["layers"]["attn"]["wq"].shape[-1] // Dh
    KV = params["layers"]["attn"]["wk"].shape[-1] // Dh
    head_idx = jnp.arange(KV)[None, None, :]
    preduce = (lambda y: jax.lax.psum(y, tp_axis)) if tp_axis else (lambda y: y)

    def layer(x, inp):
        lp, kpool, vpool = inp
        attn_in = rms_norm(x, lp["attn_norm"], config.rms_eps)
        q = (attn_in @ lp["attn"]["wq"].astype(x.dtype)).reshape(b, tchunk, H, Dh)
        k = (attn_in @ lp["attn"]["wk"].astype(x.dtype)).reshape(b, tchunk, KV, Dh)
        v = (attn_in @ lp["attn"]["wv"].astype(x.dtype)).reshape(b, tchunk, KV, Dh)
        if qk_norm is not None:
            q, k = qk_norm(lp, q, k)
        q = apply_rotary(q, cos, sin, safe_pos)
        k = apply_rotary(k, cos, sin, safe_pos)
        kpool = kpool.at[blk[:, :, None], head_idx, off[:, :, None]].set(k)
        vpool = vpool.at[blk[:, :, None], head_idx, off[:, :, None]].set(v)
        out = from_padded(paged_attention(
            to_padded(q), kpool, vpool, block_tables, lengths, start_pos, n_tokens,
            block_size=block_size, softmax_scale=1.0 / np.sqrt(Dh), window=window))
        x = x + preduce(out.reshape(b, tchunk, H * Dh) @ lp["attn"]["wo"].astype(x.dtype))
        mlp_in = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        x = x + preduce(swiglu_mlp(lp["mlp"], mlp_in) if ffn is None else ffn(lp, mlp_in, live))
        return x, (kpool, vpool)

    x, (new_k, new_v) = jax.lax.scan(layer, x, (params["layers"], kv_cache["k"], kv_cache["v"]))
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    if tp_axis is not None and gather_logits and not config.tie_embeddings:
        logits = jax.lax.all_gather(logits, tp_axis, axis=-1, tiled=True)
    return to_padded(logits), {"k": new_k, "v": new_v}


def swap_in_the_twin(monkeypatch):
    """Every family reaches the body through ``llama.forward_paged``, looked up
    at call time by mistral's and the MoE modules' own ``forward_paged``.
    Returns the list the twin appends to on each trace."""
    traced = []

    def twin(*args, **kw):
        traced.append(1)
        return sliced_forward_paged(*args, **kw)
    monkeypatch.setattr(llama, "forward_paged", twin)
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
    "mixtral-ffn-seam": (mixtral, mixtral.MixtralConfig.tiny(layers=3), None),
    "olmoe-ffn-and-qk-norm-seams": (olmoe, olmoe.OlmoeConfig.tiny(layers=3), 8),
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


@pytest.mark.parametrize("family", ["llama-padded", "olmoe-ffn-and-qk-norm-seams"])
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


@pytest.mark.parametrize("family", ["llama-compacted", "olmoe-ffn-and-qk-norm-seams"])
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
