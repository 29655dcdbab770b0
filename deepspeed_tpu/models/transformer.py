"""Shared transformer building blocks (pure-function, pytree-params style).

These replace the reference's fused CUDA transformer kernels
(csrc/transformer/ds_transformer_cuda.cpp — qkv gemm/softmax/layernorm/gelu
fusions): under XLA those fusions are automatic, so the blocks are written for
MXU-friendly shapes (large batched matmuls, bf16 inputs) and the layer stack is
a ``lax.scan`` over stacked layer params — which (a) compiles once for all
layers, and (b) under ZeRO-3 naturally gathers ONE layer's params per scan step,
the analog of the reference's per-submodule allgather/release coordinator
(runtime/zero/partitioned_param_coordinator.py:257).

Attention routes through ``attention_fn`` so Ulysses sequence parallelism
(deepspeed_tpu/sequence) or a Pallas flash kernel can be injected — mirroring
DistributedAttention wrapping "any local attention" (deepspeed/sequence/layer.py:60).
"""

import contextlib
import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ----------------------------------------------------------------- norms
def rms_norm(x, weight, eps=1e-6):
    """RMSNorm (reference csrc/transformer/inference/csrc/rms_norm.cu analog)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, weight, bias, eps=1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ----------------------------------------------------------------- rotary
def rotary_tables(head_dim: int, max_seq: int, theta: float = 10000.0):
    """Returns NUMPY tables (config-static constants): layer closures that
    capture them stay trace-free, which custom_vjp wrappers
    (activation_checkpointing.offload_checkpoint) require — a jnp constant
    created during tracing is a tracer, and custom_vjp can't close over
    tracers.  apply_rotary converts at use."""
    inv_freq = 1.0 / (theta**(np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    t = np.arange(max_seq, dtype=np.float32)
    freqs = np.outer(t, inv_freq)  # [S, D/2]
    return np.cos(freqs), np.sin(freqs)


def apply_rotary(x, cos, sin, positions=None):
    """x: [B, S, H, D]. cos/sin: [maxS, D/2] (numpy or jnp)."""
    seq = x.shape[1]
    if positions is None:
        c = jnp.asarray(cos[:seq])[None, :, None, :]
        s = jnp.asarray(sin[:seq])[None, :, None, :]
    else:
        c = jnp.asarray(cos)[positions][:, :, None, :]
        s = jnp.asarray(sin)[positions][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = c.astype(x.dtype)
    s = s.astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def kv_projection_shardable(shape) -> bool:
    """Whether a kv projection weight ([..., in, out]) may be column-sharded
    over the tensor axis.

    GQA/MQA kv projections (output narrower than the model dim) stay
    REPLICATED — the Megatron/AutoTP convention when kv heads don't divide
    over tp.  Beyond being the right layout (a kv projection is small, and a
    sub-head shard forces an allgather at every attention), sub-head-aligned
    kv sharding silently MISCOMPILES in older XLA SPMD partitioners:
    ``lax.scan`` + the rotate-half rotary on a sub-head-sharded operand
    returns wrong numerics (no error — ~90% of logits off).  tp_rules can't
    see head_dim, so "narrower than the input dim" is the conservative
    stand-in that exactly captures GQA/MQA while leaving MHA layouts (out ==
    in, head-aligned whenever q-sharding is) untouched."""
    return len(shape) >= 2 and shape[-1] >= shape[-2]


# ----------------------------------------------------------------- attention
def sdpa(q, k, v, causal=True, mask=None, softmax_scale=None, bias=None):
    """Scaled dot-product attention. q,k,v: [B, S, H, D] (k/v may have fewer
    heads — GQA — broadcast via repeat). fp32 softmax for stability.
    ``bias``: additive logit bias broadcastable to [B, H, Sq, Sk] (ALiBi)."""
    b, sq, hq, d = q.shape
    hk = k.shape[2]
    if hk != hq:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    sk = k.shape[1]
    if causal:
        # support sq != sk (decode): query i attends keys <= i + (sk - sq)
        qpos = jnp.arange(sq)[:, None] + (sk - sq)
        kpos = jnp.arange(sk)[None, :]
        causal_mask = kpos <= qpos
        logits = jnp.where(causal_mask[None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def default_attention():
    """Resolve the attention impl for this backend: the Pallas flash kernel on
    TPU (ops/attention/flash.py — the reference's fused-attention analog,
    csrc/transformer/ds_attention.cu), plain XLA sdpa elsewhere.  Callers that
    pass an explicit ``attention_fn`` (Ulysses, blocksparse, tests) override it."""
    from ..ops import _pallas
    if _pallas.use_pallas():
        from ..ops.attention.flash import flash_attention
        return flash_attention
    return sdpa


# Config-installed attention override — the functional analog of the
# reference's module injection swapping attention for SparseSelfAttention when
# the JSON's ``sparse_attention`` section is set (sparse_self_attention.py:99,
# wired by initialize()).  Models that route through attention_block pick it up
# at trace time unless they pass an explicit attention_fn; "engaged" records
# that a trace actually consumed it (tested, not just installed).
_CONFIGURED_ATTENTION = {"fn": None, "engaged": False}


def set_default_attention(fn):
    """Install (or clear, fn=None) the process-wide default attention_fn."""
    _CONFIGURED_ATTENTION["fn"] = fn
    _CONFIGURED_ATTENTION["engaged"] = False


def scoped_default_attention(loss_fn, attention_fn):
    """Wrap ``loss_fn`` so ``attention_fn`` (possibly None) is the configured
    default exactly while loss_fn's body runs — i.e. while jit TRACES it.
    This pins each engine's attention choice to its own loss function: two
    engines with different sparse_attention configs coexist in one process,
    and an engine that configured none can never inherit another's kernel."""

    def scoped(*args, **kwargs):
        prev = _CONFIGURED_ATTENTION["fn"]
        _CONFIGURED_ATTENTION["fn"] = attention_fn
        try:
            return loss_fn(*args, **kwargs)
        finally:
            _CONFIGURED_ATTENTION["fn"] = prev

    return scoped


def configured_attention_engaged() -> bool:
    return _CONFIGURED_ATTENTION["engaged"]


# ------------------------------------------------------- random-LTD scoping
# Engine-side random-LTD activation for the in-repo zoo (reference
# convert_to_random_ltd rewrites nn.Modules from config alone,
# runtime/data_pipeline/data_routing/helper.py:11).  The functional analog:
# initialize() scopes an LTD state around the loss_fn exactly like the sparse-
# attention default above; model forwards that support token dropping read it
# at TRACE time via configured_ltd().  ``state["keep"]`` is a python int —
# baked into the trace — so the engine re-jits when the scheduler's budget
# steps (the reference pays the same recompile via its seqlen buckets).
_CONFIGURED_LTD = {"state": None, "engaged": False}

# Force-empty pin: scoped_random_ltd(fn, None) installs this sentinel rather
# than None so INNER scopes can tell "an outer scope pinned LTD off" (eval)
# apart from "no scope active".  Without it the engine's eval wrapper was dead
# code: initialize() already wraps the loss_fn with the train LTD state, and
# that inner wrapper re-installed the state right over eval's empty pin, so
# eval traced WITH token dropping (ADVICE r5 medium).
_LTD_FORCE_EMPTY = object()


def scoped_random_ltd(loss_fn, ltd_state):
    """Pin ``ltd_state`` as the configured random-LTD while loss_fn traces
    (``None`` pins the scope EMPTY — how the engine's eval step keeps LTD
    train-only; the empty pin is AUTHORITATIVE over inner train wrappers).
    Engagement is recorded on the state dict itself (``ltd_state["engaged"]``),
    so each engine sees its own truth rather than a process-global flag."""
    pin = _LTD_FORCE_EMPTY if ltd_state is None else ltd_state

    def scoped(*args, **kwargs):
        prev = _CONFIGURED_LTD["state"]
        if prev is _LTD_FORCE_EMPTY and ltd_state is not None:
            # an outer scope pinned LTD off — the train wrapper must not
            # re-engage it (eval measures the full model)
            return loss_fn(*args, **kwargs)
        _CONFIGURED_LTD["state"] = pin
        if ltd_state is not None:
            _CONFIGURED_LTD["engaged"] = False  # fresh trace, fresh verdict
        try:
            return loss_fn(*args, **kwargs)
        finally:
            _CONFIGURED_LTD["state"] = prev

    return scoped


def configured_ltd():
    st = _CONFIGURED_LTD["state"]
    return None if st is _LTD_FORCE_EMPTY else st


def configured_ltd_engaged() -> bool:
    return _CONFIGURED_LTD["engaged"]


def random_ltd_scan(layer, x, stacked_params, rng, keep: int):
    """Scan a layer stack with random layerwise token dropping: first and last
    layers see every token (reference random_ltd keeps the outer layers
    intact); each middle layer processes an independent random subset of
    ``keep`` tokens — dropped tokens ride the residual stream unchanged —
    with rotary/causal math on ORIGINAL positions via the layer's
    ``positions`` argument.  Cuts middle-layer attention cost by (keep/S)^2
    (reference csrc/random_ltd token_sort/gather kernels; here the sort/
    gather is jnp.take/at[].set and XLA fuses it)."""
    from ..runtime.data_pipeline.random_ltd import (gather_tokens,
                                                    sample_token_indices,
                                                    scatter_tokens)
    leaves = jax.tree_util.tree_leaves(stacked_params)
    L = int(leaves[0].shape[0])
    S = x.shape[1]
    take = lambda i: jax.tree_util.tree_map(lambda l: l[i], stacked_params)
    if L < 3 or keep >= S:
        x, _ = jax.lax.scan(layer, x, stacked_params)
        return x
    _CONFIGURED_LTD["engaged"] = True
    st = _CONFIGURED_LTD["state"]
    if isinstance(st, dict):  # never the force-empty sentinel
        st["engaged"] = True  # per-engine truth (the global resets each trace)
    x, _ = layer(x, take(0))
    mids = jax.tree_util.tree_map(lambda l: l[1:-1], stacked_params)

    def mid_body(carry, lp):
        h, key = carry
        key, sub = jax.random.split(key)
        idx = sample_token_indices(sub, S, keep)
        kept = gather_tokens(h, idx)
        # positions passed POSITIONALLY: custom_vjp-wrapped layers
        # (offload_checkpoint) accept no kwargs
        y, _ = layer(kept, lp, idx[None, :])  # [1, K]: original rotary positions
        return (scatter_tokens(h, y, idx), key), None

    (x, _), _ = jax.lax.scan(mid_body, (x, rng), mids)
    x, _ = layer(x, take(L - 1))
    return x


def _resolve_attention(attention_fn):
    if attention_fn is not None:
        return attention_fn
    if _CONFIGURED_ATTENTION["fn"] is not None:
        _CONFIGURED_ATTENTION["engaged"] = True
        return _CONFIGURED_ATTENTION["fn"]
    return default_attention()


def attention_block(params, x, *, n_heads, n_kv_heads, cos, sin, causal=True,
                    attention_fn=None, positions=None, kv_cache=None):
    """Multi-head attention with rotary + GQA.

    params: {wq, wk, wv, wo} each [model, heads*dim] / [heads*dim, model].
    kv_cache: optional (k_cache, v_cache, cache_len) for decode; returns
    (out, new_kv_cache).
    """
    b, s, dm = x.shape
    head_dim = params["wq"].shape[1] // n_heads
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, s, n_heads, head_dim)
    k = (x @ params["wk"].astype(x.dtype)).reshape(b, s, n_kv_heads, head_dim)
    v = (x @ params["wv"].astype(x.dtype)).reshape(b, s, n_kv_heads, head_dim)
    q = apply_rotary(q, cos, sin, positions)
    k = apply_rotary(k, cos, sin, positions)

    new_cache = None
    if kv_cache is not None:
        k_cache, v_cache, cache_len = kv_cache
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, cache_len, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, cache_len, axis=1)
        k_full, v_full = k_cache, v_cache
        # mask out cache positions beyond cache_len + s
        kpos = jnp.arange(k_cache.shape[1])[None, None, None, :]
        valid = kpos < (cache_len + s)
        attn_fn = _resolve_attention(attention_fn)
        qpos = (jnp.arange(s) + cache_len)
        # causal over absolute positions
        causal_mask = kpos[:, :, :, :] <= qpos[None, None, :, None]
        out = attn_fn(q, k_full, v_full, causal=False, mask=jnp.logical_and(valid, causal_mask))
        new_cache = (k_cache, v_cache, cache_len + s)
    else:
        attn_fn = _resolve_attention(attention_fn)
        out = attn_fn(q, k, v, causal=causal)
    out = out.reshape(b, s, n_heads * head_dim)
    out = out @ params["wo"].astype(x.dtype)
    return out, new_cache


# ----------------------------------------------------------------- mlp
def swiglu_mlp(params, x):
    """Llama-style gated MLP: down(silu(gate(x)) * up(x))."""
    with jax.named_scope("dense_ffn"):
        gate = jax.nn.silu(x @ params["w_gate"].astype(x.dtype))
        up = x @ params["w_up"].astype(x.dtype)
        return (gate * up) @ params["w_down"].astype(x.dtype)


def gelu_mlp(params, x):
    """GPT2/BERT-style MLP: fc2(gelu(fc1(x)))."""
    with jax.named_scope("dense_ffn"):
        h = jax.nn.gelu((x @ params["w_fc1"].astype(x.dtype)) + params["b_fc1"].astype(x.dtype), approximate=True)
        return (h @ params["w_fc2"].astype(x.dtype)) + params["b_fc2"].astype(x.dtype)


# ------------------------------------------------------- model-family shared
def causal_lm_batch(ids):
    """Shift token ids into (input_ids, labels) next-token pairs."""
    ids = np.asarray(ids)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def count_params(init_fn) -> int:
    """Parameter count without materializing (jax.eval_shape over init)."""
    shapes = jax.eval_shape(init_fn)
    return sum(int(np.prod(np.shape(l))) for l in jax.tree_util.tree_leaves(shapes))


def init_paged_kv_pool(num_layers: int, num_kv_heads: int, head_dim: int,
                       num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    """Paged KV pool (reference inference/v2/ragged blocked KV layout):
    [L, NB, KV, bs, Dh], heads-major so the Pallas paged kernel's trailing
    (bs, Dh) tile satisfies TPU tiling.  The last block of each layer is
    reserved as a trash target for padded-token writes.

    One stacked array per K and V, layer axis first: :func:`paged_forward`
    writes and reads it where it lies (block b of layer l is row ``l * NB +
    b`` of the free ``[L * NB, KV, bs, Dh]`` view), and the engine's
    copy-on-write, its TP spec (heads on axis 2) and the benchmark's
    pool-shape reader rest on this layout."""
    shape = (num_layers, num_blocks, num_kv_heads, block_size, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}




# ------------------------------------------------------ HF state-dict helpers
def hf_tensor(state_dict, name):
    """torch tensor / array -> fp32 numpy (shared by every from_hf_state_dict)."""
    w = state_dict[name]
    return w.float().numpy() if hasattr(w, "float") else np.asarray(w, np.float32)


def hf_stack(state_dict, fmt, num_layers, dtype, transpose=True):
    """Stack one per-layer HF tensor into an [L, ...] leaf, transposing torch
    Linear [out, in] into our [in, out] unless ``transpose=False``."""
    ws = [hf_tensor(state_dict, fmt.format(i)) for i in range(num_layers)]
    return jnp.asarray(np.stack([w.T if transpose else w for w in ws]), dtype)


# -------------------------------------------------------- paged-serving shared
def paged_chunk_indices(tokens, n_tokens, start_pos, block_tables, num_blocks: int,
                        block_size: int):
    """The index scaffolding of the PADDED bucket for :func:`paged_forward`:
    maps the ragged chunk's absolute positions onto paged-KV pool coordinates.

    Returns (safe_pos [N,T], valid [N,T], lengths [N], blk [N,T], off [N,T]):
    ``blk``/``off`` address pool[blk, :, off] for each token's KV write, with
    padded tokens routed to the trash block (``num_blocks - 1``).  Every one
    of the N x T slots gets a position and a pool address, live or not; the
    compacted form takes :func:`flat_chunk_indices` instead (``lengths`` is
    the same there).
    """
    b, tchunk = tokens.shape
    trash = num_blocks - 1
    positions = start_pos[:, None] + jnp.arange(tchunk)[None, :]
    valid = jnp.arange(tchunk)[None, :] < n_tokens[:, None]
    safe_pos = jnp.where(valid, positions, 0)
    lengths = start_pos + n_tokens
    blk = jnp.take_along_axis(block_tables, safe_pos // block_size, axis=1)
    blk = jnp.where(valid, blk, trash)
    off = jnp.where(valid, safe_pos % block_size, 0)
    return safe_pos, valid, lengths, blk, off


def flat_slots(n: int, t: int, live_token_bound: Optional[int]) -> Optional[int]:
    """How many flat token slots the per-token layers of a ``[n, t]`` chunk run
    over when the caller promises at most ``live_token_bound`` live tokens, or
    None where the padded bucket is run as it is: no bound given, or the
    bucket already fits it (a decode step ``[n, 1]``, a spec verify, a full
    prefill bucket).  Decided from static shapes, so the engine's slot
    counters and its check before dispatch ask the same question the traced
    program asked."""
    if live_token_bound is None:
        return None
    slots = -(-live_token_bound // 8) * 8  # whole sublanes
    return slots if n * t > slots else None


def flat_chunk_indices(n_tokens, start_pos, block_tables, num_blocks: int,
                       block_size: int, slots: int):
    """The index scaffolding of a ragged ``[N, T]`` chunk compacted onto one
    flat token axis of ``slots`` static slots: row 0's live tokens first, then
    row 1's, and so on, the tail dead.  More live tokens than ``slots`` would
    fall off the end unseen, so the caller bounds them (the serving engine
    refuses such a step on host integers before it dispatches).

    Returns (row [S], col [S], live [S], safe_pos [S], blk [S], off [S]):
    flat slot ``j`` holds ``chunk[row[j], col[j]]``.  A dead slot reads
    ``chunk[0, 0]``, sits at position 0 and writes its KV to the trash block
    (``num_blocks - 1``); scattering back onto ``[N, T]`` it must be dropped
    (``jnp.where(live, row, N)`` under ``mode="drop"``), never written over
    the live ``[0, 0]``.
    """
    n = n_tokens.shape[0]
    ends = jnp.cumsum(n_tokens)
    j = jnp.arange(slots)
    # the row of slot j: how many rows end at or before it (N for a dead slot)
    row = jnp.sum(j[:, None] >= ends[None, :], axis=1)
    live = row < n
    row = jnp.where(live, row, 0)
    col = jnp.where(live, j - (ends - n_tokens)[row], 0)
    safe_pos = jnp.where(live, start_pos[row] + col, 0)
    blk = jnp.where(live, block_tables[row, safe_pos // block_size], num_blocks - 1)
    off = safe_pos % block_size
    return row, col, live, safe_pos, blk, off


STATE = "state"  # the key of a family's cache tree that holds its fixed per-sequence state
STATE_MIXER = "mixer"  # a layer whose parameters hold this key has no attention: ``mix`` runs it
PART_ALONE = "alone"  # a layer whose parameters hold this key touches neither cache: ``alone`` runs it
# the key of a family's cache tree that holds running tallies its forward adds to on the
# device (int32 ``[k]``): no pool leaf (the family takes it out before ``paged_forward``,
# the engine moves no block of it) and read by the host once a wave
TALLY = "tally"


class Selection(NamedTuple):
    """A family's learned selection of the cache (:func:`paged_forward`):
    ``leaf`` names the pool leaf that holds the index keys (every layer writes
    it, the kernel never attends it), ``indexer(lp, kept) -> (q_i [b, s, J, Di],
    w [b, s, J])`` is the family's own projections for the layer, and ``topk``
    how many cached tokens a query token attends."""
    leaf: str
    indexer: Callable
    topk: int


class SeqPlaces(NamedTuple):
    """Where a step's sequences lie on the ``[b, s]`` axes a mixer is handed:
    ``n_tokens`` ``[N]`` live tokens a row; ``row`` / ``col`` None for the padded
    ``[N, T]`` (row ``r`` holds sequence ``r`` from column 0) or ``[1, S]`` for
    the compacted layout (flat slot ``j`` holds token ``col[j]`` of row
    ``row[j]``'s chunk, the rows one after another)."""
    n_tokens: jax.Array
    row: Optional[jax.Array]
    col: Optional[jax.Array]


class StateRef(NamedTuple):
    """A state leaf handed to ``mix`` BY REFERENCE (:func:`paged_forward`,
    ``by_reference``): ``leaf`` the carried leaf whole and flat ``[Ls x slots,
    ...]``, ``at`` ``[N]`` the rows' slots of this layer in it (a dead row's:
    the layer's trash slot), ``begins`` ``[N]`` bool, the rows whose sequence
    begins (what their slot holds counts as zero), ``trash`` that trash slot
    (for a row that one of the family's kernels is to pass by)."""
    leaf: jax.Array
    at: jax.Array
    begins: jax.Array
    trash: jax.Array


def repeating_runs(kinds):
    """``[(start, period, repeats)]``: a list of layer kinds as runs that repeat
    a pattern of ``period`` kinds ``repeats`` times, greedily the longest run
    from each start (a run must repeat at least twice; a layer that starts none
    is a run of one).  A run is one scan of :func:`paged_forward` whose body is
    the pattern (a stack that is a tuple of stacks)."""
    out, at = [], 0
    while at < len(kinds):
        best = (1, 1)
        for period in range(1, (len(kinds) - at) // 2 + 1):
            repeats = 1
            while kinds[at + repeats * period:at + (repeats + 1) * period] == kinds[at:at + period]:
                repeats += 1
            if repeats > 1 and period * repeats > best[0] * best[1]:
                best = (period, repeats)
        out.append((at, *best))
        at += best[0] * best[1]
    return out


def layers_of_one_expert_stack(runs, segments):
    """A family whose experts are ONE stack over all layers (``[layers, held,
    ...]``) hands every layer its index into it: ``segments`` (one tuple of
    per-position stacks a run of ``runs`` = :func:`repeating_runs`) with
    ``lp["moe"]["layer"]`` ``[repeats]`` added, the list :func:`paged_forward`
    takes as ``layers``."""
    return [tuple({**lp, "moe": {**lp["moe"], "layer": start + j + jnp.arange(
        0, repeats * period, period, dtype=jnp.int32)}} for j, lp in enumerate(segment))
            for (start, period, repeats), segment in zip(runs, segments)]


def tp_psum(tp_axis: Optional[str]):
    """What a family's ``finish`` does with a row-parallel partial: the psum
    over ``tp_axis`` inside shard_map, nothing on one chip."""
    return (lambda y: jax.lax.psum(y, tp_axis)) if tp_axis else (lambda y: y)


def paged_forward(layers, tokens, n_tokens, start_pos, block_tables, kv_cache, *,
                  block_size: int, live_token_bound: Optional[int], last_rows: bool = False,
                  embed: Callable, qkv: Callable, finish: Callable, head: Callable,
                  window: Optional[int] = None, alibi_slopes=None,
                  softmax_scale: Optional[float] = None, value_dim: Optional[int] = None,
                  mix: Optional[Callable] = None, selection: Optional[Selection] = None,
                  hand_on: bool = False, by_reference=None, alone: Optional[Callable] = None):
    """The one ragged chunked forward over the paged KV pool (FastGen
    model-forward analog, inference/v2/model_implementations + blocked flash):
    every family's ``forward_paged`` is its own arithmetic as four callables
    plus one call of this.  It alone under ``models/`` knows where a step's
    tokens lie, where the pool lies and how a layer writes it, and what the
    paged kernel is handed.

    A family's ``forward_paged(config, params, tokens, n_tokens, start_pos,
    block_tables, kv_cache, *, block_size, tp_axis=None, gather_logits=True,
    live_token_bound=None, last_rows=False)`` is the contract the serving engine
    calls, the same keywords for every module: tokens [N, T] (right-padded chunks),
    n_tokens [N] valid counts, start_pos [N] absolute start of this chunk,
    block_tables [N, MAXB] (padded entries point at the trash block); returns
    (logits [N, T, V], new kv_cache), or with ``last_rows`` (logits [N, 1, V],
    new kv_cache).  ``tp_axis`` names the mesh axis of the
    enclosing shard_map (params column/row-sharded per the family's tp rules,
    the pool sharded on its heads; head counts come from the local shapes, so
    one code serves one chip and a TP shard); ``gather_logits=False`` leaves a
    vocab-parallel head's logits local for a greedy pick.  The callables,
    closed over the family's config, params and ``tp_axis``:

    - ``embed(tokens, safe_pos) -> x`` ``[b, s, D]`` in the pool's dtype;
    - ``qkv(lp, x, safe_pos) -> (q, k, v, kept)``: the layer's norm,
      projections, biases, rotary or none, QK-norm; ``q`` ``[b, s, H, Dh]``,
      ``k``/``v`` ``[b, s, KV, Dh]`` (local heads); ``kept`` is whatever the
      family wants back (the normed ``h`` of a parallel residual, or None).
      Between ``q`` and ``kept`` stands one row a leaf of the family's pool,
      in the order of its leaves: ``(q, latent, kept)`` for a pool of one;
    - ``finish(lp, x, kept, attn, live) -> x``: ``wo`` over ``attn``
      ``[b, s, H, Dh]``, the residual form, the FFN, its psums and post-psum
      biases; ``live`` is the ``[b, s]`` mask of slots that hold a token;
    - ``head(x) -> logits``: final norm, tied or untied head, bias, TP gather;

    and the facts the kernel needs: ``window`` (Mistral's sliding window: one
    value for every layer; or, for a family whose layers differ in it, a LIST
    shaped like ``layers``: a stack's one value, a period's tuple of one value a
    layer of the period, None for a layer that attends its whole past, so a
    layer's window is its place in the period and static in its trace; the
    kernel's walk begins at the first block a windowed layer's queries can see,
    ``ops/attention/paged.py``; the layer's kernel and projections then lie under
    the scope ``attn_window`` or ``attn_full``),
    ``alibi_slopes`` ([H] local heads, BLOOM), and for a family whose scores
    are not ``q . k / sqrt(Dh)`` over a pool of K and a pool of V its
    ``softmax_scale`` (None: one over the root of q's width) and
    ``value_dim`` (a latent pool: the value is the first ``value_dim`` columns
    of the one cached vector, which the kernel reads once).  ``[b, s]`` is
    ``[N, T]`` or, compacted, ``[1, S]``: a family never asks which.

    ``layers`` is the stacked per-layer parameters ``[L, ...]``, or a list of
    such stacks where the layers are not all alike (a leading dense layer,
    then the expert layers: DeepSeek-V2).  Each stack is one scan, run in
    order with the pool and the layer's index carried from one into the next;
    the callables tell a stack's layers by what ``lp`` holds.  A stack that is
    a TUPLE of stacks is one period of a layer pattern (attention, conv, conv,
    conv): the scan runs over the periods and its body runs the period's
    layers in order, so layers of several kinds share one scan.

    **A learned selection of the cache** (``selection``, a :class:`Selection`:
    DeepSeek sparse attention, GLM-5's ``glm_moe_dsa``).  A query token then
    attends only ``selection.topk`` of the cached tokens of its sequence (all of
    them where it has no more), chosen a layer by the family's *indexer*.  The
    pool gains a leaf that is WRITTEN a token as any other leaf and NEVER
    ATTENDED: ``selection.leaf`` names it (``[L, NB, 1, bs, Di]``, a token's
    index key; its width need not be another leaf's), ``qkv`` returns its row in
    the leaf's place among the rows, and the kernel is handed the other leaves
    (K and V, or the one latent leaf with ``value_dim``) exactly as without a
    selection.  After the write, so that a chunk's own tokens and every earlier
    chunk's are scored from the pool alike, ``selection.indexer(lp, kept)`` gives
    the layer's index queries and head weights for the step's tokens, in
    whichever layout they lie, and ``ops/attention/dsa.py select_keys`` turns
    them and the index-key leaf (walked through the same offset block table as
    the kernel's pool) into each token's set: the exact top-k of ``sum_j w_j
    relu(q_j . k_s)`` over ``s <= position``, ties to the lower position, as a
    mask over the sequence's positions that ``paged_attention`` /
    ``paged_attention_flat`` take as ``selection``.  One path for chunks (padded
    or compacted), decode steps and a burst's body; with no ``selection`` given
    nothing of it is traced.

    **Layers without attention** (``STATE_MIXER`` among ``lp``'s keys: LFM2's
    gated short convolutions, Qwen3-Next's Gated DeltaNet, Granite 4.0-H's
    Mamba-2, Ling-3.0's Kimi Delta Attention).  Such a layer
    touches neither the pool nor the write plan nor the kernel: ``mix(lp, x,
    filtered, live, carried, places) -> (x, carried)`` is the whole layer, and what
    it remembers of a sequence's past is a fixed state a SEQUENCE, not rows a
    token: ``kv_cache[STATE]``, a TREE OF LEAVES ``[Ls, slots + 1, ...]`` (one
    array, or a dict of arrays of any trailing shapes and dtypes: a shift's
    last values ``[.., k, D]``, a recurrence's matrix a head ``[.., H, dk,
    dv]`` in float32; ``Ls`` such layers; the last slot is the trash slot of a
    dead row) beside the pool's leaves, carried through the scans as they are
    and written in place.  The engine names each row's slot in one further,
    trailing column of ``block_tables``.  ``carried`` is the same tree with
    every leaf ``[N, ...]``: the rows' own slots of this layer, zeros where
    ``start_pos == 0`` (a slot is never zeroed in memory: a sequence that starts
    over simply does not read it); what ``mix`` returns in its place goes back
    to the rows' slots, a dead row's to the trash slot.  What only this function
    can give, since it alone knows where a step's tokens lie, comes beside it:

    - ``filtered(z, kept, w, bias=None) -> (out, last)`` for a leaf that is a
      shift, the short causal filter that reads it: for ``z`` ``[b, s, D]`` in
      either layout, ``kept`` ``[N, k, D]`` (the carried leaf) and ``w`` ``[k +
      1, D]`` (its last row weighs the token itself) ``out`` like ``z`` in
      float32, every token filtered over its own sequence's ``k`` earlier
      values, from the chunk itself where the chunk has them and from ``kept``
      where it does not (a row's first ``k`` tokens), and ``last`` ``[N, k,
      D]``, the leaf's new value: the chunk's last ``k`` values (a chunk of one
      token shifts it).  No shifted copy of ``z`` is made: compacted, the pass
      reads ``z`` once and ``kept`` touches ``N x k`` slots
      (:func:`sequence_filter`); the activation, norms and casts around the
      filter are the family's;
    - ``places`` (:class:`SeqPlaces`) for a leaf that is a recurrence over the
      step's tokens: ``n_tokens`` and, compacted, whose token each flat slot
      holds, so that the family's scan can walk each sequence from its own
      carried matrix (several sequences a pass, each continuing from its slot;
      a decode row is a chunk of one token; a fused burst carries the state in
      its loop, as it does the pool).

    That is a leaf BY VALUE: a read of the rows' slots, a select and a scatter
    around ``mix``, each a pass over the rows, which suits a few rows of filter
    taps (``filtered`` reads them for a row's first ``k`` tokens alone).  A leaf
    whose kernels index the slots themselves goes BY REFERENCE: ``by_reference`` is the family's
    statement, in its code, of which leaves those are (a tree of bools shaped
    like ``kv_cache[STATE]``; None: none).  For such a leaf nothing is read,
    selected or scattered here: ``carried`` holds in its place a
    :class:`StateRef` (the carried leaf whole and flat ``[Ls x slots, ...]``,
    the rows' slots ``at`` ``[N]`` in it, a dead row's the trash slot of the
    layer, ``begins`` ``[N]``, ``start_pos == 0``, and that trash slot), and what ``mix``
    returns in its place is the NEW FLAT LEAF, which goes back into the layer
    scan's carry as it is: the rows' slots updated (a beginning row's from
    zeros), every other slot as it was (Granite's ``ssm``: 4 MB a row a layer,
    read and written once, inside ``ssd_update`` / ``ssd_scan``; Ling-3.0's
    ``recurrent``: 2 MB, inside ``kda_update`` / ``kda_scan``).

    The pool's row is counted over the attention layers alone, the state's over
    the mixers (a layer that touches neither, below, counts in neither), in whatever order the two kinds lie within a period or across
    stacks: a pool ``[L_attention, ...]`` of ANY leaves (K and V; or ONE latent
    leaf with ``value_dim``, as Ling-3.0's ``bailing_hybrid``: one layer in six
    attends a latent, five keep a matrix by reference and a shift by value) and
    state leaves ``[L_mixer, slots + 1, ...]`` lie side by side in one
    ``kv_cache`` and one scan's carry; ``softmax_scale``, ``value_dim`` and
    ``window`` are the attention layers' alone and a mixer never sees them
    (``tests/unit/inference/test_latent_pool_beside_state.py`` holds this with a
    toy family of its own).

    **Layers that touch neither cache** (``PART_ALONE`` among ``lp``'s keys:
    Nemotron-H's expert layers, where a layer is ONE part alone, a mixer OR a
    feed-forward part, and not a mixer then an FFN).  ``alone(lp, x, live) -> x``
    is the whole layer (its norm, its part, its residual, under the family's own
    scopes).  It remembers nothing of a sequence: it takes NO STATE ROW AND NO
    POOL ROW, reads no slot, writes no block, and neither kind's row count moves
    past it, so a stack of 6 mixers, 6 such layers and 2 attention layers carries
    state leaves ``[6, slots + 1, ...]`` and a pool ``[2, NB, ...]`` (run through
    ``mix`` it would hold a state row a layer that nothing reads).  It lies in a
    period beside the other two kinds in any order (``M E M E M * E``), padded,
    compacted and in a burst's body alike, or makes a stack of its own
    (``tests/unit/inference/test_part_alone_layer.py`` holds this with a toy
    family of its own).  Under ``hand_on`` it is ``alone(lp, x, live, handed) ->
    (x, handed)`` and stands in the period's chain like an attention layer's
    ``finish``.

    **A hand-on inside a period** (``hand_on``: LongCat-Flash's shortcut expert
    layer, computed from the first sublayer's normed stream and added at the end
    of the second).  ``finish`` is then ``finish(lp, x, kept, attn, live, handed)
    -> (x, handed)``: whatever tree of arrays an attention layer's ``finish``
    returns beside ``x`` is handed, as it is, to the ``finish`` of the NEXT
    attention layer of the SAME period (same scan step, same layout, padded or
    compacted, in a burst's body as anywhere); a period's first layer is handed
    None.  The hand-on ends with the period: it is no part of the scan's carry,
    so nothing reaches a later period through it.  What the period's LAST layer
    hands on is handed to no layer: it leaves the scan, stacked a period
    ``[depth, ...]``, and this function returns ``(logits, cache, [that, a
    stack of ``layers``])``: a family's per-period by-product (its tallies),
    or None where it has none.  A mixer layer is passed over; a layer alone
    (above) takes and hands on like a ``finish``.  Without
    ``hand_on`` (every other family) ``finish`` keeps its five arguments and
    one result, and nothing of this is traced.

    ``kv_cache`` is whatever tree of ``[L, NB, KV, bs, width]`` leaves the
    family's ``init_paged_cache`` made (``{"k", "v"}``; one latent leaf for
    MLA; a latent leaf and a narrower leaf of index keys for a family with a
    ``selection``), in and out.  The
    layer scan CARRIES the pools whole beside the activations (its ``xs`` is
    ``layers``, the stacked per-layer parameters, and the layer's index); each
    layer writes this step's rows into the carried stack in place
    (``ops/attention/kv_write.py``: on the TPU a Pallas writer, a 16-row tile
    of a block for every KV head at a time from a list of the tiles the pass
    touches, made once here from ``n_tokens``, ``start_pos`` and the tables,
    and nothing for a dead slot; off it a scatter, one index per (token, KV
    head), a dead slot's row into the layer's trash block, its last) and
    hands the paged kernel the stack as one pool of ``L * NB`` blocks, with
    the block table offset by the layer's first row ``l * NB``: the kernel
    knows nothing of layers.  No layer is ever cut out of the pool or stacked
    back, so a jitted caller that donates ``kv_cache`` (or carries it through
    a loop of its own, as the fused burst does) runs with the one pool it was
    given and no copy of it.

    ``live_token_bound``: the caller's promise that ``sum(n_tokens)`` never
    passes it (the serving engine hands its scheduler's ``token_budget``).
    Where the bucket holds more slots than that (``flat_slots``, from the
    static shapes: a mixed SplitFuse step of one 225-token chunk beside 31
    decode rows is ``[32, 256]`` = 8,192 slots for 256 live tokens), the chunk
    is compacted onto one flat axis of S slots and everything that is per
    token (all four callables) runs over ``[1, S, ...]``, attention too: the
    paged kernel takes ``q`` from the flat axis and returns its output there
    (``paged_attention_flat``: a sequence's rows found by an offset that is
    data), so nothing of the padded ``[N, T]`` size is built inside the layer
    scan.  With None, or where the bucket fits the bound (decode ``[N, 1]``, a
    burst body, a spec verify), every slot of the bucket is computed and the
    trace is the padded one.

    ``last_rows``: the caller's statement that it reads each row's last live
    token alone (the serving engine's step does: one sampled token a sequence;
    a speculative verify and the benchmark's references read every position and
    leave it False).  The N rows ``x[n, n_tokens[n] - 1]`` (compacted: row n's
    last token is flat slot ``cumsum(n_tokens)[n] - 1``) are then taken BEFORE
    the head, which runs over ``[N, 1, D]`` and returns ``[N, 1, V]``: no head
    over the other slots, nothing of the size ``[N, T, V]``.  A row with no
    token returns some live row's logits (a finite row nobody reads).  False:
    every position's logits ``[N, T, V]``, a compacted pass's scattered once
    after the scan, zero wherever no live token sits.

    Attention runs in the Pallas paged kernel (ops/attention/paged.py) on TPU:
    only live blocks are read via scalar-prefetched table indices; off-TPU the
    identical-math dense-gather fallback runs.  One grid step of it holds a
    (sequence, a few consecutive table slots): their blocks fetched once for all
    the step's KV heads, every q head that reads them stacked into the rows of
    one product over all the step's keys, and no arithmetic for the rows of the
    padded ``[N, T]`` that hold no token.  How many KV heads and table slots a
    step holds the kernel decides from the shapes it is handed here against one
    VMEM budget (``paged.step_tile``); nothing is passed for it
    (:func:`paged_step_slots` works the same choice out for the engine's counters)."""
    from ..ops.attention.kv_write import kv_write, write_plan
    from ..ops.attention.paged import paged_attention, paged_attention_flat

    if selection is not None:  # where its leaf lies among the pool's (a dict's leaves: by name)
        from ..ops.attention.dsa import select_keys
        index_leaf = sorted(k for k in kv_cache if k != STATE).index(selection.leaf)
    n, t = tokens.shape
    state_leaves = []
    if isinstance(kv_cache, dict) and STATE in kv_cache:
        kv_cache = dict(kv_cache)
        state_leaves, state_tree = jax.tree_util.tree_flatten(kv_cache.pop(STATE))
        state_slots = state_leaves[0].shape[1]
        by_ref = ([False] * len(state_leaves) if by_reference is None
                  else state_tree.flatten_up_to(by_reference))
        # the rows' state slots ride as the table's last column; a dead row's is the trash slot
        seq_slot = jnp.where(n_tokens > 0, block_tables[:, -1], state_slots - 1)
        block_tables = block_tables[:, :-1]
    pool_leaves, pool_tree = jax.tree_util.tree_flatten(kv_cache)
    pool_shape = pool_leaves[0].shape
    num_blocks = pool_shape[1]
    slots = flat_slots(n, t, live_token_bound)
    if slots is None:
        # the padded bucket as it is: the per-token layers see [N, T]
        safe_pos, live, lengths, blk, off = paged_chunk_indices(
            tokens, n_tokens, start_pos, block_tables, num_blocks, block_size)
        to_padded = lambda a: a
        row = col = None
    else:
        # the live tokens on one flat axis: the per-token layers see [1, S]
        row, col, live, safe_pos, blk, off = (a[None] for a in flat_chunk_indices(
            n_tokens, start_pos, block_tables, num_blocks, block_size, slots))
        lengths = start_pos + n_tokens
        tokens = tokens[row, col]
        drop_row = jnp.where(live, row, n)[0]  # out of bounds: a dead slot lands nowhere

        def to_padded(a):  # [1, S, ...] -> [N, T, ...], zero wherever no live token sits
            return jnp.zeros((n, t) + a.shape[2:], a.dtype).at[drop_row, col[0]].set(
                a[0], mode="drop")

    # The named scopes of this function and of its helpers (``embed``,
    # ``attn_qkv``, ``kv_write``, ``attn_kernel``, ``layer_finish``, ``mixer_layer``,
    # ``head``; a family's own inside them) are metadata on the operations and change
    # nothing that is compiled: ``monitor/program_scopes.py`` reads them off the
    # executable, so that a device trace's busy time can be told apart by them.
    with jax.named_scope("embed"):
        x = embed(tokens, safe_pos)
    # which tiles of the pool this pass writes, the same in every layer (None
    # off the TPU: the scatter's one index per (token, head) writes instead)
    flat_pools = [leaf.reshape((-1, ) + leaf.shape[2:]) for leaf in pool_leaves]
    with jax.named_scope("kv_write"):
        plan = write_plan(flat_pools, n_tokens, start_pos, block_tables, t=t, slots=slots)

    stacks = layers if isinstance(layers, list) else [layers]
    by_layer = isinstance(window, list)  # the family told one a layer, shaped like ``layers``
    windows = window if by_layer else [window] * len(stacks)

    def kind_scope(window):  # the layer's kind, where layers differ in it
        if not by_layer:
            return contextlib.nullcontext()
        return jax.named_scope("attn_full") if window is None else jax.named_scope("attn_window")

    def attention_layer(x, pools, lp, l, window, handed=None):
        with jax.named_scope("attn_qkv"), kind_scope(window):
            q, *rows, kept = qkv(lp, x, safe_pos)
        # this step's rows, in place: pool[l*NB + blk, h, off] = k[n, t, h]
        first = l * num_blocks  # the layer's first row of the flat stack
        with jax.named_scope("kv_write"):
            pools = kv_write(pools, rows, first, blk, off, plan)
        # the kernel takes the flat stack as it would one layer's pool (a Pallas
        # operand is materialised, so kpool[l] would be a copy): the table is offset
        facts = dict(block_size=block_size, softmax_scale=softmax_scale, window=window,
                     alibi_slopes=alibi_slopes, value_dim=value_dim)
        attended = pools
        with jax.named_scope("attn_kernel"), kind_scope(window):
            if selection is not None:
                # the index-key leaf is scored, not attended: the kernel's pools are the others
                q_i, w = selection.indexer(lp, kept)
                if slots is not None:  # the flat tokens as they lie
                    q_i, w = q_i[0], w[0]
                facts["selection"] = select_keys(
                    q_i, w, pools[index_leaf], block_tables + first, start_pos, n_tokens,
                    topk=selection.topk, chunk=None if slots is None else t)
                attended = [pool for i, pool in enumerate(pools) if i != index_leaf]
            kpool, vpool = attended if value_dim is None else (attended[0], None)
            if slots is None:
                attn = paged_attention(q, kpool, vpool, block_tables + first, lengths, start_pos,
                                       n_tokens, **facts)
            else:  # q as it lies on the flat axis: the kernel finds a sequence's rows by an offset
                attn = paged_attention_flat(q[0], kpool, vpool, block_tables + first, lengths,
                                            start_pos, n_tokens, chunk=t, **facts)[None]
        with jax.named_scope("layer_finish"):
            if not hand_on:
                return finish(lp, x, kept, attn, live), pools
            x, handed = finish(lp, x, kept, attn, live, handed)
        return x, pools, handed

    def mixer_layer(x, flat_states, lp, l):
        """A layer whose past is its sequences' slots ``[N, ...]`` of each flat state leaf."""
        if not state_leaves or mix is None:
            raise ValueError(f"a layer holds {STATE_MIXER!r} and the family gave no "
                             f"{'mix' if mix is None else 'kv_cache[STATE]'}")
        at = l * state_slots + seq_slot
        with jax.named_scope("seq_state"):  # a leaf by reference: whole, with where its rows lie
            kept = [StateRef(leaf, at, start_pos == 0, (l + 1) * state_slots - 1) if ref else
                    jnp.where((start_pos > 0).reshape((-1, ) + (1, ) * (leaf.ndim - 1)), leaf[at], 0)
                    for leaf, ref in zip(flat_states, by_ref)]
        with jax.named_scope("mixer_layer"):  # the family's own (``ssm_mixer``, its FFN) inside it
            x, carried = mix(lp, x, filtered, live, jax.tree_util.tree_unflatten(state_tree, kept), places)
        with jax.named_scope("seq_state"):
            return x, [new if ref else leaf.at[at].set(new.astype(leaf.dtype))
                       for leaf, new, ref in zip(flat_states, state_tree.flatten_up_to(carried), by_ref)]

    # The pool is carried, never sliced (xs) and restacked (ys): a scan's ys is
    # a new [L, ...] array that cannot alias a donated argument still being
    # read, which cost a slice, an update and a copy of the whole pool a pass.
    places = SeqPlaces(n_tokens, row, col)
    filtered = lambda z, kept, w, bias=None: sequence_filter(z, kept, w, bias, *places)
    carry = (x, *flat_pools, *(leaf.reshape((-1, ) + leaf.shape[2:]) for leaf in state_leaves))
    done = {False: 0, True: 0}  # attention / mixer layers behind the stack being scanned (None: neither)
    left_over = []  # with ``hand_on``: what each stack's periods handed to no layer
    for stack, here in zip(stacks, windows):
        period = stack if isinstance(stack, tuple) else (stack, )
        here = here if isinstance(here, tuple) else (here, ) * len(period)  # a layer's own, by its place
        # by what a layer's parameters hold: a mixer, attention, or (None) a part alone
        mixes = [None if PART_ALONE in lp else STATE_MIXER in lp for lp in period]
        depth = jax.tree_util.tree_leaves(period[0])[0].shape[0]
        # each kind's index of a period's first layer of that kind: the pool's and the state's row
        # (no step where it is 1: a given step compiles every older family's program anew)
        firsts = {kind: jnp.arange(done[kind], done[kind] + depth * mixes.count(kind),
                                   *([mixes.count(kind)] if mixes.count(kind) > 1 else []),
                                   dtype=jnp.int32)
                  for kind in set(mixes) - {None}}

        def body(carry, inp, mixes=mixes, here=here):
            (x, *pools), (lps, first) = carry, inp
            handed = None  # a period begins with nothing handed, and what it ends with leaves the scan
            for j, (lp, is_mix) in enumerate(zip(lps, mixes)):
                if is_mix is None:  # no row of either cache: nothing but the stream goes in or out
                    if alone is None:
                        raise ValueError(f"a layer holds {PART_ALONE!r} and the family gave no alone")
                    if hand_on:
                        x, handed = alone(lp, x, live, handed)
                    else:
                        x = alone(lp, x, live)
                    continue
                behind = mixes[:j].count(is_mix)  # layers of its kind before it in the period
                l = first[is_mix] + behind if behind else first[is_mix]
                if is_mix:
                    x, pools[len(flat_pools):] = mixer_layer(x, pools[len(flat_pools):], lp, l)
                elif hand_on:
                    x, pools[:len(flat_pools)], handed = attention_layer(
                        x, pools[:len(flat_pools)], lp, l, here[j], handed)
                else:
                    x, pools[:len(flat_pools)] = attention_layer(x, pools[:len(flat_pools)], lp, l,
                                                                 here[j])
            return (x, *pools), handed

        carry, left = jax.lax.scan(body, carry, (period, firsts))
        left_over.append(left)
        for kind in firsts:
            done[kind] += depth * mixes.count(kind)
    x, *pools = carry
    cache = jax.tree_util.tree_unflatten(
        pool_tree, [pool.reshape(leaf.shape) for pool, leaf in zip(pools, pool_leaves)])
    if state_leaves:
        cache[STATE] = jax.tree_util.tree_unflatten(state_tree, [
            flat.reshape(leaf.shape) for flat, leaf in zip(pools[len(flat_pools):], state_leaves)])
    by_product = (left_over, ) if hand_on else ()
    with jax.named_scope("head"):
        if not last_rows:
            return (to_padded(head(x)), cache) + by_product
        if slots is not None:  # the rows lie one after another: row n ends where the first n + 1 counts do
            last = x[0, jnp.clip(jnp.cumsum(n_tokens) - 1, 0, slots - 1)][:, None]
        elif t > 1:
            last = jnp.take_along_axis(x, jnp.maximum(n_tokens - 1, 0)[:, None, None], axis=1)
        else:  # a decode step: the one slot a row has
            last = x
        return (head(last), cache) + by_product


def paged_step_slots(module, config, kv_cache, q_dtype, tp: int = 1):
    """``(t -> slots, (n, flat) -> positions)`` for the engine's counters, from
    the shapes :func:`paged_forward` hands the kernel: the family's ``num_heads``
    and its pool's KV heads, a ``tp``-th of each (KV heads that ``tp`` does not
    divide are held whole by every shard: ``inference/v2/tp.py kv_pool_spec``),
    the pool's block and width, and, where the module states a
    ``paged_value_dim``, the value's width inside the key.  The first: the table
    slots one grid step of the paged kernel takes in a ``[n, t]`` program
    (``paged.step_tile``; ``ServeCounters.kernel_steps`` counts the kernel's grid
    with it).  The second: the token positions of the kernel's flat row axis in
    a compacted pass of ``flat`` slots over ``n`` sequences
    (``paged.flat_token_slots``; ``ServeCounters.attn_token_slots``)."""
    from ..ops.attention.paged import flat_token_slots, step_tile
    # a state; running tallies; a leaf of index keys
    unattended = (STATE, TALLY, getattr(module, "PAGED_SELECT_LEAF", None))
    pool = jax.tree_util.tree_leaves({k: v for k, v in kv_cache.items() if k not in unattended})[0]
    (_, _, kvh, bs, width), pool_dtype = pool.shape, pool.dtype  # the array itself is not kept
    value_dim = getattr(module, "paged_value_dim", lambda config: None)(config)
    heads, local_kvh = config.num_heads // tp, kvh // tp if kvh % tp == 0 else kvh

    @functools.lru_cache(maxsize=None)
    def slots(t: int) -> int:
        return step_tile(t, heads, local_kvh, width, bs, q_dtype, pool_dtype, value_dim)[-1]

    return slots, lambda n, flat: flat_token_slots(n, flat, heads // local_kvh)


def sequence_filter(z, kept, w, bias, n_tokens, row, col):
    """The depth-wise causal filter of :func:`paged_forward`'s ``filtered``, local
    to a sequence.  ``z`` ``[b, s, D]`` in the padded layout ``[N, T]`` (``row``
    None) or the compacted ``[1, S]`` (``row`` / ``col`` ``[1, S]``: whose token a
    flat slot holds); ``kept`` ``[N, k, D]`` the rows' remembered values, oldest
    first; ``w`` ``[k + 1, D]`` whose LAST row weighs the token itself.  Returns
    ``(out, last)``: ``out`` like ``z`` in float32, ``sum_i w[i] z_{t - k + i}``
    (the token itself first, then the earlier ones oldest first, then ``bias``,
    each product and sum in float32), and ``last`` ``[N, k, D]`` the rows' new
    remembered values: the last ``k`` of what was kept followed by the chunk's
    live tokens (a row with no token keeps what it had).

    Compacted, the rows lie one after another on the flat axis, so token ``t -
    back`` of a row is flat slot ``j - back`` wherever the row has that many
    tokens before it: ONE pass over ``z``, its earlier taps slices of the chunk
    itself.  ``kept`` matters to a row's first ``k`` tokens alone: those ``N x k``
    slots are filtered apart, from ``kept`` and the row's first tokens, and set
    in place (what the pass read across a row's edge there is written over)."""
    k = kept.shape[1]
    kept, w = kept.astype(z.dtype), w.astype(jnp.float32)

    def over(whole, t):  # ``whole`` [.., k + t, D]: position p of it is token p - k
        out = w[-1] * whole[..., k:, :].astype(jnp.float32)
        for i in range(k):
            out = out + w[i] * whole[..., i:i + t, :].astype(jnp.float32)
        return out if bias is None else out + bias.astype(jnp.float32)

    if row is None:
        whole = jnp.concatenate([kept, z], axis=1)  # [N, k + T, D]
        pick = n_tokens[:, None] + jnp.arange(k)[None, :]  # the k before token n_tokens
        return over(whole, z.shape[1]), jnp.take_along_axis(whole, pick[:, :, None], axis=1)
    flat = z[0]
    s = flat.shape[0]
    out = over(jnp.pad(flat, ((k, 0), (0, 0))), s)
    ends = jnp.cumsum(n_tokens)
    j = jnp.arange(k)[None, :]
    at = (ends - n_tokens)[:, None] + j  # [N, k]: the rows' first k slots
    first = over(jnp.concatenate([kept, flat[jnp.clip(at, 0, s - 1)]], axis=1), k)
    # out of bounds where the row has no such token: it lands nowhere
    out = out.at[jnp.where(j < n_tokens[:, None], at, s)].set(first, mode="drop")
    last = []
    for back in range(k, 0, -1):  # the value `back` before the row's next token
        in_chunk = flat[jnp.clip(ends - back, 0, s - 1)]
        from_kept = kept[jnp.arange(kept.shape[0]), jnp.clip(k + n_tokens - back, 0, k - 1)]
        last.append(jnp.where((n_tokens >= back)[:, None], in_chunk, from_kept))
    return out[None], jnp.stack(last, axis=1)


# ----------------------------------------------------------------- losses
def cross_entropy_loss(logits, labels, ignore_index=-100, z_loss=0.0):
    """Token cross entropy with masking; logits [B,S,V], labels [B,S] int."""
    logits = logits.astype(jnp.float32)
    mask = labels != ignore_index
    safe_labels = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    loss = nll.sum() / jnp.maximum(mask.sum(), 1)
    if z_loss > 0.0:
        loss = loss + z_loss * jnp.mean((logz * mask)**2)
    return loss


def init_linear(key, in_dim, out_dim, scale=None, dtype=jnp.float32):
    # a Python float, so the product keeps ``dtype``: a numpy scalar here
    # promoted bf16 weights to float32 (twice the bytes at 4096 width)
    scale = float(scale if scale is not None else 1.0 / np.sqrt(in_dim))
    return jax.random.normal(key, (in_dim, out_dim), dtype) * scale
