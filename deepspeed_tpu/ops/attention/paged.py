"""Pallas TPU paged (blocked-KV) attention for ragged serving.

Replaces the dense block-table gather the v2 engine shipped with (the analog of
the reference's blocked flash kernels, inference/v2/kernels/ragged_ops/
blocked_flash + linear_blocked_kv_rotary): instead of gathering every
sequence's whole block table into a dense [N, MAXB*bs, KV, Dh] context (HBM
traffic O(MAXB) regardless of actual length), the kernel walks each sequence's
block table with **scalar-prefetched indices** — the block index feeds the KV
BlockSpec index_map, so only blocks below the sequence's live length are ever
read, with online-softmax accumulation across blocks.

Layout: q [N, T, H, Dh] (T = SplitFuse chunk, 1 at decode); KV pool
[NB, KV, bs, Dh] (one layer's pool — heads-major so the (bs, Dh) tile is the
trailing pair, as the TPU lowering requires); tables [N, MAXB] int32 (padded
entries may point anywhere — never read past ``lengths``); lengths [N] = live
context per sequence (including this chunk); start_pos/n_tokens [N] describe
the chunk's absolute query positions.  Causality is absolute-position based so
chunked prefill and decode share one kernel.  The kernel knows nothing of
layers: ``models.transformer.paged_forward``, its one caller under ``models/``,
hands it every layer's pool as one [L*NB, KV, bs, Dh] and tables offset by
``l*NB`` (a slice ``pool[l]`` handed to a Pallas call would be a copy of it).

GQA maps q-head -> kv-head in the index_map.  Off-TPU falls back to the dense
gather + masked sdpa (identical math; tests compare the two).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...compat import CompilerParams
from .. import _pallas
from .._pallas import use_pallas as _use_pallas

NEG_INF = -1e30

# Scalar memory (SMEM) of one TensorCore, and what the kernel's own scalars and
# the compiler keep of it.  Source: the v5e compiler (libtpu 0.0.34) asked
# through a described topology — "Ran out of memory in memory space smem. Used
# 1.01M of 1.00M" for a [512, 512] table, and the padded sizes it names for
# other shapes (rows to 8 — to 1, 2 or 4 under that —, columns to 128, 4 bytes
# each; the per-sequence vectors and ~1.1 KiB of the compiler's own on top).
SMEM_BYTES = 1 << 20
_SMEM_RESERVE = 2048


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def check_block_table_fits(n: int, maxb: int, n_vectors: int = 3) -> None:
    """The block table and the per-sequence vectors ride into the kernel as
    scalar-prefetch operands, so they must fit scalar memory whole.  Raise a
    readable error here instead of an XLA resource error mid-serve."""
    rows = _round_up(n, 8) if n > 4 else (1, 1, 2, 4, 4)[n]
    table = rows * _round_up(maxb, 128) * 4
    need = table + n_vectors * _round_up(n, 128) * 4 + _SMEM_RESERVE
    if need > SMEM_BYTES:
        raise ValueError(
            f"paged_attention: block table [{n}, {maxb}] int32 needs {table} bytes of "
            f"scalar memory (padded to {rows} x {_round_up(maxb, 128)}) plus "
            f"{need - table} for the per-sequence scalars; the chip has {SMEM_BYTES}. "
            f"Serve fewer sequences per step, a shorter max context "
            f"(max_blocks_per_seq), or a larger KV block size "
            f"(n_seqs x max_blocks_per_seq must stay under ~{SMEM_BYTES // 4}).")


def _paged_kernel(tables_ref, lengths_ref, start_ref, ntok_ref, *rest,
                  scale, block_size, t_pad, window, alibi):
    if alibi:
        slopes_ref, q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc = rest
    else:
        q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc = rest
    n, h, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(b == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    length = lengths_ref[n]

    @pl.when(b * block_size < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [T, Dh]
        k = k_ref[0, 0].astype(jnp.float32)  # [bs, Dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # [T, bs]
        kpos = b * block_size + jax.lax.broadcasted_iota(jnp.int32, (t_pad, block_size), 1)
        t_iota = jax.lax.broadcasted_iota(jnp.int32, (t_pad, block_size), 0)
        qp = start_ref[n] + t_iota  # absolute query positions
        if alibi:
            # ALiBi key-only form: slope_h * absolute key index (softmax-
            # equivalent to the relative-distance form per query row —
            # models/bloom.py docstring; HF build_alibi_tensor)
            s = s + slopes_ref[h] * kpos.astype(jnp.float32)
        mask = (kpos <= qp) & (kpos < length) & (t_iota < ntok_ref[n])
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qp - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:, 0:1] = l_sc[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[:, 0:1] = m_new
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(b == nb - 1)
    def _finalize():
        l = l_sc[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / l_safe).astype(o_ref.dtype)


def paged_attention(q, kpool, vpool, tables, lengths, start_pos, n_tokens, *,
                    block_size: int, softmax_scale: Optional[float] = None,
                    window: Optional[int] = None, alibi_slopes=None):
    """q [N, T, H, Dh]; kpool/vpool [NB, KV, bs, Dh]; tables [N, MAXB] int32;
    lengths/start_pos/n_tokens [N] int32.  Returns [N, T, H, Dh] (rows at
    t >= n_tokens[n] are zero).  ``window`` = sliding-window size (Mistral);
    ``alibi_slopes`` [H] f32 adds slope_h * key_index to the scores (BLOOM —
    reference serves ALiBi through its softmax op's alibi path,
    ops/transformer/inference/op_binding/softmax.py)."""
    n, t, hq, dh = q.shape
    kvh, bs = kpool.shape[1], kpool.shape[2]
    maxb = tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(dh))
    if not _use_pallas():
        return _dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens,
                               scale, window, alibi_slopes)

    alibi = alibi_slopes is not None
    check_block_table_fits(n, maxb, n_vectors=4 if alibi else 3)
    group = hq // kvh
    t_pad = max(8, int(np.ceil(t / 8)) * 8)
    qt = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))

    kernel = functools.partial(_paged_kernel, scale=scale, block_size=bs,
                               t_pad=t_pad, window=window, alibi=alibi)
    nsp = 5 if alibi else 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(n, hq, maxb),
        in_specs=[
            pl.BlockSpec((1, 1, t_pad, dh), lambda ni, h, b, *refs: (ni, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh),
                         lambda ni, h, b, tables, *refs: (tables[ni, b], h // group, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh),
                         lambda ni, h, b, tables, *refs: (tables[ni, b], h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, t_pad, dh), lambda ni, h, b, *refs: (ni, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((t_pad, dh), jnp.float32),
            pltpu.VMEM((t_pad, 128), jnp.float32),
            pltpu.VMEM((t_pad, 128), jnp.float32),
        ],
    )
    scalars = [tables.astype(jnp.int32), lengths.astype(jnp.int32),
               start_pos.astype(jnp.int32), n_tokens.astype(jnp.int32)]
    if alibi:
        scalars.append(jnp.asarray(alibi_slopes, jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, hq, t_pad, dh), q.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_pallas.INTERPRET,
        name="paged_attention",
    )(*scalars, qt, kpool, vpool)
    return out[:, :, :t].transpose(0, 2, 1, 3)


def _dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens, scale,
                    window, alibi_slopes=None):
    """Reference-math path: gather the whole table, masked sdpa (the v2
    engine's original implementation — kept as the CPU/parity baseline)."""
    from ...models.transformer import sdpa
    n, t, hq, dh = q.shape
    maxb = tables.shape[1]
    kvh, bs = kpool.shape[1], kpool.shape[2]
    ctx_k = kpool[tables].transpose(0, 1, 3, 2, 4).reshape(n, maxb * bs, kvh, dh)
    ctx_v = vpool[tables].transpose(0, 1, 3, 2, 4).reshape(n, maxb * bs, kvh, dh)
    positions = start_pos[:, None] + jnp.arange(t)[None, :]
    qpos = jnp.where(jnp.arange(t)[None, :] < n_tokens[:, None], positions, -1)
    kpos = jnp.arange(maxb * bs)[None, None, :]
    qp = qpos[:, :, None]
    mask = (kpos <= qp) & (kpos < lengths[:, None, None]) & (qp >= 0)
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qp - window)
    bias = None
    if alibi_slopes is not None:
        bias = (jnp.asarray(alibi_slopes, jnp.float32)[None, :, None, None]
                * jnp.arange(maxb * bs, dtype=jnp.float32)[None, None, None, :])
    out = sdpa(q, ctx_k, ctx_v, causal=False, mask=mask[:, None, :, :],
               softmax_scale=scale, bias=bias)
    return jnp.where((qp >= 0)[..., None], out, 0.0)
