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

**What one grid step holds.**  The grid is (sequence, group of KV heads, row
split, table slot), the slot innermost: one step per (sequence, slot) wherever
a step can hold every KV head.  A step takes the block ``tables[n, b]`` ONCE
for ``kvg`` KV heads — a [kvg, bs, Dh] tile of K and one of V, contiguous in
the pool, 256 KiB each for 8 heads of 128 x 128 bf16 — and with it ALL the q
heads that read those KV heads: q goes in as [N, KV, T * group, Dh], the
``group = H // KV`` q heads of a KV head stacked into the rows of one product
(row = token * group + head within the group, a token's group adjacent), so a
decode step multiplies ``group`` live rows a KV head instead of one a q head
and a chunk [group * T, Dh] x [Dh, bs]; the step's KV heads go through each
product together, as its batch dimension ([kvg, rows, Dh] x [kvg, bs, Dh]:
the body is traced once whatever the head count, and the compiler overlaps
the heads' products and softmaxes).  The accumulator, ``m`` and ``l`` are
per (KV head, row) and live in VMEM across a sequence's slots.  Operands are
the pool's dtype (bf16 on the chip) with f32 accumulation, the probabilities
cast to it for p . v as ``models.transformer.sdpa`` does; softmax state stays
f32.  Per-row facts follow the rows: positions and the ``n_tokens`` mask are
those of token ``row // group``, the ALiBi slope that of q head
``kv * group + row % group``.

**Rows that hold no token do no arithmetic.**  The live rows of a sequence are
a prefix of its rows (``n_tokens * group``), so products, the state's
initialisation and the final division run over the row tiles under that bound
(tiles of ROW_TILE; one tile of SMALL_ROWS for a decode row riding in a chunk's
bucket) and the other rows are written as zeros.  A table slot past the
sequence's last live block is still a grid step, but names the last live block
again in its index map, so it fetches nothing and computes nothing.

**The tile is chosen from the static shapes** (``step_tile``: T, H, KV, Dh,
bs and the two dtypes against one VMEM budget, ``VMEM_BUDGET_BYTES``): all KV
heads a step wherever q, out, the accumulators and the double-buffered K/V
tiles fit (every decode and verify shape; Mistral's [n, 256] too), a divisor
of them where they do not (T = 512 with 32 q heads), and only where one KV
head's rows alone pass the budget (MQA with 64 heads over 512 tokens) are the
rows cut into several steps, each fetching the block again.  No option, no
model name, no caller's hint; a block too large for any step is a readable
error, in the manner of ``check_block_table_fits``.

**A value that is a prefix of the key** (latent attention, MLA absorbed: the
cached token is one vector ``[c_kv | k_pe]``, the scores run over all of it and
the weighted sum over its first ``value_dim`` columns).  ``vpool=None`` says
so: there is no second pool, the step's one tile is read once and its leading
columns are ``v``; the accumulator and the output are ``value_dim`` wide while
q and the scores are the key's width (576 against 512 for DeepSeek-V2).  One
KV head with the q group of every head stacked into the rows is then the whole
of q: ``[N, T, H, Dk] -> [N, 1, T * H, Dk]`` is a reshape, no transpose.

Off-TPU falls back to the dense gather + masked sdpa (identical math; tests
compare the two).
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


# Vector memory (VMEM) a grid step may hold by this file's own reckoning
# (``_step_vmem_bytes``), and the scoped limit the compiler is handed with it
# (its default, 16 MiB, is under one [32, 256] step of 32 q heads).  A v5e core
# has 128 MiB; the difference is left to the compiler's own temporaries.
VMEM_BUDGET_BYTES = 40 << 20
VMEM_LIMIT_BYTES = 64 << 20

# Rows of q one product takes inside a grid step: a tile of ROW_TILE, or the
# one tile of SMALL_ROWS where a sequence has no more live rows than that (a
# decode row riding in a chunk's bucket).  Both are whole bf16 sublane tiles.
ROW_TILE = 256
SMALL_ROWS = 16


def _step_vmem_bytes(kvg: int, rows: int, tile: int, dh: int, bs: int,
                     q_bytes: int, kv_bytes: int, dv: Optional[int] = None) -> int:
    """VMEM of one grid step holding ``kvg`` KV heads and ``rows`` q rows a
    head: q and out (double-buffered by the pipeline), the K and V tiles
    (likewise), the f32 accumulator with ``m`` and ``l`` (a lane tile each a
    row), and one row tile's scores, probabilities and masks for every head.
    ``dv``: the value is the key tile's first ``dv`` columns (no V tile; out
    and the accumulator are that wide); None where V is a pool of its own."""
    lanes = _round_up(dh, 128)
    out_lanes = lanes if dv is None else _round_up(dv, 128)
    q_and_out = 2 * kvg * rows * (lanes + out_lanes) * q_bytes
    k_and_v = (2 if dv is None else 1) * 2 * kvg * _round_up(bs, 16) * lanes * kv_bytes
    state = kvg * rows * (out_lanes + 2 * 128) * 4
    work = kvg * tile * (4 * _round_up(bs, 128) + lanes + out_lanes) * 4
    return q_and_out + k_and_v + state + work


def step_tile(t: int, hq: int, kvh: int, dh: int, bs: int, q_dtype, pool_dtype,
              dv: Optional[int] = None):
    """What one grid step holds, from the static shapes alone: ``(kvg, rows,
    splits, tile)``.  ``kvg`` KV heads (a divisor of ``kvh``) with all their q
    heads, ``rows`` q rows a KV head (``t * hq // kvh`` live at most, padded to
    whole row tiles of ``tile``) and, only where one KV head's rows do not fit,
    the rows cut into ``splits`` grid steps (K and V are then fetched once a
    split).  The largest step under ``VMEM_BUDGET_BYTES`` wins: all KV heads
    for every decode and verify shape, fewer for a wide chunk of many heads.
    ``dv`` is the value's width where it is a prefix of the key (``vpool=None``
    in :func:`paged_attention`): one tile a block, out and the accumulator
    ``dv`` wide.  Its one KV head makes q's rows a reshape, so padding them
    would be the only copy of q: there the first split into equal whole parts
    is taken where it costs at most a third more steps than the first that fits
    (128 heads x 512 tokens: 16 parts of 32 tokens against 13 padded ones).
    With K and V pools q is transposed anyway and the first fit stands."""
    group = hq // kvh
    q_bytes, kv_bytes = jnp.dtype(q_dtype).itemsize, jnp.dtype(pool_dtype).itemsize
    rows = _round_up(t * group, SMALL_ROWS)
    tile = min(rows, ROW_TILE)
    rows = _round_up(rows, tile)

    def fits(kvg, rows):
        return _step_vmem_bytes(kvg, rows, tile, dh, bs, q_bytes, kv_bytes, dv) <= VMEM_BUDGET_BYTES

    for kvg in range(kvh, 0, -1):
        if kvh % kvg == 0 and fits(kvg, rows):
            return kvg, rows, 1, tile
    fitting = [(splits, part) for splits in range(2, rows // tile + 1)
               for part in [_round_up(-(-rows // splits), tile)] if fits(1, part)]
    if fitting:
        splits, part = fitting[0]
        exact = [(s, p) for s, p in fitting if s * p == rows and 3 * s <= 4 * splits]
        if dv is not None and exact:
            splits, part = exact[0]
        return 1, part, splits, tile
    need = _step_vmem_bytes(1, tile, tile, dh, bs, q_bytes, kv_bytes, dv)
    raise ValueError(
        f"paged_attention: one grid step over KV blocks of [{bs}, {dh}] "
        f"({jnp.dtype(pool_dtype).name}) needs {need} bytes of vector memory with a single "
        f"KV head and one tile of {tile} q rows (q [T={t}, H={hq}], {kvh} KV heads); the "
        f"kernel keeps a step under {VMEM_BUDGET_BYTES}. Serve with a smaller KV block "
        f"size (block_size x head_dim must stay under ~{VMEM_BUDGET_BYTES // (8 * kv_bytes)}).")


def _paged_kernel(tables_ref, lengths_ref, start_ref, ntok_ref, *rest,
                  scale, block_size, group, kvg, tile, window, alibi, value_dim):
    if alibi:
        slopes_ref, *rest = rest
    if value_dim is None:
        q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc = rest
    else:  # the value is the key tile's leading columns: one tile a block
        q_ref, k_ref, o_ref, acc, m_sc, l_sc = rest
    n, g, r, b = (pl.program_id(i) for i in range(4))
    nb = pl.num_programs(3)
    rows = acc.shape[1]
    length, start, ntok = lengths_ref[n], start_ref[n], ntok_ref[n]
    first_row = r * rows
    # row = token * group + (q head within the KV head's group): the rows that
    # hold a token are a prefix, so leaving the others out is a loop bound
    live = jnp.clip(ntok * group - first_row, 0, rows)

    def each_live_tile(when, fn):
        """``fn(r0, size)`` for every row tile that holds a token, all local KV
        heads at once: tiles of ``tile``, or the one of SMALL_ROWS where no more
        rows are live (a decode row in a chunk's bucket does a decode row's work)."""
        if rows <= SMALL_ROWS:
            pl.when(when & (live > 0))(lambda: fn(0, rows))
            return
        pl.when(when & (live > 0) & (live <= SMALL_ROWS))(lambda: fn(0, SMALL_ROWS))

        @pl.when(when & (live > SMALL_ROWS))
        def _tiles():
            jax.lax.fori_loop(0, pl.cdiv(live, tile),
                              lambda i, _: fn(pl.multiple_of(i * tile, tile), tile), None)

    def init(r0, size):
        at = pl.ds(r0, size)
        acc[:, at, :] = jnp.zeros((kvg, size, acc.shape[2]), jnp.float32)
        m_sc[:, at, :] = jnp.full((kvg, size, m_sc.shape[2]), NEG_INF, jnp.float32)
        l_sc[:, at, :] = jnp.zeros((kvg, size, l_sc.shape[2]), jnp.float32)

    def attend(r0, size):
        """Rows [r0, r0 + size) of every local KV head against this block: one
        batched product over the heads, [kvg, size, Dh] x [kvg, bs, Dh]."""
        at = pl.ds(r0, size)
        k = k_ref[0]  # [kvg, bs, Dh], the pool's dtype
        v = v_ref[0] if value_dim is None else k[:, :, :value_dim]
        s = jax.lax.dot_general(q_ref[0, :, at, :].astype(k.dtype), k,
                                (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale  # [kvg, size, bs]
        row = first_row + r0 + jax.lax.broadcasted_iota(jnp.int32, (1, size, 1), 1)
        tok = row // group
        qp = start + tok  # absolute query positions
        kpos = b * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_size), 2)
        if alibi:
            # ALiBi key-only form: slope_h * absolute key index (softmax-
            # equivalent to the relative-distance form per query row —
            # models/bloom.py docstring; HF build_alibi_tensor)
            slopes = []
            for h in range(kvg):  # q head of a row: (kv head) * group + row % group
                head = (g * kvg + h) * group
                slope = jnp.full((1, size, 1), slopes_ref[head], jnp.float32)
                for j in range(1, group):
                    slope = jnp.where(row - tok * group == j, slopes_ref[head + j], slope)
                slopes.append(slope)
            s = s + jnp.concatenate(slopes, axis=0) * kpos.astype(jnp.float32)
        # causal, inside the live context, and only for a row that holds a token
        mask = kpos <= jnp.where(tok < ntok, jnp.minimum(qp, length - 1), -1)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qp - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[:, at, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:, at, 0:1] = l_sc[:, at, 0:1] * corr + jnp.sum(p, axis=2, keepdims=True)
        m_sc[:, at, 0:1] = m_new
        acc[:, at, :] = acc[:, at, :] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    def normalise(r0, size):
        at = pl.ds(r0, size)
        l = l_sc[:, at, 0:1]
        o_ref[0, :, at, :] = (acc[:, at, :] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    each_live_tile(b == 0, init)
    each_live_tile(b * block_size < length, attend)

    @pl.when(b == nb - 1)
    def _zero():  # rows that hold no token come back zero
        o_ref[...] = jnp.zeros_like(o_ref)

    each_live_tile(b == nb - 1, normalise)


def paged_attention(q, kpool, vpool, tables, lengths, start_pos, n_tokens, *,
                    block_size: int, softmax_scale: Optional[float] = None,
                    window: Optional[int] = None, alibi_slopes=None,
                    value_dim: Optional[int] = None):
    """q [N, T, H, Dh]; kpool/vpool [NB, KV, bs, Dh]; tables [N, MAXB] int32;
    lengths/start_pos/n_tokens [N] int32.  Returns [N, T, H, Dh] (rows at
    t >= n_tokens[n] are zero).  ``window`` = sliding-window size (Mistral);
    ``alibi_slopes`` [H] f32 adds slope_h * key_index to the scores (BLOOM —
    reference serves ALiBi through its softmax op's alibi path,
    ops/transformer/inference/op_binding/softmax.py).  ``vpool=None``: the
    value of a cached token is the first ``value_dim`` columns of its key (a
    latent pool); the result is then [N, T, H, value_dim]."""
    n, t, hq, dh = q.shape
    kvh, bs = kpool.shape[1], kpool.shape[2]
    maxb = tables.shape[1]
    if (vpool is None) != (value_dim is not None):
        raise ValueError("paged_attention: a value pool, or the width of the value inside the "
                         f"key (vpool=None with value_dim); got vpool={type(vpool).__name__}, "
                         f"value_dim={value_dim}")
    dv = dh if value_dim is None else value_dim
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(dh))
    if not _use_pallas():
        return _dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens,
                               scale, window, alibi_slopes, value_dim)

    alibi = alibi_slopes is not None
    check_block_table_fits(n, maxb, n_vectors=4 if alibi else 3)
    group = hq // kvh
    kvg, rows, splits, tile = step_tile(t, hq, kvh, dh, bs, q.dtype, kpool.dtype, value_dim)
    # [N, T, KV, group, Dh] -> [N, KV, T * group, Dh]: a KV head's q rows, a
    # token's group adjacent, padded with rows that hold no token
    qr = q.reshape(n, t, kvh, group, dh).transpose(0, 2, 1, 3, 4).reshape(n, kvh, t * group, dh)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, splits * rows - t * group), (0, 0)))

    def q_block(ni, g, r, b, *refs):
        return ni, g, r, 0

    def kv_block(ni, g, r, b, tables, lengths, *refs):
        # a slot past the sequence's last live block names that block again: the
        # pipeline fetches a block only when its index changes, so a dead slot
        # is a grid step and no fetch
        last = jnp.maximum(lengths[ni] - 1, 0) // bs
        return tables[ni, jnp.minimum(b, last)], g, 0, 0

    kernel = functools.partial(_paged_kernel, scale=scale, block_size=bs, group=group,
                               kvg=kvg, tile=tile, window=window, alibi=alibi,
                               value_dim=value_dim)
    pools = (kpool, vpool) if value_dim is None else (kpool, )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if alibi else 4,
        grid=(n, kvh // kvg, splits, maxb),
        in_specs=[pl.BlockSpec((1, kvg, rows, dh), q_block)]
        + [pl.BlockSpec((1, kvg, bs, dh), kv_block) for _ in pools],
        out_specs=pl.BlockSpec((1, kvg, rows, dv), q_block),
        scratch_shapes=[
            pltpu.VMEM((kvg, rows, dv), jnp.float32),
            pltpu.VMEM((kvg, rows, 128), jnp.float32),
            pltpu.VMEM((kvg, rows, 128), jnp.float32),
        ],
    )
    scalars = [tables.astype(jnp.int32), lengths.astype(jnp.int32),
               start_pos.astype(jnp.int32), n_tokens.astype(jnp.int32)]
    if alibi:
        scalars.append(jnp.asarray(alibi_slopes, jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qr.shape[:3] + (dv, ), q.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=_pallas.INTERPRET,
        name="paged_attention",
    )(*scalars, qr, *pools)
    out = out[:, :, :t * group].reshape(n, kvh, t, group, dv)
    return out.transpose(0, 2, 1, 3, 4).reshape(n, t, hq, dv)


def _dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens, scale,
                    window, alibi_slopes=None, value_dim: Optional[int] = None):
    """Reference-math path: gather the whole table, masked sdpa (the v2
    engine's original implementation — kept as the CPU/parity baseline)."""
    from ...models.transformer import sdpa
    n, t, hq, dh = q.shape
    maxb = tables.shape[1]
    kvh, bs = kpool.shape[1], kpool.shape[2]
    ctx_k = kpool[tables].transpose(0, 1, 3, 2, 4).reshape(n, maxb * bs, kvh, dh)
    if vpool is None:
        ctx_v = ctx_k[..., :value_dim]
    else:
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
