"""Pallas TPU paged (blocked-KV) attention for ragged serving.

Replaces the dense block-table gather the v2 engine shipped with (the analog of
the reference's blocked flash kernels, inference/v2/kernels/ragged_ops/
blocked_flash + linear_blocked_kv_rotary): instead of gathering every
sequence's whole block table into a dense [N, MAXB*bs, KV, Dh] context (HBM
traffic O(MAXB) regardless of actual length), the kernel walks each sequence's
block table with **scalar-prefetched indices** — the block index names the
source of a copy out of the pool, which stays in HBM, so only blocks below the
sequence's live length are ever read, with online-softmax accumulation across
the steps of the walk.

Layout: q [N, T, H, Dh] (T = SplitFuse chunk, 1 at decode), or the flat
[S, H, Dh] of a compacted pass; KV pool
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
split, step along the table), the table innermost: one step per (sequence,
``slots`` consecutive table slots) wherever a step can hold every KV head.  A
step takes the blocks ``tables[n, b * slots : (b + 1) * slots]`` ONCE for
``kvg`` KV heads — a [kvg, slots * bs, Dh] tile of K and one of V, each block
a contiguous [kvg, bs, Dh] of the pool, 256 KiB for 8 heads of 128 x 128 bf16 —
and with them ALL the q heads that read those KV heads: q goes in KV-major on
ONE flat row axis, [KV, R, Dh] (below), the ``group = H // KV`` q heads of a KV
head stacked into the rows of one product (row = token * group + head within
the group, a token's group adjacent), so a decode step multiplies ``group``
live rows a KV head
instead of one a q head and a chunk [group * T, Dh] x [Dh, slots * bs]; the
step's KV heads go through each product together, as its batch dimension
([kvg, rows, Dh] x [kvg, slots * bs, Dh]: the body is traced once whatever the
head count and whatever ``slots``, and the compiler overlaps the heads'
products and softmaxes).  The scores of all the step's keys are that one
product and the online softmax (``m``, ``l``, ``acc * corr``, ``p . v``) is
updated once a step, not once a block: one wait for the blocks, one pass over
the accumulator and one step's overhead for ``slots`` blocks.  The accumulator,
``m`` and ``l`` are per (KV head, row) and live in VMEM across a sequence's
steps.  Operands are the pool's dtype (bf16 on the chip) with f32 accumulation,
the probabilities cast to it for p . v as ``models.transformer.sdpa`` does;
softmax state stays f32.  Per-row facts follow the rows: positions and the
``n_tokens`` mask are those of token ``row // group``, the ALiBi slope that of
q head ``kv * group + row % group``.

**q comes from, and the output goes back to, a flat row axis** (ISSUE 40).
Sequence ``n``'s rows begin at ``row0[n]``, a number the plan carries (data, in
whole sublane tiles), not at block ``n`` of a padded array: a grid step's q
window is ``rows`` rows from ``row0[n] + r * rows`` (an element-indexed
``BlockSpec``: the pipeline fetches it ahead like any block).  The padded bucket
``[N, T, H, Dh]`` (:func:`paged_attention`: decode, a burst body, a verify) is
that with ``row0[n] = n x splits x rows``; a compacted pass
(:func:`paged_attention_flat`) hands the tokens as they lie, ``[S, H, Dh]``, each
sequence begun on a whole tile of rows, so nothing of the padded ``[N, T]`` size
exists: windows then lie over the rows of the sequences behind them.  Reading
those is harmless.  The output is written by the kernel's own copies, the live
rows alone and one copy on its way at a time, so the windows' rows reach HBM in
the grid's order and a whole window's dead rows (zeros) land before the live
rows of the sequences behind it; rows no window writes are the zeros the output
began as (aliased in).

**The kernel fetches its own blocks.**  The pools are handed over where they
lie (``memory_space=pl.ANY``) and a step's live blocks are copied by one loop
of ``make_async_copy`` into one half of a [2, kvg, slots * bs, Dh] tile a pool:
the loop is traced once whatever ``slots`` (a BlockSpec a slot cost more to
trace and lower than this whole body, in every one of a cell's 38-70 programs),
no copy joins the blocks, and a slot past the sequence's last live block is a
shorter loop, not a fetch.  The copies run one live step ahead of the
arithmetic, across row splits, KV-head groups and sequences (the grid runs in
order: every axis is "arbitrary"): a live step starts the next live step's
copies into the other half, then waits for its own.  Which step is next is
read from a plan made once a call outside the kernel (``_fetch_plan``: live
blocks and live row splits a sequence), so a grid step works nothing out.  A
sequence behind a row of the bucket that holds no token starts its own copies;
such a row's blocks are never fetched.  Slots never fetched hold zeros (the
tiles are zeroed once a call: 0 x NaN would be NaN in p . v) and their keys
lie past ``lengths``, which the mask already leaves out.

**Rows that hold no token do no arithmetic.**  The live rows of a sequence are
a prefix of its rows (``n_tokens * group``), so products and the final division
run over the row tiles under that bound (tiles of ROW_TILE; one tile of
SMALL_ROWS for a decode row riding in a chunk's bucket) and the other rows are
zeros.  A sequence's first step starts the softmax state instead of reading it,
so nothing is initialised apart.

**A row tile works only on the steps it sees** (ISSUE 57).  A tile's rows are a
few consecutive tokens, and those see a contiguous band of the walk's steps:
from the step that holds the oldest key the tile's FIRST token sees through the
window (the walk's first step without one) to the step that holds its LAST
token's own position.  Outside the band the mask would throw everything away
(``p`` is 0, ``corr`` is 1: the state stays what it is), so the product, the
mask, the two ``exp`` passes and the read and write of the accumulator are not
done: the tile loop of a live step runs over ``[lo, hi)`` of :func:`tile_band`,
the tiles below ``lo`` lying wholly before the step's first key (a chunk's
causal edge: a token does not multiply the keys of the tokens behind it in its
own chunk, by whole steps) and those from ``hi`` wholly past the window behind
the step's last key (a window's near edge: a chunk of T tokens is no longer ONE
window of T + window keys a token).  A tile that skipped the walk's first steps
has no state to read at its own first one, so a tile's state starts at
:func:`tile_first_step`, not at step 0; after its band a tile's state is
complete and waits for the sequence's last live step, where every live tile is
divided and leaves as before.  The FETCH did not move: a step's blocks serve all
the tiles of the step, some tile of the sequence sees every live step, and the
copy one step ahead knows nothing of tiles.  The two paths that never enter the
tile loop (a window of at most SMALL_ROWS rows, a decode row in a chunk's bucket)
are what they were, and a latent cache's decode step (one token of 128 rows, one
tile) has the band of every live step.

**The tile is chosen from the static shapes** (``step_tile``: T, H, KV, Dh,
bs and the two dtypes against one VMEM budget, ``VMEM_BUDGET_BYTES``): all KV
heads a step wherever q, out, the accumulators and the double-buffered K/V
tiles fit (every decode and verify shape; Mistral's [n, 256] too), a divisor
of them where they do not (T = 512 with 32 q heads), and only where one KV
head's rows alone pass the budget (MQA with 64 heads over 512 tokens) are the
rows cut into several steps, each fetching the blocks again.  Then the table
slots a step takes, 4, 2 or 1: the most that still fit beside those heads and
rows (``VMEM_SLOTS_BYTES``; never fewer KV heads for more slots).  The table's
width need be no multiple of it: a slot past the table is a dead slot.  No
option, no model name, no caller's hint; a block too large for any step is a
readable error, in the manner of ``check_block_table_fits``.

**A value that is a prefix of the key** (latent attention, MLA absorbed: the
cached token is one vector ``[c_kv | k_pe]``, the scores run over all of it and
the weighted sum over its first ``value_dim`` columns).  ``vpool=None`` says
so: there is no second pool, the step's one tile is copied once and its leading
columns are ``v``; the accumulator and the output are ``value_dim`` wide while
q and the scores are the key's width (576 against 512 for DeepSeek-V2).  One
KV head with the q group of every head stacked into the rows is then the whole
of q: ``[S, H, Dk] -> [1, S * H, Dk]`` is a reshape, no transpose, and with a
group of whole tiles (128) no gather either.

**A selection of the cache** (``selection``: a learned top-k, ``ops/attention/
dsa.py``; a bit a (token, position) pair).  The walk, the products and the
softmax are those of a call without one: every live block is fetched and
multiplied, and what a token did not select is masked.  The selection is a
TOKEN's and the rows are (token, q head of the group), so it is handed over by
token (``_selection_tiles``: float32, eight tokens the sublanes of a tile, a
step's keys its lanes) and a grid step's window of it rides beside q's.  A row
tile's mask is its tokens' rows, each read where it lies (one row at its own
sublane: a window's first token lies on no tile edge, and a one-row read needs
none) and broadcast over the token's ``group`` rows, which are whole sublane
tiles (``_check_selected_rows``), laid under one another and compared once.  No
product, no one-hot: the body holds ``q k^T`` and ``p v`` whether or not a
selection is handed in, and without one it is traced as if there were no such
thing.  A grid step that does no arithmetic (past a sequence's last live
block, or of a row of the bucket that holds no token) asks for the window the
live step before it took, so the pipeline fetches nothing for it.

**A window's walk begins at the window** (ISSUE 56).  With ``window`` given, no
query token of the step sees a key older than ``start_pos - window + 1``, so the
blocks wholly behind that position are not walked: the plan's last row names the
table slot the walk begins at (:func:`walk_first_block`), ``BLOCKS`` counts the
live blocks from there, a step's copies take ``tables[n, first + j]`` and its key
positions begin at ``first * bs``.  Nothing else of the body knows: the steps, the
fetch one step ahead, the softmax state and the output's leaving are a shorter
sequence's.  The window's near edge is the row tiles' (above: a tile leaves out
the steps wholly past its own tokens' windows); inside a step both edges, and the
far edge inside the walk's first block, stay the mask's.  Under a ``selection``
(whose tiles lie by the table's own steps) the walk begins at the first slot as
before, and the band counts its steps from there.

Off-TPU falls back to the dense gather + masked sdpa (identical math; tests
compare the two).
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
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
# What a step may hold by the same reckoning once its heads and rows are chosen
# and only the table slots it takes at once are left to choose.  Measured on the
# chip (PR 35): 8 KV heads of 1,024 rows with four slots reckon 42.0 MiB, compile
# under the limit and run a long prompt's chunk call in 284 us where two slots
# (32.0 MiB) take 355 and the one-slot kernel 390.
VMEM_SLOTS_BYTES = 44 << 20

# Rows of q one product takes inside a grid step: a tile of ROW_TILE, or the
# one tile of SMALL_ROWS where a sequence has no more live rows than that (a
# decode row riding in a chunk's bucket).  Both are whole bf16 sublane tiles.
ROW_TILE = 256
SMALL_ROWS = 16


def _step_vmem_bytes(kvg: int, rows: int, tile: int, dh: int, bs: int,
                     q_bytes: int, kv_bytes: int, dv: Optional[int] = None,
                     slots: int = 1) -> int:
    """VMEM of one grid step holding ``kvg`` KV heads, ``rows`` q rows a head
    and the blocks of ``slots`` table slots: q (double-buffered by the pipeline)
    and out (held once since ISSUE 40 and still reckoned twice: the heads and
    rows a step takes stay the parent's), the K and V tiles of ``slots`` blocks
    each (two of each: the
    next step's are fetched while this one computes), the f32 accumulator with
    ``m`` and ``l`` (a lane tile each a row), and one row tile's scores,
    probabilities and masks over the step's ``slots * bs`` keys for every head.
    ``dv``: the value is the key tile's first ``dv`` columns (no V tile; out
    and the accumulator are that wide); None where V is a pool of its own."""
    lanes = _round_up(dh, 128)
    out_lanes = lanes if dv is None else _round_up(dv, 128)
    q_and_out = 2 * kvg * rows * (lanes + out_lanes) * q_bytes
    k_and_v = (2 if dv is None else 1) * 2 * kvg * slots * _round_up(bs, 16) * lanes * kv_bytes
    state = kvg * rows * (out_lanes + 2 * 128) * 4
    work = kvg * tile * (4 * _round_up(slots * bs, 128) + lanes + out_lanes) * 4
    return q_and_out + k_and_v + state + work


# Table slots one grid step may take, most first (the engine's table widths are
# multiples of 4: ``engine_v2.TABLE_STEP``).
STEP_SLOTS = (4, 2, 1)


def step_tile(t: int, hq: int, kvh: int, dh: int, bs: int, q_dtype, pool_dtype,
              dv: Optional[int] = None):
    """What one grid step holds, from the static shapes alone: ``(kvg, rows,
    splits, tile, slots)``.  ``kvg`` KV heads (a divisor of ``kvh``) with all
    their q heads, ``rows`` q rows a KV head (``t * hq // kvh`` live at most,
    padded to whole row tiles of ``tile``) and, only where one KV head's rows
    do not fit, the rows cut into ``splits`` grid steps (K and V are then
    fetched once a split).  The largest step under ``VMEM_BUDGET_BYTES`` wins:
    all KV heads for every decode and verify shape, fewer for a wide chunk of
    many heads.  Then ``slots``, the consecutive table slots whose blocks the
    step takes at once: the most of ``STEP_SLOTS`` that still fit beside those
    heads and rows under ``VMEM_SLOTS_BYTES`` (never fewer KV heads a step for
    more slots).
    ``dv`` is the value's width where it is a prefix of the key (``vpool=None``
    in :func:`paged_attention`): one tile a block, out and the accumulator
    ``dv`` wide.  Its one KV head makes q's rows a reshape, so padding them
    would be the only copy of q: there the first split into equal whole parts
    is taken where it costs at most a third more steps than the first that fits
    (128 heads x 512 tokens: 16 parts of 32 tokens against 13 padded ones).
    With K and V pools q is transposed anyway and the first fit stands."""
    q_bytes, kv_bytes = jnp.dtype(q_dtype).itemsize, jnp.dtype(pool_dtype).itemsize
    kvg, rows, splits, tile = _heads_and_rows(t, hq, kvh, dh, bs, q_bytes, pool_dtype, dv)
    slots = next(s for s in STEP_SLOTS if s == 1 or _step_vmem_bytes(
        kvg, rows, tile, dh, bs, q_bytes, kv_bytes, dv, s) <= VMEM_SLOTS_BYTES)
    return kvg, rows, splits, tile, slots


def _heads_and_rows(t: int, hq: int, kvh: int, dh: int, bs: int, q_bytes: int, pool_dtype,
                    dv: Optional[int]):
    """``step_tile``'s KV heads and rows, reckoned at one table slot a step."""
    group = hq // kvh
    kv_bytes = jnp.dtype(pool_dtype).itemsize
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


# Tokens whose selections share the sublanes of one float32 tile of the selection's
# layout (``_selection_tiles``): the kernel reads a token's row at sublane
# ``token % SEL_GROUP`` of group ``token // SEL_GROUP``.
SEL_GROUP = 8


def _selection_tiles(selection, tokens: int, block_groups: int, steps: int, keys: int):
    """``selection`` bool ``[tokens', C]`` (the kernel's token axis, ``tokens'
    <= tokens``; ``C`` positions of each token's own sequence) as the kernel
    reads it: float32 ``[G, steps, SEL_GROUP, keys]``, token ``g x 8 + i``'s
    selection among step ``b``'s keys at ``[g, b, i]``: a window of tokens that
    begins anywhere is then whole leading-axis entries, what a grid step takes
    is one rectangle, and a token's row in it is one sublane of 32-bit lanes,
    which the kernel reads wherever it lies and broadcasts over the token's
    rows.  ``G`` holds every block of ``block_groups`` groups."""
    groups = -(-tokens // SEL_GROUP) + block_groups + 1
    sel = lax.convert_element_type(selection, jnp.float32)
    sel = lax.pad(sel, np.float32(0), ((0, groups * SEL_GROUP - sel.shape[0], 0),
                                       (0, steps * keys - sel.shape[1], 0)))
    return lax.transpose(lax.reshape(sel, (groups, SEL_GROUP, steps, keys)), (0, 2, 1, 3))


def _last_live_step(b, blocks, slots: int):
    """Step ``b`` of a sequence's walk, or the last that holds a live block where
    ``b`` lies past it (step 0 for a row of the bucket that holds no token, which
    has none): a step that does no arithmetic asks for the selection's window of
    the live step before it, so the pipeline fetches nothing for it."""
    return lax.min(b, lax.max(lax.sub(lax.div(lax.add(blocks, slots - 1), slots), 1), 0))


# Rows of the plan the kernel reads as scalars (``_fetch_plan``).
BLOCKS, ROW0, SPLITS = 0, 1, 2


def walk_first_block(start_pos: int, window: Optional[int], bs: int) -> int:
    """The first table slot the walk of a sequence takes in a step whose first
    query token sits at ``start_pos``: the block of the oldest key that token
    sees through ``window`` (every later query sees no older one); 0 without a
    window.  Host integers: ``_fetch_plan`` states the same for the kernel
    (its tests hold the two together), and ``ServeCounters`` counts with this
    the blocks a sequence's one table keeps behind a window."""
    return 0 if window is None else max(start_pos - (window - 1), 0) // bs


def _fetch_plan(lengths, n_tokens, row0, bs: int, maxb: int, group: int, rows: int, splits: int,
                start_pos=None, window: Optional[int] = None):
    """[2 to 4, N + 1] int32 (``lengths``, ``n_tokens``, ``row0`` and
    ``start_pos`` come as int32): what the kernel's fetch asks of a sequence,
    worked out once a call and not once a grid step.  Row ``BLOCKS``: the table
    slots the walk takes, those that name a live block of a sequence that holds
    a token (0 for a row of the bucket that holds none: its blocks are never
    fetched).  Row ``ROW0``: where the sequence's q rows begin on the kernel's
    row axis, and its output's, in sublane tiles of SMALL_ROWS (the compiler
    must see that a window begins on a whole one).  Only where a KV head's rows
    are cut into ``splits`` grid steps, row ``SPLITS``: the splits that hold a
    token.  Only with a ``window``, the LAST row: the table slot the walk begins
    at (:func:`walk_first_block`: the blocks wholly behind the window of the
    step's first query token, which no query of the step can see, are not
    walked), and ``BLOCKS`` counts from there.  Column N is the sequence past
    the last: no blocks, no fetch."""
    i32 = np.int32  # numpy scalars are literals of the trace: no equation, no jnp wrapper
    blocks = lax.min(lax.div(lax.add(lengths, i32(bs - 1)), i32(bs)), i32(maxb))
    if window is not None:
        first = lax.div(lax.max(lax.sub(start_pos, i32(window - 1)), i32(0)), i32(bs))
        blocks = lax.sub(blocks, first)
    plan = [lax.select(lax.gt(n_tokens, i32(0)), blocks, lax.full_like(blocks, 0)), row0]
    if splits > 1:
        plan.append(lax.min(lax.div(lax.add(lax.mul(n_tokens, i32(group)), i32(rows - 1)), i32(rows)),
                            i32(splits)))
    if window is not None:
        plan.append(first)
    plan = lax.concatenate([lax.expand_dims(row, (0, )) for row in plan], 0)
    return lax.pad(plan, i32(0), ((0, 0, 0), (0, 1, 0)))


def tile_band(k0, first_row, live, start, *, tile: int, group: int, keys: int, window: Optional[int]):
    """``(lo, hi)``: the row tiles ``[lo, hi)`` of a live grid step that see at least
    one of the step's keys ``[k0, k0 + keys)``.  The step's rows are ``first_row +
    [0, live)`` of a sequence whose query tokens sit at positions ``start, start + 1,
    ...`` (row = token x ``group`` + head of the group).  Tile ``i`` (the step's rows
    ``i x tile ...``) sees a key iff the step's first key is no later than the tile's
    LAST token (causal) and, with a ``window``, the step's last key is newer than what
    the tile's FIRST token has left behind; both are monotone in ``i``, so the tiles
    are one range.  Exact where the live context ends at the last query token
    (``lengths = start_pos + n_tokens``, as every caller has it: a live step then
    begins inside the context and the last step's keys past it are no token's to see);
    with a longer or shorter context never a tile too few, and none before its own
    first step.  Scalars through ``lax`` (the kernel calls this inside its traced
    body; plain integers do as well): integer division only, truncating, each
    quotient guarded where its dividend can be negative."""
    tiles = lax.div(lax.add(live, tile - 1), tile)
    # the first of the step's rows whose token sits at or past the step's first key
    ahead = lax.sub(k0, start)
    lo = lax.max(lax.div(lax.sub(lax.mul(ahead, group), first_row), tile), 0)
    if window is None:
        return lo, tiles
    # how many of the step's rows have tokens that still see the step's last key
    near = lax.sub(lax.mul(lax.add(ahead, keys - 1 + window), group), first_row)
    return lo, lax.min(tiles, lax.div(lax.add(near, tile - 1), tile))


def tile_first_step(row, start, base, *, group: int, keys: int, window: Optional[int]):
    """The step of the walk at which the band of the row tile that begins at ``row``
    of its sequence begins: the step that holds the oldest key the tile's first token
    sees through ``window`` (the walk's keys begin at ``base``); 0 without a window.
    A tile's softmax state starts there (``begun`` in ``attend``)."""
    if window is None:
        return 0
    oldest = lax.sub(lax.add(lax.div(row, group), start), window - 1)
    return lax.max(lax.div(lax.sub(oldest, base), keys), 0)


def _paged_kernel(tables_ref, lengths_ref, start_ref, ntok_ref, plan_ref, *rest,
                  scale, block_size, group, kvg, tile, slots, head_steps, splits, window, alibi,
                  value_dim, selected=False, begins=None):
    # Every program traces and lowers this body once, and a cell meets 38-70
    # programs: scalars and equal shapes go through ``lax`` (a jnp operator
    # costs five times as much to trace), a ``pl.when`` costs 2-3 ms (so what
    # runs under one condition stands under one), and nothing here loops over
    # ``slots``.
    if alibi:
        slopes_ref, *rest = rest
    if selected:  # the window's tokens' selected keys, behind q (``_selection_tiles``)
        rest = list(rest)
        sel_ref = rest.pop(1)
    if value_dim is None:
        q_ref, k_hbm, v_hbm, _, o_hbm, acc, m_sc, l_sc, o_buf, k_buf, v_buf, sems, turn = rest
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    else:  # the value is the key tile's leading columns: one tile a block
        q_ref, k_hbm, _, o_hbm, acc, m_sc, l_sc, o_buf, k_buf, sems, turn = rest
        pools = ((k_hbm, k_buf), )
    n, g, r, b = (pl.program_id(i) for i in range(4))
    last = lax.eq(b, pl.num_programs(3) - 1)
    rows = acc.shape[1]
    keys = slots * block_size
    length, start, ntok = lengths_ref[n], start_ref[n], ntok_ref[n]
    first_row = lax.mul(r, rows)
    k0 = lax.mul(b, keys)  # the step's first key
    base = 0
    if begins is not None:  # the walk's step 0 holds the table slot it began at
        base = lax.mul(plan_ref[begins, n], block_size)
        k0 = lax.add(k0, base)
    # row = token * group + (q head within the KV head's group): the rows that
    # hold a token are a prefix, so leaving the others out is a loop bound
    live = lax.max(lax.min(lax.sub(lax.mul(ntok, group), first_row), rows), 0)
    if selected:  # where the window's first token lies in the first group of SEL_GROUP its block holds
        sel_base = lax.rem(lax.add(lax.div(lax.mul(plan_ref[ROW0, n], SMALL_ROWS), group),
                                   lax.mul(r, rows // group)), SEL_GROUP)

    def each_block(i, gi, step, half, act):
        """``act`` on the copy of every live block among the ``slots`` table
        slots of sequence ``i``'s step ``step`` (KV heads ``gi``) into ``half``
        of the tiles: one loop whatever ``slots``, shorter at a sequence's end
        (a dead slot is no fetch), empty for a sequence that has no token."""
        first = lax.mul(step, slots)
        heads = pl.ds(lax.mul(gi, kvg), kvg)

        def one(j, _):
            at = pl.ds(pl.multiple_of(lax.mul(lax.sub(j, first), block_size), block_size),
                       block_size)
            # a windowed walk counts its slots from the first block a query of the step sees
            blk = tables_ref[i, j if begins is None else lax.add(j, plan_ref[begins, i])]
            for p, (hbm, tiles) in enumerate(pools):
                act(pltpu.make_async_copy(hbm.at[blk, heads], tiles.at[half, :, at, :],
                                          sems.at[half, p]))

        lax.fori_loop(first, lax.min(lax.add(first, slots), plan_ref[BLOCKS, i]), one, None)

    @pl.when(lax.eq(lax.add(lax.add(n, g), lax.add(r, b)), 0))  # the grid's first step
    def _first():  # a slot never fetched holds zeros, not what VMEM held (0 x NaN is NaN in p . v)
        for _, tiles in pools:
            tiles[...] = lax.full(tiles.shape, 0, tiles.dtype)
        turn[0] = turn[1] = turn[2] = 0

    # The fetch runs one live step ahead of the arithmetic, into the other half
    # of the tiles: ``turn`` = (the half this step's blocks are in, whether the
    # live step before this one started their copies, the rows of ``o_buf`` still
    # on their way out).  The grid runs in order (every axis "arbitrary"), so
    # the step before may be another sequence's.
    half, fetched = turn[0], turn[1]
    blocks = plan_ref[BLOCKS, n]
    step_live = lax.lt(lax.mul(b, slots), blocks)
    if splits > 1:
        step_live = lax.bitwise_and(step_live, lax.lt(r, plan_ref[SPLITS, n]))

    def attend(r0, size, first=0):
        """Rows [r0, r0 + size) of every local KV head against the step's
        blocks: one batched product over the heads and over all the step's
        keys, [kvg, size, Dh] x [kvg, slots * bs, Dh], and one softmax update
        (the rows' first step, ``first``, starts the state: nothing is read of
        what VMEM held)."""
        at = pl.ds(r0, size)
        k = k_buf[half]  # [kvg, slots * bs, Dh], the pool's dtype
        v = v_buf[half] if value_dim is None else lax.slice_in_dim(k, 0, value_dim, axis=2)
        s = lax.mul(lax.dot_general(lax.convert_element_type(q_ref[:, at, :], k.dtype), k,
                                    (((2,), (2,)), ((0,), (0,))),
                                    preferred_element_type=jnp.float32), scale)  # [kvg, size, keys]
        row = lax.add(lax.broadcasted_iota(jnp.int32, (1, size, 1), 1), lax.add(first_row, r0))
        tok = lax.div(row, group)
        qp = lax.add(tok, start)  # absolute query positions
        kpos = lax.add(lax.broadcasted_iota(jnp.int32, (1, 1, keys), 2), k0)
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
        # causal, inside the live context (a slot past the sequence's last live
        # block was not fetched: its keys lie past ``length``), and only for a
        # row that holds a token
        seen = lax.select(lax.lt(tok, ntok), lax.min(qp, lax.sub(length, 1)), lax.full_like(qp, -1))
        mask = lax.le(kpos, seen)  # [1, size, keys]
        if window is not None:
            mask = lax.bitwise_and(mask, lax.gt(kpos, lax.sub(qp, window)))
        if selected:
            # A token's selection is one row of ``sel_ref`` for all its ``group`` rows
            # here: each of the tile's tokens' rows is read where it lies (one row at
            # its own sublane: no offset need be a whole tile), broadcast over the
            # token's rows, which are whole sublane tiles, and compared once.
            at_tok = lax.add(sel_base, r0 // group if isinstance(r0, int) else lax.div(r0, group))
            held = []
            for j in range(max(size // group, 1)):
                t = lax.add(at_tok, j)
                own = sel_ref[lax.div(t, SEL_GROUP), 0, pl.ds(lax.rem(t, SEL_GROUP), 1), :]  # [1, keys]
                held.append(lax.broadcast_in_dim(own, (min(group, size), keys), (0, 1)))
            chosen = held[0] if len(held) == 1 else lax.concatenate(held, 0)
            mask = lax.bitwise_and(mask, lax.expand_dims(lax.gt(chosen, 0.5), (0, )))
        mask = lax.broadcast_in_dim(mask, s.shape, (0, 1, 2))
        s = lax.select(mask, s, lax.full_like(s, NEG_INF))

        column = (kvg, size, 1)  # a number a row
        begun = lax.broadcast(lax.gt(b, first), column)
        m_prev = lax.select(begun, m_sc[:, at, 0:1], lax.full(column, NEG_INF, jnp.float32))
        m_new = lax.max(m_prev, lax.expand_dims(lax.reduce_max(s, (2, )), (2, )))
        p = lax.select(mask, lax.exp(lax.sub(s, m_new)), lax.full_like(s, 0.0))
        corr = lax.exp(lax.sub(m_prev, m_new))  # 0 at the first step: exp(NEG_INF - m)
        l_prev = lax.select(begun, l_sc[:, at, 0:1], lax.full(column, 0.0, jnp.float32))
        l_sc[:, at, 0:1] = lax.add(lax.mul(l_prev, corr), lax.expand_dims(lax.reduce_sum(p, (2, )), (2, )))
        m_sc[:, at, 0:1] = m_new
        pv = lax.dot_general(lax.convert_element_type(p, v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        a_prev = acc[:, at, :]
        acc[:, at, :] = lax.add(lax.select(lax.broadcast_in_dim(begun, a_prev.shape, (0, 1, 2)),
                                           lax.mul(a_prev, corr), lax.full_like(a_prev, 0.0)), pv)

    out_at = pl.multiple_of(lax.add(lax.mul(plan_ref[ROW0, n], SMALL_ROWS), first_row), SMALL_ROWS)
    out_heads = pl.ds(lax.mul(g, kvg), kvg)

    def o_rows(size):
        """The copy of this window's first ``size`` output rows to the flat axis
        (a wait asks for the bytes alone: a later window's address will do)."""
        return pltpu.make_async_copy(o_buf.at[:, pl.ds(0, size), :],
                                     o_hbm.at[out_heads, pl.ds(out_at, size), :],
                                     sems.at[0, len(pools)])

    small = min(rows, SMALL_ROWS)
    one = o_rows(small)  # a decode row's tile

    def normalise(r0, size):
        at = pl.ds(r0, size)
        l = l_sc[:, at, 0:1]
        o_buf[:, at, :] = lax.convert_element_type(
            lax.div(acc[:, at, :], lax.select(lax.eq(l, 0.0), lax.full_like(l, 1.0), l)), o_buf.dtype)

    @pl.when(step_live)
    def _step():
        # the next step in order: this sequence's next slots, row split or KV
        # heads, else the sequence after it (no fetch if that one has no token)
        b1, g1 = lax.add(b, 1), lax.add(g, 1)
        more = lax.lt(lax.mul(b1, slots), blocks)
        stay, g2 = more, g
        if splits > 1:
            stay = lax.bitwise_or(stay, lax.lt(lax.add(r, 1), plan_ref[SPLITS, n]))
        if head_steps > 1:
            g2 = lax.select(stay, g, lax.select(lax.lt(g1, head_steps), g1, 0))
            stay = lax.bitwise_or(stay, lax.lt(g1, head_steps))
        n1, other = lax.add(n, 1), lax.sub(1, half)
        each_block(lax.select(stay, n, n1), g2, lax.select(more, b1, 0), other,
                   lambda c: c.start())

        def arrive(c):  # the kernel's first live step, and one behind a row with no token
            pl.when(lax.eq(fetched, 0))(c.start)
            c.wait()

        each_block(n, g, b, half, arrive)
        turn[0] = other
        turn[1] = lax.convert_element_type(
            lax.bitwise_or(stay, lax.gt(plan_ref[BLOCKS, n1], 0)), jnp.int32)
        # every row tile that holds a token and sees a key of the step
        # (``tile_band``), all local KV heads at once: tiles of ``tile``, or the
        # one of SMALL_ROWS where no more rows are live (a decode row in a
        # chunk's bucket does a decode row's work)
        if rows <= SMALL_ROWS:
            attend(0, rows)
        else:
            pl.when(lax.le(live, SMALL_ROWS))(lambda: attend(0, SMALL_ROWS))

            @pl.when(lax.gt(live, SMALL_ROWS))
            def _tiles():
                def work(i, _):
                    r0 = pl.multiple_of(lax.mul(i, tile), tile)
                    attend(r0, tile, tile_first_step(lax.add(first_row, r0), start, base, group=group,
                                                     keys=keys, window=window))

                lax.fori_loop(*tile_band(k0, first_row, live, start, tile=tile, group=group, keys=keys,
                                         window=window), work, None)

        # A window's output leaves by a copy of the kernel's own, at the window's
        # last live step: its live rows alone.  The one tile of a decode row is
        # started here and waited for where the next window leaves (the grid's last
        # step waits for what is left); a window of more rows goes whole (rows
        # without a token as zeros) and is waited for on the spot.  So one copy is
        # on its way at a time and the windows' rows go out in the grid's order: on
        # the flat axis a whole window lies over the rows of the sequences BEHIND
        # it, and those write their live rows later.  ``turn[2]``: a tile is on its way.
        @pl.when(lax.bitwise_not(more))
        def _leave():
            pl.when(lax.ne(turn[2], 0))(one.wait)

            def tile_out():
                normalise(0, small)
                one.start()
                turn[2] = 1

            if rows <= SMALL_ROWS:
                return tile_out()
            turn[2] = 0
            pl.when(lax.le(live, SMALL_ROWS))(tile_out)

            @pl.when(lax.gt(live, SMALL_ROWS))
            def _whole():
                o_buf[...] = lax.full(o_buf.shape, 0, o_buf.dtype)
                lax.fori_loop(0, pl.cdiv(live, tile),
                              lambda i, _: normalise(pl.multiple_of(lax.mul(i, tile), tile), tile), None)
                whole = o_rows(rows)
                whole.start()
                whole.wait()

    end = lax.bitwise_and(  # the grid's last step
        lax.bitwise_and(lax.eq(n, pl.num_programs(0) - 1), lax.eq(g, head_steps - 1)),
        lax.bitwise_and(lax.eq(r, splits - 1), last))
    pl.when(lax.bitwise_and(end, lax.ne(turn[2], 0)))(one.wait)


def _checked_scale(dh: int, vpool, value_dim, softmax_scale) -> float:
    if (vpool is None) != (value_dim is not None):
        raise ValueError("paged_attention: a value pool, or the width of the value inside the "
                         f"key (vpool=None with value_dim); got vpool={type(vpool).__name__}, "
                         f"value_dim={value_dim}")
    return softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(dh))


def paged_attention(q, kpool, vpool, tables, lengths, start_pos, n_tokens, *,
                    block_size: int, softmax_scale: Optional[float] = None,
                    window: Optional[int] = None, alibi_slopes=None,
                    value_dim: Optional[int] = None, selection=None):
    """q [N, T, H, Dh]; kpool/vpool [NB, KV, bs, Dh]; tables [N, MAXB] int32;
    lengths/start_pos/n_tokens [N] int32.  Returns [N, T, H, Dh] (rows at
    t >= n_tokens[n] are zero).  ``window`` = sliding-window size (Mistral);
    ``alibi_slopes`` [H] f32 adds slope_h * key_index to the scores (BLOOM —
    reference serves ALiBi through its softmax op's alibi path,
    ops/transformer/inference/op_binding/softmax.py).  ``vpool=None``: the
    value of a cached token is the first ``value_dim`` columns of its key (a
    latent pool); the result is then [N, T, H, value_dim].  ``selection`` bool
    [N, T, MAXB x bs]: the positions of its own sequence a token attends, and no
    others (``ops/attention/dsa.py select_keys``: a learned selection of the
    cache; under the causal limit by construction).  The kernel walks the live
    blocks as it does without one and masks what was not selected: a step's
    keys are multiplied whether or not any token of the window selected them.
    A token's row of it reaches the token's ``H / KV`` rows of the kernel by a
    broadcast (no product), so that group must be whole tiles of 16 rows.

    The padded bucket is the flat form (:func:`paged_attention_flat`) with every
    sequence's rows begun a whole window apart, ``row0[n] = n x splits x rows``:
    the same kernel, and no index worked out from ``n_tokens``."""
    n, t, hq, dh = q.shape
    scale = _checked_scale(dh, vpool, value_dim, softmax_scale)
    if not _use_pallas():
        return _dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens,
                               scale, window, alibi_slopes, value_dim, selection=selection)
    kvh = kpool.shape[1]
    group, dv = hq // kvh, dh if value_dim is None else value_dim
    shape = step_tile(t, hq, kvh, dh, kpool.shape[2], q.dtype, kpool.dtype, value_dim)
    held = shape[2] * shape[1]  # a sequence's rows: ``splits`` windows of ``rows``
    if selection is not None:  # onto the kernel's token axis: a sequence's ``held`` rows of tokens
        _check_selected_rows(group, shape)
        selection = lax.reshape(
            lax.pad(selection, np.zeros((), bool), ((0, 0, 0), (0, held // group - t, 0), (0, 0, 0))),
            (n * (held // group), selection.shape[-1]))
    # [N, T, KV, group, Dh] -> [KV, N, T * group, Dh]: a KV head's q rows, a
    # token's group adjacent, each sequence padded with rows that hold no token
    # (through ``lax`` like the body: every program traces and lowers this wrapper too)
    qr = lax.reshape(lax.transpose(lax.reshape(q, (n, t, kvh, group, dh)), (2, 0, 1, 3, 4)),
                     (kvh, n, t * group, dh))
    padded = held - t * group
    if padded:
        qr = lax.pad(qr, np.zeros((), q.dtype), ((0, 0, 0), (0, 0, 0), (0, padded, 0), (0, 0, 0)))
    out = _walk(lax.reshape(qr, (kvh, n * held, dh)),
                lax.mul(lax.iota(jnp.int32, n), np.int32(held // SMALL_ROWS)), kpool, vpool,
                tables, lengths, start_pos, n_tokens, group=group, shape=shape, scale=scale,
                window=window, alibi_slopes=alibi_slopes, value_dim=value_dim, selection=selection)
    out = lax.reshape(out, (kvh, n, held, dv))
    if padded:
        out = lax.slice_in_dim(out, 0, t * group, axis=2)
    out = lax.transpose(lax.reshape(out, (kvh, n, t, group, dv)), (1, 2, 0, 3, 4))
    return lax.reshape(out, (n, t, hq, dv))


def flat_token_slots(n: int, s: int, group: int) -> int:
    """Token positions of the kernel's row axis in a flat pass of ``s`` slots
    over ``n`` sequences: every sequence begins on a whole sublane tile of rows
    (``SMALL_ROWS``; a token is ``group`` rows), so up to ``align - 1`` positions
    a sequence hold no token.  ``ServeCounters.attn_token_slots`` counts this
    (the spare window behind them, which no sequence can begin in, apart)."""
    return s + n * (SMALL_ROWS // math.gcd(group, SMALL_ROWS) - 1)


def _rows_at(x, at):
    """``x[at]`` along the first axis, every index in bounds (through ``lax``:
    jnp's indexing costs a millisecond a use to trace, in every program)."""
    numbers = lax.GatherDimensionNumbers(offset_dims=tuple(range(1, x.ndim)),
                                         collapsed_slice_dims=(0, ), start_index_map=(0, ))
    return lax.gather(x, lax.expand_dims(at, (1, )), numbers, (1, ) + x.shape[1:],
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _flat_rows(n_tokens, s: int, held: int, align: int):
    """Where a flat pass's tokens lie on the kernel's token axis of ``held``
    positions, each sequence begun on a multiple of ``align``: ``(first [N],
    take [held], back [s])``: sequence ``n`` begins at ``first[n]``, position
    ``p`` holds flat slot ``take[p]`` (slot 0 where it holds none: the kernel
    masks it), and flat slot ``j`` lies at ``back[j]`` (the last position, which
    holds nothing and comes back zero, for a slot past the live tokens)."""
    i32, n = np.int32, n_tokens.shape[0]
    whole = lax.mul(lax.div(lax.add(n_tokens, i32(align - 1)), i32(align)), i32(align))
    sums = lax.cumsum(lax.concatenate([lax.expand_dims(n_tokens, (0, )),
                                       lax.expand_dims(whole, (0, ))], 0), axis=1)
    ends = lax.index_in_dim(sums, 0, 0, keepdims=False)
    first = lax.sub(lax.index_in_dim(sums, 1, 0, keepdims=False), whole)
    gap = lax.sub(first, lax.sub(ends, n_tokens))  # positions without a token before sequence n's
    j = lax.iota(jnp.int32, s)
    past = lax.ge(lax.broadcast_in_dim(j, (s, n), (0, )), lax.broadcast_in_dim(ends, (s, n), (1, )))
    # the sequence a slot's token is of: N for a slot past the live tokens
    row = lax.reduce_sum(lax.convert_element_type(past, jnp.int32), (1, ))
    back = lax.select(lax.lt(row, lax.full_like(row, n)),
                      lax.add(j, _rows_at(gap, lax.min(row, lax.full_like(row, n - 1)))),
                      lax.full_like(j, held - 1))
    # the other way round: every position takes slot 0 but those a live slot lies at
    numbers = lax.ScatterDimensionNumbers(update_window_dims=(), inserted_window_dims=(0, ),
                                          scatter_dims_to_operand_dims=(0, ))
    take = lax.scatter(lax.full((held, ), 0, jnp.int32), lax.expand_dims(back, (1, )), j, numbers,
                       indices_are_sorted=True, mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    return first, take, back


def paged_attention_flat(q, kpool, vpool, tables, lengths, start_pos, n_tokens, *, chunk: int,
                         block_size: int, softmax_scale: Optional[float] = None,
                         window: Optional[int] = None, alibi_slopes=None,
                         value_dim: Optional[int] = None, selection=None):
    """:func:`paged_attention` over a pass's tokens as they lie on ONE flat axis:
    q [S, H, Dh], sequence 0's ``n_tokens[0]`` tokens first, then sequence 1's,
    the tail past ``sum(n_tokens)`` dead (``models.transformer.flat_chunk_indices``);
    ``chunk`` is the bucket's T, the most tokens a sequence may hold.  Returns
    [S, H, Dv]; a dead slot's value is finite and never used (zero from the
    kernel, slot 0's from the fallback).  No array of the padded ``[N, chunk]``
    size is built: q is laid KV-major at the flat size (``[KV, R, Dh]``, row =
    position x group + head within the group; one KV head: a reshape), each
    sequence begun on a whole sublane tile of rows (a gather of the flat q where
    ``group`` is no multiple of ``SMALL_ROWS``), and the kernel finds sequence
    ``n``'s window at the row offset ``row0[n]`` of its plan, which is data.
    A window is ``rows`` long whatever the sequence holds, so it lies over the
    rows of the sequences behind it: reading them is harmless, and the kernel
    writes a window's output only after every earlier window's.  One spare
    window behind the last position keeps the last copy in bounds."""
    s, hq, dh = q.shape
    scale = _checked_scale(dh, vpool, value_dim, softmax_scale)
    if not _use_pallas():
        return _dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens,
                               scale, window, alibi_slopes, value_dim, chunk=chunk,
                               selection=selection)
    n, kvh = n_tokens.shape[0], kpool.shape[1]
    group, dv = hq // kvh, dh if value_dim is None else value_dim
    shape = step_tile(chunk, hq, kvh, dh, kpool.shape[2], q.dtype, kpool.dtype, value_dim)
    align = SMALL_ROWS // math.gcd(group, SMALL_ROWS)  # tokens whose rows are whole tiles
    if selection is not None:
        _check_selected_rows(group, shape)  # align is 1: the kernel's token axis is the flat one
    held = flat_token_slots(n, s, group) + -(-shape[1] // group)  # and the spare window
    n_tokens = lax.convert_element_type(n_tokens, jnp.int32)
    if align == 1:  # every token's rows are whole tiles: the flat axis as it is
        first, back = lax.sub(lax.cumsum(n_tokens, axis=0), n_tokens), None
        qk = lax.pad(q, np.zeros((), q.dtype), ((0, held - s, 0), (0, 0, 0), (0, 0, 0)))
    else:
        first, take, back = _flat_rows(n_tokens, s, held, align)
        qk = _rows_at(q, take)
    qr = lax.reshape(lax.transpose(lax.reshape(qk, (held, kvh, group, dh)), (1, 0, 2, 3)),
                     (kvh, held * group, dh))
    out = _walk(qr, lax.div(lax.mul(first, np.int32(group)), np.int32(SMALL_ROWS)), kpool, vpool,
                tables, lengths, start_pos, n_tokens, group=group, shape=shape, scale=scale,
                window=window, alibi_slopes=alibi_slopes, value_dim=value_dim, selection=selection)
    out = lax.reshape(lax.transpose(lax.reshape(out, (kvh, held, group, dv)), (1, 0, 2, 3)),
                      (held, hq, dv))
    return lax.slice_in_dim(out, 0, s, axis=0) if back is None else _rows_at(out, back)


def _check_selected_rows(group: int, shape) -> None:
    """A selection is a token's, and the kernel's rows are (token, q head of
    the group): it is handed over by token, so a window and a row tile must be
    whole tokens (or a tile lie inside one) and a token's rows whole sublane tiles."""
    _, rows, _, tile, _ = shape
    if group % SMALL_ROWS or rows % group or (tile % group and group % tile):
        raise ValueError(
            f"paged_attention: a selection needs the {group} q heads of a KV head to be whole "
            f"tiles of {SMALL_ROWS} rows and a step's {rows} rows in tiles of {tile} to be whole "
            f"tokens; serve this family with a head count that is a multiple of {SMALL_ROWS}")


def _walk(qr, row0, kpool, vpool, tables, lengths, start_pos, n_tokens, *, group, shape, scale,
          window, alibi_slopes, value_dim, selection=None):
    """The kernel's call.  ``qr`` [KV, R, Dh]: q KV-major on the flat row axis;
    ``row0`` [N] int32: where each sequence's window of rows begins, in
    sublane tiles of SMALL_ROWS (``R`` holds the last live window whole).  Returns the output
    on the same rows, [KV, R, Dv]; rows of no token are zero.  ``selection`` bool
    [tokens, MAXB x bs] on the same axis, a token ``group`` rows: what each may attend."""
    kvh, total, dh = qr.shape
    (n, maxb), bs = tables.shape, kpool.shape[2]
    kvg, rows, splits, tile, slots = shape
    dv = dh if value_dim is None else value_dim
    alibi = alibi_slopes is not None
    # a window's walk begins at the first block a query of the step sees; under a selection
    # (whose tiles lie by the table's own steps) every live block is walked as before
    skips = window is not None and selection is None
    check_block_table_fits(n, maxb, n_vectors=(4 if alibi else 3) + 3 + skips)  # and the plan's rows
    kernel = functools.partial(_paged_kernel, scale=scale, block_size=bs, group=group,
                               kvg=kvg, tile=tile, slots=slots, head_steps=kvh // kvg,
                               splits=splits, window=window, alibi=alibi, value_dim=value_dim,
                               **({} if selection is None else {"selected": True}),
                               **({"begins": 2 + (splits > 1)} if skips else {}))
    pools = (kpool, vpool) if value_dim is None else (kpool, )
    def q_window(ni, g, r, b, tables, lengths, start, ntok, plan, *_):
        """Where the step's q rows begin on the flat axis (an element, not a
        block: whole sublane tiles, which the compiler must see).  A row split
        past the last that holds a token stays on that one: nothing to fetch."""
        at = lax.mul(plan[ROW0, ni], SMALL_ROWS)
        if splits > 1:
            at = pl.multiple_of(lax.add(at, lax.mul(
                lax.min(r, lax.max(lax.sub(plan[SPLITS, ni], 1), 0)), rows)), SMALL_ROWS)
        return lax.mul(g, kvg), at, 0

    # the pools and the output stay where they are: the kernel copies a step's live
    # blocks itself (``slots`` of them into one tile a pool, the next step's while
    # this one computes) and a window's output rows back; q's window comes by the
    # pipeline, from wherever the plan says the sequence's rows begin
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    steps = pl.cdiv(maxb, slots)
    selections = []
    if selection is not None:
        # the window's tokens' selections among the step's keys: the groups of
        # SEL_GROUP tokens from the one the window's first token lies in (one more
        # than the window's tokens fill: the first lies anywhere among its group's eight)
        groups = -(-(rows // group) // SEL_GROUP) + 1
        selections = [_selection_tiles(selection, total // group, groups, steps, slots * bs)]

        def selected_window(ni, g, r, b, tables, lengths, start, ntok, plan, *_):
            _, at, _ = q_window(ni, g, r, b, tables, lengths, start, ntok, plan)
            return (lax.div(lax.div(at, group), SEL_GROUP),
                    _last_live_step(b, plan[BLOCKS, ni], slots), 0, 0)

        select_spec = [pl.BlockSpec((pl.Element(groups), pl.Element(1), pl.Element(SEL_GROUP),
                                     pl.Element(slots * bs)), selected_window)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6 if alibi else 5,
        grid=(n, kvh // kvg, splits, steps),
        in_specs=[pl.BlockSpec((pl.Element(kvg), pl.Element(rows), pl.Element(dh)), q_window)]
        + (select_spec if selections else []) + [anywhere] * (len(pools) + 1),
        out_specs=anywhere,
        scratch_shapes=[
            pltpu.VMEM((kvg, rows, dv), jnp.float32),
            pltpu.VMEM((kvg, rows, 128), jnp.float32),
            pltpu.VMEM((kvg, rows, 128), jnp.float32),
            pltpu.VMEM((kvg, rows, dv), qr.dtype),
            *(pltpu.VMEM((2, kvg, slots * bs, dh), pool.dtype) for pool in pools),
            pltpu.SemaphoreType.DMA((2, len(pools) + 1)),  # a pool's two halves; [0, -1] the output's
            pltpu.SMEM((3, ), jnp.int32),
        ],
    )
    tables, lengths, start_pos, n_tokens = (lax.convert_element_type(x, jnp.int32)
                                            for x in (tables, lengths, start_pos, n_tokens))
    scalars = [tables, lengths, start_pos, n_tokens,
               _fetch_plan(lengths, n_tokens, row0, bs, maxb, group, rows, splits,
                           *((start_pos, window) if skips else ()))]
    if alibi:
        scalars.append(jnp.asarray(alibi_slopes, jnp.float32))
    # rows no window writes come back zero: the output begins as zeros, aliased in
    zeros = lax.full((kvh, total, dv), 0, qr.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(zeros.shape, zeros.dtype),
        input_output_aliases={len(scalars) + 1 + len(selections) + len(pools): 0},
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", ) * 4,  # the fetch runs ahead across all four
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=_pallas.INTERPRET,
        name="paged_attention",
    )(*scalars, qr, *selections, *pools, zeros)


def _flat_slots(n_tokens, s: int):
    """(row [s], col [s], live [s]): whose token a flat slot holds (a dead slot:
    [0, 0]), from ``n_tokens`` alone (``transformer.flat_chunk_indices`` wants
    the tables and positions as arrays too)."""
    ends = jnp.cumsum(n_tokens)
    j = jnp.arange(s)
    row = jnp.sum(j[:, None] >= ends[None, :], axis=1)
    live = row < n_tokens.shape[0]
    row = jnp.where(live, row, 0)
    return row, jnp.where(live, j - (ends - n_tokens)[row], 0), live


def _dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens, scale,
                    window, alibi_slopes=None, value_dim: Optional[int] = None,
                    chunk: Optional[int] = None, selection=None):
    """Reference-math path: gather the whole table, masked sdpa (the v2
    engine's original implementation — kept as the CPU/parity baseline).  Both
    forms: q [N, T, H, Dh], or with ``chunk`` the flat q [S, H, Dh] of
    :func:`paged_attention_flat`, scattered onto the padded ``[N, chunk]`` here
    (it may pad as it likes) and gathered back."""
    from ...models.transformer import sdpa
    if chunk is not None:
        row, col, live = _flat_slots(n_tokens, q.shape[0])
        n = n_tokens.shape[0]
        onto = lambda a: jnp.zeros((n, chunk) + a.shape[1:], a.dtype).at[
            jnp.where(live, row, n), col].set(a, mode="drop")
        out = _dense_fallback(onto(q), kpool, vpool, tables, lengths, start_pos, n_tokens, scale,
                              window, alibi_slopes, value_dim,
                              selection=None if selection is None else onto(selection))
        return out[row, col]  # a dead slot: sequence 0's first token, as the padded form gathered it
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
    if selection is not None:  # a learned selection: of what is visible, only what was chosen
        mask = jnp.logical_and(mask, selection)
    bias = None
    if alibi_slopes is not None:
        bias = (jnp.asarray(alibi_slopes, jnp.float32)[None, :, None, None]
                * jnp.arange(maxb * bs, dtype=jnp.float32)[None, None, None, :])
    out = sdpa(q, ctx_k, ctx_v, causal=False, mask=mask[:, None, :, :],
               softmax_scale=scale, bias=bias)
    return jnp.where((qp >= 0)[..., None], out, 0.0)
