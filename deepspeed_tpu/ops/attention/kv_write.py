"""Pallas TPU writer of a step's new K and V rows into the paged KV pool, in
place (the write half of the reference's linear_blocked_kv_rotary; the read
half is ``paged.py``).

The pool is the flat stack ``[L*NB, KV, bs, width]`` that
``models.transformer.paged_forward`` carries through its layer scan, tiled
(sublanes, 128) over its trailing ``(bs, width)``: a token's row is one sublane
row of a tile (half of a packed pair for bf16) and its KV heads lie a block's
worth of bytes apart.  XLA writes such rows as a serial scatter, one index per
(token, KV head), 50-75 ns each whatever the bytes.  Here the unit of work is a
**tile**: ``tile`` consecutive rows of one block (a whole sublane tile of the
pool's dtype: 16 rows of bf16, 8 of float32, from ``tile_rows``) for every KV
head at once, ``[KV, tile, width]``.

**The work list** (``write_plan``, once a pass, outside the layer scan).  A
sequence's chunk is one run of consecutive positions, so the tiles a step
touches follow from ``n_tokens``, ``start_pos`` and ``block_tables``: the run
cut at tile boundaries (a block is whole tiles).  Entry ``w`` names the block
and tile it writes, which of the tile's rows are new (``r0 <= row < r0 +
cnt``), and where their values lie: the new rows come in as ``[KV, slots,
width]`` (a token slot of ``[b, s]`` per row, one tile of padding in front),
and tile row ``r`` takes source row ``src * tile + phase + r``: consecutive,
because the run is.  The list is ``work`` entries long (a
static bound from the bucket's shape, ``work_bound``), ``count`` of them live;
each (block, tile) appears once, since a block is written by the one sequence
that owns it.  A dead slot of the bucket makes no entry: nothing is written
for it, not even into the trash block.

**One grid step an entry.**  The list is scalar-prefetched and read by the
``BlockSpec`` index maps: the pool's tile is an input and, aliased
(``input_output_aliases``), the output; the two source tiles that hold the
window ``[phase, phase + tile)`` are inputs.  The body rotates the window into
place (in 32 bits: a packed row pair cannot be rotated by one), keeps the old
tile's rows outside ``[r0, r0 + cnt)`` and stores the tile.  Steps past
``count`` name the last live entry again, so they fetch nothing, compute
nothing and write nothing.  K and V (every leaf of the family's pool tree) go
through one call: one list, an aliased operand a leaf.  No arithmetic: the
pool's bytes outside the new rows are what they were, bit for bit.

Off the TPU, or where a block is not whole tiles, the scatter stays
(``write_plan`` returns None and ``kv_write`` takes the ``.at[].set``).
"""

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...compat import CompilerParams
from .. import _pallas
from .._pallas import use_pallas as _use_pallas


class WritePlan(NamedTuple):
    """The tiles one pass writes, the same for every layer: ``count`` ``[1]``
    live entries of ``block`` ``[work]`` (of the layer's pool) and ``table``
    ``[5, work]`` (int32), whose rows are ``TILE`` (of the block), ``SRC`` and
    ``PHASE`` (the first source tile, and the window's first row in it), ``R0``
    and ``CNT`` (the tile's first new row, and how many).  Entries past the
    live ones repeat the last live one."""
    count: jax.Array
    block: jax.Array
    table: jax.Array


TILE, SRC, PHASE, R0, CNT = range(5)


def tile_rows(pools: Sequence[jax.Array]) -> Optional[int]:
    """Rows of one tile of work from the pools' own shapes and dtype: a whole
    sublane tile (32 bytes of a lane: 16 rows of bf16, 8 of float32).  None
    where a block is not whole tiles or the leaves disagree: the scatter's."""
    bs, dtype = pools[0].shape[2], pools[0].dtype
    rows = 32 // jnp.dtype(dtype).itemsize
    same = all(p.shape[2] == bs and p.dtype == dtype for p in pools)
    return rows if same and bs % rows == 0 else None


def work_bound(n: int, t: int, slots: Optional[int], tile: int) -> int:
    """The most tiles a ``[n, t]`` bucket can touch: a run of ``c`` tokens from
    any phase lies in at most ``(c - 1 + tile - 1) // tile + 1`` tiles; compacted
    onto ``slots`` live tokens the runs together in ``slots // tile + 2 n``."""
    padded = n * ((t + tile - 2) // tile + 1)
    return padded if slots is None else min(padded, slots // tile + 2 * n)


def write_plan(pools: Sequence[jax.Array], n_tokens, start_pos, block_tables, *, t: int,
               slots: Optional[int]) -> Optional[WritePlan]:
    """The work list of one pass over a ``[n, t]`` bucket whose new rows come
    as ``[n * t]`` token slots (``slots=None``) or compacted onto ``slots`` flat
    ones (``transformer.flat_chunk_indices``' order).  None where the scatter
    writes instead (off the TPU, or ``tile_rows`` finds no tile)."""
    tile = tile_rows(pools) if _use_pallas() else None
    if tile is None:
        return None
    count, row, column, live, table = _tiles_of_a_pass(
        n_tokens.astype(jnp.int32), start_pos.astype(jnp.int32), t=t, slots=slots, tile=tile,
        per_block=pools[0].shape[2] // tile)  # a block is whole tiles
    block = block_tables.astype(jnp.int32).reshape(-1)[row * block_tables.shape[1] + column]
    return WritePlan(count=count, block=jnp.where(live, block, 0), table=table)


# jitted for its trace cache alone (inlined where it is called): every one of a
# cell's 38-70 step programs makes this list, and those that differ only in the
# table's width make the same one
@functools.partial(jax.jit, static_argnames=("t", "slots", "tile", "per_block"), inline=True)
def _tiles_of_a_pass(n_tokens, start_pos, *, t, slots, tile, per_block):
    """``write_plan`` but for the one fact it takes from the block tables:
    ``(count, row, column, live, table)``, entry ``w`` writing block
    ``block_tables[row[w], column[w]]`` where ``live[w]``."""
    n = n_tokens.shape[0]
    end_pos = start_pos + n_tokens
    first_tile = start_pos // tile  # of the sequence, counted from its position 0
    tiles = jnp.where(n_tokens > 0, (end_pos - 1) // tile - first_tile + 1, 0)
    ends = jnp.cumsum(tiles)
    count = ends[-1:]
    # where a run's rows lie among the token slots: row i's first token
    base = jnp.arange(n, dtype=jnp.int32) * t if slots is None else jnp.cumsum(n_tokens) - n_tokens
    # an entry past the live ones is the last live one again: its grid step
    # fetches nothing new (``kv_write``), computes nothing and writes nothing
    w = jnp.minimum(jnp.arange(work_bound(n, t, slots, tile), dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    row = jnp.sum((w[:, None] >= ends[None, :]).astype(jnp.int32), axis=1)
    live = row < n  # false only with no live token at all: entry 0 then keeps every row
    row = jnp.minimum(row, n - 1)
    to_tile, start, end, to_slot = jnp.stack(
        [first_tile - (ends - tiles), start_pos, end_pos, base - start_pos])[:, row]
    g = w + to_tile  # the sequence's g-th tile
    lo, hi = jnp.maximum(start, g * tile), jnp.minimum(end, (g + 1) * tile)
    r0 = lo - g * tile
    # source row of the tile's row 0, past one tile of padding in front (held
    # inside the padded source whatever the caller's promise of live tokens)
    window = jnp.minimum(to_slot + lo - r0 + tile, (n * t if slots is None else slots) + tile - 1)
    table = jnp.stack([g % per_block, window // tile, window % tile, r0, hi - lo])
    return count, row, g // per_block, live, jnp.where(live[None, :], table, 0)


def _write_kernel(count_ref, first_ref, block_ref, table_ref, *refs):
    del first_ref, block_ref  # the index maps' own
    leaves = len(refs) // 4
    ins, outs = refs[:3 * leaves], refs[3 * leaves:]
    i = pl.program_id(0)

    # a step past the live entries holds the last live tile again: leave it be.
    # Step 0 always runs: with nothing live its entry keeps every row (cnt 0)
    @pl.when((i < count_ref[0]) | (i == 0))
    def _merge():
        # few operations, and select in place of where: every program traces
        # and lowers this body once
        phase, r0, cnt = table_ref[PHASE, i], table_ref[R0, i], table_ref[CNT, i]
        rows = outs[0].shape[2]
        shift = (rows - phase) % rows  # rolled[r] = tile[(r + phase) % rows]
        r = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1)
        from_lo = r + phase < rows
        keep = (r < r0) | (r >= r0 + cnt)
        for leaf, out_ref in enumerate(outs):
            lo_ref, hi_ref, old_ref = ins[3 * leaf:3 * leaf + 3]
            # 32 bits a value: a packed pair of rows cannot be rotated by one row
            wide = jnp.float32 if out_ref.dtype.itemsize < 4 else out_ref.dtype
            shape = out_ref.shape[1:]
            lo = pltpu.roll(lo_ref[...].astype(wide), shift, 1)
            hi = pltpu.roll(hi_ref[...].astype(wide), shift, 1)
            new = jax.lax.select(jnp.broadcast_to(from_lo, shape), lo, hi)
            out_ref[0] = jax.lax.select(jnp.broadcast_to(keep, shape), old_ref[0].astype(wide),
                                        new).astype(out_ref.dtype)


def kv_write(pools, rows, first, blk, off, plan: Optional[WritePlan]):
    """``pools[i][first + blk, :, off] = rows[i]`` for every token slot of the
    pass: ``pools`` the flat stacks ``[L*NB, KV, bs, width]``, ``rows`` one
    ``[b, s, KV, width]`` a pool, ``first`` the layer's first row ``l * NB``,
    ``blk``/``off`` ``[b, s]`` the slots' pool coordinates (a dead slot's the
    trash block).  With a ``plan`` (``write_plan`` of the same pass) the Pallas
    writer does it a tile at a time and dead slots write nothing; with None it
    is the scatter, one index per (token, KV head): a token's heads written as
    one window (``.at[row, :, off]``) makes the compiler relayout the pool, two
    copies a pass."""
    if plan is None:
        head_idx = jnp.arange(pools[0].shape[1])[None, None, :]
        at = (first + blk)[:, :, None]
        return [pool.at[at, head_idx, off[:, :, None]].set(new) for pool, new in zip(pools, rows)]
    return _write_tiles(plan, jnp.asarray(first, jnp.int32)[None], list(pools), list(rows),
                        interpret=_pallas.INTERPRET)


def _pool_tile(i, count, first, block, table):
    return first[0] + block[i], 0, table[TILE, i], 0


def _source_tile(nth, i, count, first, block, table):
    return 0, table[SRC, i] + nth, 0


# jitted for its trace cache, as ``_tiles_of_a_pass``
@functools.partial(jax.jit, static_argnames=("interpret", ), inline=True)
def _write_tiles(plan: WritePlan, first, pools, rows, *, interpret):
    tile = tile_rows(pools)
    operands, in_specs = [], []
    for pool, new in zip(pools, rows):
        kvh, width = pool.shape[1], pool.shape[3]
        # [b, s, KV, W] -> [KV, slots, W], a tile of padding in front (a window
        # may start before slot 0) and whole tiles with one to spare behind
        src = new.reshape(-1, kvh, width).transpose(1, 0, 2)
        src = jnp.pad(src, ((0, 0), (tile, -src.shape[1] % tile + tile), (0, 0)))
        operands += [src, src, pool]
        in_specs += [pl.BlockSpec((kvh, tile, width), functools.partial(_source_tile, 0)),
                     pl.BlockSpec((kvh, tile, width), functools.partial(_source_tile, 1)),
                     pl.BlockSpec((1, kvh, tile, width), _pool_tile)]
    scalars = [plan.count, first, plan.block, plan.table]
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(plan.block.shape[0], ), in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, p.shape[1], tile, p.shape[3]), _pool_tile)
                       for p in pools]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={len(scalars) + 3 * leaf + 2: leaf for leaf in range(len(pools))},
        compiler_params=CompilerParams(dimension_semantics=("arbitrary", )),
        interpret=interpret,
        name="kv_write",
    )(*scalars, *operands)
