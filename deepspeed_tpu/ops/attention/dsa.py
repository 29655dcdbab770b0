"""A learned selection of the cache: index scores over a paged pool of index
keys, and the exact top-k of them a query token (DeepSeek sparse attention,
DSA: DeepSeek-V3.2-Exp ``inference/model.py`` ``Indexer``; ``glm_moe_dsa``).

A layer that attends a selection caches, beside what it attends, one small
*index key* a token (``k^I_s``, 128 values) in a pool leaf of its own, and
scores every cached token of a query token's sequence with a light
multi-head product that has no softmax::

    I[t, s] = sum_j w[t, j] * relu(q^I[t, j] . k^I[s])        for s <= t

The ``topk`` positions of largest ``I[t, .]`` are the keys the token attends
(all of its past where it has no more than ``topk``), equal scores broken
towards the LOWER position.  :func:`select_keys` returns that set as a mask
``[tokens, table width x block size]`` over the sequence's positions, which
``paged.py``'s kernel and its fallback take as ``selection``.

**The scores** are a product over the blocks of a sequence's table whose
result is the scores themselves, not a softmax's accumulator.  On the TPU a
Pallas kernel (``dsa_index_scores``): the step's query tokens are laid onto an
axis on which every sequence begins a whole tile of ``tt`` tokens (as a
compacted pass's attention lays them onto whole sublane tiles), so a tile is
one sequence's; a grid step is (tile, ``INDEX_BLOCKS`` consecutive table
slots): the slots' blocks of index keys come through four ``BlockSpec``s whose
index is the table's entry (scalar-prefetched), the tile's ``J x tt`` query
rows lie head-major, so ``relu`` of one ``[J x tt, Di] x [Di, keys]`` product,
times ``w``, is summed over the heads by adding ``J`` aligned ``[tt, keys]``
slabs.  Steps past the tile's last position do nothing (and fetch nothing:
their block index repeats the last live one).  Off the TPU the same sum in
plain ``jnp`` over the gathered table.

**The top-k is exact.**  A sort of ``[512, 16k]`` floats in every layer of
every pass will not do, and ``lax.approx_max_k`` is not the published
selection.  A float32 score's bits, read as an ordered unsigned integer
(:func:`ordered_image`), turn "the k-th largest score" into a bisection:
the largest ``v`` with ``count(image >= v) >= k``, a bit a counting pass,
32 passes; the keys above the threshold are in, and of those AT it the
lowest positions fill what is left, their cutoff found by the same bisection
over positions (15 passes for 16.9k of them).  A pass is one fused
compare-and-count over the scores: all 47 take 0.61 ms for 512 tokens over
16,896 positions on the v5e (two bits a pass, three candidates counted at once,
0.69 ms; four bits 1.71: PERF.md, PR 45).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...compat import CompilerParams
from .. import _pallas
from .paged import _flat_rows, _flat_slots, _round_up, _rows_at

INDEX_BLOCKS = 4  # table slots whose index keys one grid step of the score kernel takes
TOKEN_TILE = 64   # query tokens one grid step holds at most (x J heads rows of the product)


def token_tile(t: int) -> int:
    """Query tokens a grid step of the score kernel holds for a bucket of
    ``t`` tokens a sequence: whole float32 sublane tiles, ``TOKEN_TILE`` at most."""
    return min(TOKEN_TILE, _round_up(t, 8))


def ordered_image(scores):
    """float32 -> uint32 whose unsigned order is the floats' order (``-0.0``
    and ``0.0`` are one score, as a comparison of floats has them): a
    positive float's bits with the sign bit set, a negative one's inverted."""
    scores = jnp.where(scores == 0, 0.0, scores.astype(jnp.float32))
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _bisect(holds, bits: int, shape, dtype):
    """The largest ``v`` of ``bits`` bits for which ``holds(v)`` is true, for a
    predicate that is true at 0 and monotone (true up to some ``v``, false
    above), a row apart: a bit a counting pass, from the highest down."""

    def one(i, v):
        tried = v | (jnp.ones((), dtype) << (bits - 1 - i).astype(dtype))
        return jnp.where(holds(tried), tried, v)

    return lax.fori_loop(0, bits, one, jnp.zeros(shape, dtype))


def top_k_mask(scores, valid, k: int):
    """scores ``[R, C]`` float32, valid ``[R, C]`` bool -> bool ``[R, C]``: in
    every row the ``k`` valid columns of largest score, equal scores broken
    towards the lower column; every valid column where a row has no more than
    ``k``.  Exact: no sort, no approximation (the module's docstring)."""
    with jax.named_scope("dsa_select"):
        image = jnp.where(valid, ordered_image(scores), jnp.uint32(0))  # a valid score's is >= 1
        count = lambda hit: jnp.sum(hit, axis=-1, keepdims=True, dtype=jnp.int32)
        # the k-th largest image: the largest v that k columns reach (0: fewer than k are valid)
        rows = scores.shape[:-1] + (1, )
        at = _bisect(lambda v: count(image >= v) >= k, 32, rows, jnp.uint32)
        above = image > at
        tied = (image == at) & (at > 0)
        room = k - count(above)  # how many of the tied columns, lowest first, still fit
        col = lax.broadcasted_iota(jnp.int32, (1, scores.shape[-1]), 1)
        # the largest column c with fewer than `room` tied columns before it: the last one taken
        last = _bisect(lambda c: count(tied & (col < c)) < room,
                       max(1, (scores.shape[-1] - 1).bit_length()), rows, jnp.int32)
        return above | (tied & (col <= last))


def _index_kernel(tables_ref, seq_ref, steps_ref, q_ref, w_ref, *rest, heads: int):
    *key_refs, o_ref = rest
    i, b = pl.program_id(0), pl.program_id(1)
    tt = o_ref.shape[0]

    @pl.when(b < steps_ref[i])
    def _scores():
        keys = jnp.concatenate([ref[...] for ref in key_refs], axis=0)  # [blocks x bs, Di]
        s = lax.dot_general(q_ref[0], keys.astype(q_ref.dtype), (((1, ), (1, )), ((), ())),
                            preferred_element_type=jnp.float32)  # [J x tt, keys], head-major rows
        s = jnp.maximum(s, 0.0) * w_ref[0]
        o_ref[...] = jnp.sum(s.reshape(heads, tt, s.shape[-1]), axis=0)

    @pl.when(b >= steps_ref[i])
    def _nothing():  # past the tile's last position: no key there is visible to any of its tokens
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def _scores_on_tiles(qi, w, seq, steps, ipool, tables, tt: int):
    """The kernel's call.  ``qi`` ``[tiles x tt, J, Di]`` and ``w`` ``[tiles x
    tt, J]`` on an axis of whole tiles, tile ``i`` sequence ``seq[i]``'s,
    scored over that sequence's first ``steps[i]`` x ``INDEX_BLOCKS`` table
    slots.  Returns ``[tiles x tt, C]`` float32, ``C`` the table's width in
    whole steps x the block size; zeros past a tile's last step."""
    total, heads, di = qi.shape
    bs = ipool.shape[2]
    tiles, keys = total // tt, INDEX_BLOCKS * bs
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % INDEX_BLOCKS)), mode="edge")
    n_steps = tables.shape[1] // INDEX_BLOCKS
    # head-major rows inside a tile: the sum over heads adds aligned [tt, keys] slabs
    q = qi.reshape(tiles, tt, heads, di).transpose(0, 2, 1, 3).reshape(tiles, heads * tt, di)
    w = w.astype(jnp.float32).reshape(tiles, tt, heads).transpose(0, 2, 1).reshape(
        tiles, heads * tt, 1)

    def block_of(j):
        def index(i, b, tables, seq, steps):
            live = jnp.minimum(b, jnp.maximum(steps[i] - 1, 0))  # a dead step fetches nothing new
            return tables[seq[i], live * INDEX_BLOCKS + j], 0, 0, 0
        return pl.BlockSpec((None, None, bs, di), index)

    whole_tile = lambda i, b, *_: (i, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(tiles, n_steps),
        in_specs=[pl.BlockSpec((1, heads * tt, di), whole_tile),
                  pl.BlockSpec((1, heads * tt, 1), whole_tile)]
        + [block_of(j) for j in range(INDEX_BLOCKS)],
        out_specs=pl.BlockSpec((tt, keys), lambda i, b, *_: (i, b)))
    return pl.pallas_call(
        functools.partial(_index_kernel, heads=heads), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((total, n_steps * keys), jnp.float32),
        compiler_params=CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                       vmem_limit_bytes=64 << 20),
        interpret=_pallas.INTERPRET, name="dsa_index_scores",
    )(tables.astype(jnp.int32), seq.astype(jnp.int32), steps.astype(jnp.int32), q, w,
      *([ipool] * INDEX_BLOCKS))


def _tile_steps(seq, off, n_tokens, start_pos, tt: int, keys: int):
    """Steps of ``keys`` positions that hold a key some token of a tile sees:
    up to the tile's last live position; none for a tile without a token."""
    held = jnp.clip(n_tokens[seq] - off, 0, tt)
    return jnp.where(held > 0, (start_pos[seq] + off + held - 1) // keys + 1, 0)


def index_scores(qi, w, ipool, tables, start_pos, n_tokens, *, chunk=None):
    """``I[t, s]`` for every token of a step over its own sequence's table.
    ``qi`` ``[N, T, J, Di]`` / ``w`` ``[N, T, J]`` (the padded bucket), or with
    ``chunk`` the flat ``[S, J, Di]`` / ``[S, J]`` of a compacted pass (``chunk``
    the bucket's T); ``ipool`` ``[NB, 1, bs, Di]``, the index keys; ``tables``
    ``[N, MAXB]``.  Returns float32 ``[N, T, MAXB x bs]`` or ``[S, MAXB x bs]``;
    a column past a token's own position holds anything finite."""
    n, maxb = tables.shape
    bs = ipool.shape[2]
    width = maxb * bs
    if not _pallas.use_pallas():
        if chunk is not None:  # the padded form may pad as it likes: scatter, score, gather back
            row, col, live = _flat_slots(n_tokens, qi.shape[0])
            at = (jnp.where(live, row, n), col)
            padded = lambda a: jnp.zeros((n, chunk) + a.shape[1:], a.dtype).at[at].set(
                a, mode="drop")
            return index_scores(padded(qi), padded(w), ipool, tables, start_pos,
                                n_tokens)[row, col]
        keys = ipool[tables][:, :, 0].reshape(n, width, -1)
        s = jnp.einsum("ntjd,ncd->ntjc", qi, keys.astype(qi.dtype),
                       preferred_element_type=jnp.float32)
        return jnp.einsum("ntjc,ntj->ntc", jnp.maximum(s, 0.0), w.astype(jnp.float32))
    keys = INDEX_BLOCKS * bs
    if chunk is None:
        t = qi.shape[1]
        tt = token_tile(t)
        pad = [(0, 0), (0, -t % tt)]
        qi = jnp.pad(qi, pad + [(0, 0), (0, 0)])
        w = jnp.pad(w, pad + [(0, 0)])
        a_seq = qi.shape[1] // tt  # tiles a sequence
        tile = jnp.arange(n * a_seq)
        seq, off = tile // a_seq, tile % a_seq * tt
        out = _scores_on_tiles(qi.reshape((-1, ) + qi.shape[2:]), w.reshape(-1, w.shape[-1]),
                               seq, _tile_steps(seq, off, n_tokens, start_pos, tt, keys),
                               ipool, tables, tt)
        return out.reshape(n, qi.shape[1], -1)[:, :t, :width]
    s, tt = qi.shape[0], token_tile(chunk)
    held = _round_up(s + n * (tt - 1), tt) + tt  # the last position holds nothing
    n_tokens = n_tokens.astype(jnp.int32)
    first, take, back = _flat_rows(n_tokens, s, held, tt)
    ends = first + _round_up(n_tokens, tt)
    at = jnp.arange(held // tt) * tt
    seq = jnp.minimum(jnp.sum(at[:, None] >= ends[None, :], axis=1), n - 1)
    off = at - first[seq]
    steps = jnp.where(off >= 0, _tile_steps(seq, off, n_tokens, start_pos, tt, keys), 0)
    out = _scores_on_tiles(_rows_at(qi, take), _rows_at(w, take), seq, steps, ipool, tables, tt)
    return _rows_at(out, back)[:, :width]


def select_keys(qi, w, ipool, tables, start_pos, n_tokens, *, topk: int, chunk=None):
    """The selection of every token of a step: bool ``[N, T, C]``, or with
    ``chunk`` ``[S, C]`` for the flat tokens of a compacted pass, ``C`` =
    ``MAXB x bs`` the positions of the token's own sequence: true at the
    ``min(position + 1, topk)`` positions ``s <= position`` of largest index
    score (:func:`index_scores`; ties towards the lower position).  A slot that
    holds no token selects nothing."""
    n = tables.shape[0]
    with jax.named_scope("dsa_index"):
        scores = index_scores(qi, w, ipool, tables, start_pos, n_tokens, chunk=chunk)
    if chunk is None:
        col = jnp.arange(qi.shape[1])[None, :]
        live, pos = col < n_tokens[:, None], start_pos[:, None] + col
    else:
        row, col, live = _flat_slots(n_tokens, qi.shape[0])
        pos = start_pos[row] + col
    seen = jnp.arange(scores.shape[-1]) <= jnp.where(live, pos, -1)[..., None]
    flat = (-1, scores.shape[-1])
    return top_k_mask(scores.reshape(flat), seen.reshape(flat), topk).reshape(scores.shape)
