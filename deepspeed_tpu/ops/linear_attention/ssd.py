"""The state-space duality recurrence of Mamba-2 (Dao & Gu 2024; ``mamba_ssm``'s
``mamba_chunk_scan_combined`` and ``selective_state_update`` are the reference's
kernels): a selective scan whose decay is ONE SCALAR a head a token, so a head's
memory of a sequence is a matrix ``S`` ``[P, N]`` (``P`` the head's width, ``N``
the state's) and

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;    y_t = S_t C_t + D x_t

with ``x_t`` ``[P]`` the head's input, ``B_t``, ``C_t`` ``[N]`` SHARED BY EVERY
HEAD (one group), ``dt_t > 0`` a step a head a token, ``A < 0`` and ``D`` a
scalar a head.  No delta term: nothing is solved for, and the chunked form is
matrix products alone.  Two forms:

**One token** (:func:`ssd_update`): a decode row and a burst's step; the
recurrence as written.  On the TPU (or with ``_pallas.INTERPRET``) the Pallas
kernel ``ssd_update``: grid (row, ``UPDATE_HEADS`` heads), a step reads its
heads' ``[P, N]`` float32 matrices once and writes them once, aliased onto what
it read; the decay comes as a row broadcast along the state's lanes, the outer
product ``(dt x) B^T`` of all the step's heads as ONE product with a padded
contraction of ``PAD`` rows (a column vector costs a whole lane tile an element
in memory, a row nothing), and ``y = S C`` as one product ``C S^T`` whose
result lies along lanes.

**A chunked scan** (:func:`ssd_scan`): the tokens of a step in chunks of
``CHUNK``.  With ``l_i`` the chunk's running sum of ``dt A`` (so ``exp(l_i -
l_j)`` is what token j's write has decayed to at token i), for one head

    Y   = (tril(exp(l_i - l_j)) * (C B^T) * dt_j) X  +  exp(l) * (C S_0^T)  +  D X
    S_1 = exp(l_C) S_0 + (exp(l_C - l) * dt * X)^T B

``C B^T`` ``[C, C]`` once a chunk for all heads; a head's own work is the mask
and three products.  A sequence's chunks are walked in order with ``S``
carried.  The decays are float32 everywhere and every exponent is <= 0; the
products' operands are in the dtype ``x`` comes in (bfloat16 on the chip: the
state is float32 in memory and rounded where a product reads it, as the gated
delta rule's), the accumulations float32.

**Sequences on one axis**: as ``gated_delta.py`` (whose layout this imports):
every sequence of a step, padded ``[N, T]`` or compacted ``[1, S]``, is laid
out to begin on a chunk's edge; the first chunk of a sequence loads its carried
matrices, the last stores them; dead positions hold ``dt = 0`` and change
nothing.  On the TPU the walk is the Pallas kernel ``ssd_scan``: grid (``SCAN_HEADS``
heads, chunk), x and y ``[heads, C, P]``, B and C ``[C, N]``, the running sums
steps and ``D`` of the heads both as rows ``[3 heads, C]`` and as columns ``[C, 3
heads]`` (the mask needs ``l_i - l_j``: a column minus a row), the chunk table
scalar-prefetched, ``S`` in VMEM scratch between a sequence's chunks.  Off the
TPU the same chunk mathematics, every head at once, under ``lax.scan``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...compat import CompilerParams
from .. import _pallas
from . import gated_delta
from .gated_delta import FIRST, LAST, LIVE, SEQ, lay_on_chunk_edges

CHUNK = 64         # positions of one chunk of the scan (PERF.md, PR 52: the sweep 64 / 128 / 256)
SCAN_HEADS = 8     # heads one grid step of ``ssd_scan`` takes
UPDATE_HEADS = 32  # heads one grid step of ``ssd_update`` takes: 32 x [64, 128] float32 = 1 MiB
PAD = 16           # rows a one-row operand is padded to: a whole sublane tile of bfloat16


def scan_chunks(n: int, t: int, flat=None) -> int:
    """``gated_delta.scan_chunks`` at this scan's ``CHUNK``: the chunks walked for
    a ``[n, t]`` bucket (``flat``: the flat slots it is compacted onto)."""
    return gated_delta.scan_chunks(n, t, flat, CHUNK)


def _heads_a_step(heads: int, most: int) -> int:
    """The largest divisor of ``heads`` that is at most ``most``."""
    return max(h for h in range(1, most + 1) if heads % h == 0)


def _dot(a, b, dims, dtype):
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


NN = ((1, ), (0, ))  # a @ b
NT = ((1, ), (1, ))  # a @ b^T
TN = ((0, ), (0, ))  # a^T @ b


# ------------------------------------------------------------------- one token
def ssd_update(x, dt, A, B, C, D, state):
    """One token a row.  x ``[N, H, P]``, dt ``[N, H]`` float32 (after its
    softplus), A, D ``[H]``, B, C ``[N, Ns]``, state ``[N, H, P, Ns]`` float32
    -> (y ``[N, H, P]`` float32, state)."""
    dt, state = dt.astype(jnp.float32), state.astype(jnp.float32)
    decay = jnp.exp(dt * A.astype(jnp.float32))  # [N, H]
    dtx = dt[..., None] * x.astype(jnp.float32)
    if _pallas.use_pallas():
        y, state = _update_pallas(dtx.astype(x.dtype), decay, B.astype(x.dtype), C.astype(x.dtype),
                                  state, interpret=_pallas.INTERPRET)
    else:
        state = state * decay[..., None, None] + dtx[..., None] * B.astype(jnp.float32)[:, None, None, :]
        y = jnp.sum(state * C.astype(jnp.float32)[:, None, None, :], axis=-1)
    return y + D.astype(jnp.float32)[None, :, None] * x.astype(jnp.float32), state


def _update_body(dtx_ref, decay_ref, b_ref, c_ref, state_ref, y_ref, out_ref):
    heads, p, ns = state_ref.shape[1:]
    dtype = dtx_ref.dtype
    first = jax.lax.broadcasted_iota(jnp.int32, (PAD, ns), 0) == 0
    # (dt x) B^T of every head of the step at once: [PAD, heads P]^T [PAD, Ns], one live row
    outer = _dot(jnp.broadcast_to(dtx_ref[0], (PAD, heads * p)),
                 jnp.where(first, b_ref[0].astype(jnp.float32), 0.0), TN, dtype)
    for h in range(heads):
        out_ref[0, h] = state_ref[0, h] * decay_ref[0, h:h + 1, :] + outer[h * p:(h + 1) * p]
    # y = S C as C S^T: the result lies along lanes, one row of PAD alike
    y = _dot(jnp.broadcast_to(c_ref[0], (PAD, ns)), out_ref[0].reshape(heads * p, ns), NT, dtype)
    y_ref[0] = y[0:1]


@functools.partial(jax.jit, static_argnames=("interpret", ), inline=True)
def _update_pallas(dtx, decay, b, c, state, *, interpret):
    n, heads, p, ns = state.shape
    step = _heads_a_step(heads, UPDATE_HEADS)
    a_row = lambda r, g: (r, 0, 0)
    y, state = pl.pallas_call(
        _update_body,
        grid=(n, heads // step),
        in_specs=[pl.BlockSpec((1, 1, step * p), lambda r, g: (r, 0, g)),
                  pl.BlockSpec((1, step, ns), lambda r, g: (r, g, 0)),
                  pl.BlockSpec((1, 1, ns), a_row), pl.BlockSpec((1, 1, ns), a_row),
                  pl.BlockSpec((1, step, p, ns), lambda r, g: (r, g, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, step * p), lambda r, g: (r, 0, g)),
                   pl.BlockSpec((1, step, p, ns), lambda r, g: (r, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, 1, heads * p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},  # a row's matrices, in place
        compiler_params=CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssd_update",
    )(dtx.reshape(n, 1, heads * p), jnp.broadcast_to(decay[..., None], (n, heads, ns)),
      b[:, None], c[:, None], state)
    return y.reshape(n, heads, p), state


# ------------------------------------------------------------ one chunk's algebra
def _head(x, scores, b, c, l_row, l_col, dt_row, dt_col, d_col, s0, causal):
    """One chunk of one head.  x ``[C, P]``, b, c ``[C, Ns]`` (their dtype is the
    products' operand dtype), scores ``[C, C]`` float32 = ``c b^T``; l (the
    running sum of ``dt A``) and dt as a row ``[1, C]`` and as a column ``[C,
    1]``, the head's ``D`` as a column, float32; s0 ``[P, Ns]`` float32; causal
    ``[C, C]`` bool (i >= j).  Returns (y ``[C, P]`` float32, s1)."""
    dtype = x.dtype
    decay = jnp.exp(jnp.where(causal, l_col - l_row, -1e30))  # what write j is worth at i: 0 above
    y = (_dot(scores * decay * dt_row, x, NN, dtype) + jnp.exp(l_col) * _dot(c, s0, NT, dtype)
         + d_col * x.astype(jnp.float32))
    last = jnp.sum(l_row[:, -1:])  # the chunk's whole decay, a scalar
    written = jnp.exp(last - l_col) * dt_col * x.astype(jnp.float32)
    return y, jnp.exp(last) * s0 + _dot(written, b, TN, dtype)


def _causal(c: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


# ----------------------------------------------------------------- the chunked scan
def ssd_scan(x, dt, A, B, C, D, state, n_tokens, row=None, col=None):
    """The chunked scan over a step's tokens.  x ``[b, s, H, P]``, dt ``[b, s,
    H]`` float32 (after its softplus), A, D ``[H]``, B, C ``[b, s, Ns]``, ``[b,
    s]`` = ``[N, T]`` (``row`` None) or the compacted ``[1, S]`` (``row``,
    ``col`` ``[1, S]``); state ``[N, H, P, Ns]`` float32, each row's carried
    matrices (zeros for a sequence that begins); n_tokens ``[N]``.  Returns (y
    ``[b, s, H, P]`` in x's dtype, the rows' new states).  A row with no token
    keeps its state."""
    heads = x.shape[2]
    table, laid, back, chunks = lay_on_chunk_edges(n_tokens, x.shape[:2], row, col, CHUNK)
    dt = dt.astype(jnp.float32)
    xa = laid(x)  # [H, chunks C, P]
    ba, ca = laid(B[:, :, None])[0], laid(C[:, :, None])[0]  # [chunks C, Ns]
    dta = laid(dt[..., None])[..., 0].reshape(heads, chunks, CHUNK)  # 0 where no token sits
    la = jnp.cumsum(dta * A.astype(jnp.float32)[:, None, None], axis=-1)
    walk = _walk_kernel if _pallas.use_pallas() else _walk_scan
    d = jnp.broadcast_to(D.astype(jnp.float32)[:, None, None], la.shape)
    y, state = walk(table, xa, ba, ca, (la, dta, d), state.astype(jnp.float32))
    return back(jnp.moveaxis(y, 0, 1)), state


def _walk_scan(table, x, b, c, scalars, state):
    """The walk in XLA: a ``lax.scan`` over the chunks, every head at once.
    ``scalars``: l, dt and D, each ``[H, chunks, C]``."""
    heads, p = x.shape[0], x.shape[-1]
    chunks, ns = table.shape[1], b.shape[-1]
    causal = _causal(CHUNK)
    per_head = jax.vmap(_head, in_axes=(0, None, None, None, 0, 0, 0, 0, 0, 0, None))

    def one(carry, inp):
        s, states = carry
        (seq, first, last, live), xc, bc, cc, (lc, tc, dc) = inp
        s = jnp.where(first > 0, states[seq], s)
        scores = _dot(cc, bc, NT, x.dtype)
        y, s1 = per_head(xc, scores, bc, cc, lc[:, None, :], lc[:, :, None], tc[:, None, :],
                         tc[:, :, None], dc[:, :, None], s, causal)
        s = jnp.where(live > 0, s1, s)
        states = states.at[jnp.where(last > 0, seq, states.shape[0])].set(s, mode="drop")
        return (s, states), jnp.where(live > 0, y, 0.0).astype(x.dtype)

    (_, state), y = jax.lax.scan(
        one, (jnp.zeros((heads, p, ns), jnp.float32), state),
        (table.T, jnp.moveaxis(x.reshape(heads, chunks, CHUNK, p), 1, 0),
         b.reshape(chunks, CHUNK, ns), c.reshape(chunks, CHUNK, ns),
         tuple(jnp.moveaxis(a, 1, 0) for a in scalars)))
    return jnp.moveaxis(y, 0, 1).reshape(heads, chunks * CHUNK, p), state


def _scan_body(table_ref, x_ref, b_ref, c_ref, rows_ref, cols_ref, state_ref, y_ref, out_state_ref,
               s_ref):
    k = pl.program_id(1)
    heads = x_ref.shape[0]

    @pl.when(table_ref[FIRST, k] > 0)
    def _load():
        s_ref[...] = state_ref[0]

    @pl.when(table_ref[LIVE, k] > 0)
    def _compute():
        b, c = b_ref[...], c_ref[...]
        scores = _dot(c, b, NT, b.dtype)
        causal = _causal(b.shape[0])
        rows, cols = rows_ref[0, 0], cols_ref[0, 0]  # [3 heads, C], [C, 3 heads]: l, dt, D
        row = lambda at: rows[at:at + 1]
        column = lambda at: cols[:, at:at + 1]
        for h in range(heads):
            y, s1 = _head(x_ref[h], scores, b, c, row(h), column(h), row(heads + h),
                          column(heads + h), column(2 * heads + h), s_ref[h], causal)
            y_ref[h] = y.astype(y_ref.dtype)
            s_ref[h] = s1

    @pl.when(table_ref[LIVE, k] == 0)
    def _empty():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(table_ref[LAST, k] > 0)
    def _store():
        out_state_ref[0] = s_ref[...]


# jitted for its trace cache: every chunk program of a cell traces the kernel once
@functools.partial(jax.jit, static_argnames=("interpret", ), inline=True)
def _walk_pallas(table, x, b, c, scalars, state, *, interpret):
    heads, p = x.shape[0], x.shape[-1]
    chunks, ns = table.shape[1], b.shape[-1]
    size = scalars[0].shape[-1]  # a chunk's positions
    step = _heads_a_step(heads, SCAN_HEADS)
    # a step's l, dt and D side by side: [groups, chunks, 3 heads, C], and transposed
    rows = jnp.concatenate([a.reshape(heads // step, step, chunks, size) for a in scalars], axis=1)
    rows = jnp.moveaxis(rows, 1, 2)
    of_heads = lambda g, k, table: (g, k, 0)
    of_chunk = lambda g, k, table: (k, 0)
    of_both = lambda g, k, table: (g, k, 0, 0)
    seq_state = lambda g, k, table: (table[SEQ, k], g, 0, 0)
    return pl.pallas_call(
        _scan_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(heads // step, chunks),
            in_specs=[pl.BlockSpec((step, size, p), of_heads),
                      pl.BlockSpec((size, ns), of_chunk), pl.BlockSpec((size, ns), of_chunk),
                      pl.BlockSpec((1, 1, 3 * step, size), of_both),
                      pl.BlockSpec((1, 1, size, 3 * step), of_both),
                      pl.BlockSpec((1, step, p, ns), seq_state)],
            out_specs=[pl.BlockSpec((step, size, p), of_heads),
                       pl.BlockSpec((1, step, p, ns), seq_state)],
            scratch_shapes=[pltpu.VMEM((step, p, ns), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},  # the carried states, in place: a row with no chunk keeps its own
        compiler_params=CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(table, x, b, c, rows, jnp.moveaxis(rows, 2, 3), state)


def _walk_kernel(table, x, b, c, scalars, state):
    return _walk_pallas(table, x, b, c, scalars, state, interpret=_pallas.INTERPRET)
