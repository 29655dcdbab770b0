"""The state-space duality recurrence of Mamba-2 (Dao & Gu 2024; ``mamba_ssm``'s
``mamba_chunk_scan_combined`` and ``selective_state_update`` are the reference's
kernels): a selective scan whose decay is ONE SCALAR a head a token, so a head's
memory of a sequence is a matrix ``S`` ``[P, N]`` (``P`` the head's width, ``N``
the state's) and

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;    y_t = S_t C_t + D x_t

with ``x_t`` ``[P]`` the head's input, ``B_t``, ``C_t`` ``[N]`` A GROUP OF HEADS
(``G`` groups of ``H / G`` consecutive heads: head ``h`` reads group ``h // (H /
G)``; Granite 4.0-H has one group, every head's; Nemotron-H eight), ``dt_t > 0``
a step a head a token, ``A < 0`` and ``D`` a scalar a head.  No delta term:
nothing is solved for, and the chunked form is matrix products alone.  Every
function here takes ``B`` and ``C`` as ``[.., G, N]`` or, for one group, as
``[.., N]``; a grid step of either kernel takes whole groups or a part of one
(:func:`_heads_a_step`), and with one group the programs are what they were.
Two forms:

**The state by reference**: both forms take the carried state as the WHOLE
flat leaf ``[slots, H, P, N]`` float32 (every such layer's slots on one axis,
as ``transformer.paged_forward`` carries it), the rows' slots ``at`` ``[rows]``
and a flag a row, ``begins``: what the slot of a sequence that begins holds
counts as zero and reaches nothing.  They return the leaf: each row's slot
holds its new matrices, every slot no live row names is bit for bit what it
was.  On the TPU the kernels index the slots themselves (``at`` and the flags
scalar-prefetched, the state's block of a grid step ``(slot, heads)`` for input
and output alike, the leaf aliased in and out), so a row's matrices are read
from where they lie once and written there once and nothing else moves; off it
``_by_value`` gathers the rows, the ``jax.numpy`` form updates them and a
scatter puts them back.  Live rows name distinct slots; the dead rows of a
step all name one trash slot, which is sound on these grids because a grid
step touches no block but its own slot's: whatever order the dead rows'
reads and writes of the trash slot fall in (a block named twice in a row is
fetched once and written once), they reach the trash slot alone, whose
content no live row reads.

**One token** (:func:`ssd_update`): a decode row and a burst's step; the
recurrence as written.  On the TPU (or with ``_pallas.INTERPRET``) the Pallas
kernel ``ssd_update``: grid (row, ``UPDATE_HEADS`` heads), a step reads its
heads' ``[P, N]`` float32 matrices once and writes them once, in their slot;
the decay comes as a row broadcast along the state's lanes, the outer
product ``(dt x) B^T`` of all the step's heads as ONE product with a padded
contraction of ``PAD`` rows (a column vector costs a whole lane tile an element
in memory, a row nothing), and ``y = S C`` as one product ``C S^T`` whose
result lies along lanes.  A step that spans several groups (Nemotron-H: 32
heads, four groups) keeps both as one product: row ``j`` of the contraction
holds group ``j``'s ``B`` against ``dt x`` of that group's heads alone (zero in
every other head's columns), and row ``j`` of ``C S^T`` is read at group ``j``'s
heads.

**A chunked scan** (:func:`ssd_scan`): the tokens of a step in chunks of
``CHUNK``.  With ``l_i`` the chunk's running sum of ``dt A`` (so ``exp(l_i -
l_j)`` is what token j's write has decayed to at token i), for one head

    Y   = (tril(exp(l_i - l_j)) * (C B^T) * dt_j) X  +  exp(l) * (C S_0^T)  +  D X
    S_1 = exp(l_C) S_0 + (exp(l_C - l) * dt * X)^T B

``C B^T`` ``[C, C]`` once a chunk a group; a head's own work is the mask
and three products.  A sequence's chunks are walked in order with ``S``
carried.  The decays are float32 everywhere and every exponent is <= 0; the
products' operands are in the dtype ``x`` comes in (bfloat16 on the chip: the
state is float32 in memory and rounded where a product reads it, as the gated
delta rule's), the accumulations float32.

**Sequences on one axis**: every walked sequence of a step, padded ``[N, T]`` or
compacted ``[1, S]``, is laid out to begin on a chunk's edge; the first chunk of
a sequence loads its carried matrices from its slot (zeros where it begins),
the last stores them there; a row with no token walks no chunk and its slot is
never written; dead positions hold ``dt = 0`` and change nothing.  The padded
layout is ``gated_delta.py``'s (a row's chunks in place).  The compacted one is
this module's (:func:`_lay_window`) and is sized BY THE TOKENS OF THE PASS, not by
the rows of its bucket: ``ceil(S / CHUNK) + WINDOW`` chunks hold any ``WINDOW``
rows' tokens, and a pass that walks more rows runs the walk again over the rows
left (:func:`ssd_scan`).  On the TPU the walk is the Pallas kernel ``ssd_scan``:
grid (``SCAN_HEADS`` heads, chunk), x and y ``[heads, C, P]``, B and C of the
step's groups ``[groups, C, N]``, the running sums, steps and ``D`` of the heads both as rows ``[3 heads,
C]`` and as columns ``[C, 3 heads]`` (the mask needs ``l_i - l_j``: a column
minus a row), the chunk table (its ``SEQ`` row the sequences' slots, a fifth row
their flags, a sixth the chunk whose blocks a step names: what is left of the
static grid past the live chunks names the last live one's, moves nothing and
computes nothing) scalar-prefetched, ``S`` in VMEM scratch between a sequence's
chunks.  Off the TPU the same chunk mathematics, every head at once, under
``lax.scan``.

**A pass of chunks** (:func:`ssd_chunks`): what a family calls for a step whose
rows hold any number of tokens.  A row of ONE token (a decode row riding beside
the prompts' pieces, a prompt's last piece) is the update kernel's, a row of
more the scan's: a one-token row costs its matrices' read and write and no
chunk of the walk.
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

CHUNK = 64         # positions of one chunk of the scan (PERF.md, PRs 52 and 55: the sweeps 64 / 128 / 256)
WINDOW = 4         # rows one trip of a compacted walk lays out (PERF.md, PR 55: 4 against 8)
SCAN_HEADS = 8     # heads one grid step of ``ssd_scan`` takes
UPDATE_HEADS = 32  # heads one grid step of ``ssd_update`` takes: 32 x [64, 128] float32 = 1 MiB
PAD = 16           # rows a one-row operand is padded to: a whole sublane tile of bfloat16
BEGINS, BLOCK = 4, 5  # the scan kernel's rows of the chunk table, after ``gated_delta``'s four


def walk_trips(walked):
    """Trips a compacted walk of ``walked`` rows takes: ``WINDOW`` rows a trip,
    none where no row is walked (a Python integer or a traced one)."""
    return -(-walked // WINDOW)


def scan_chunks(n: int, t: int, flat=None, walked=None) -> int:
    """The chunks of the layout the scan is given for a ``[n, t]`` bucket: none
    for a step of one token a row (the one-token update), a row's ``ceil(t /
    CHUNK)`` padded; compacted onto ``flat`` slots ``ceil(flat / CHUNK) +
    WINDOW`` a trip, whatever ``n``, and :func:`walk_trips` trips for ``walked``
    rows (None: one trip)."""
    if flat is not None:
        return (-(-flat // CHUNK) + WINDOW) * (1 if walked is None else walk_trips(walked))
    return gated_delta.scan_chunks(n, t, None, CHUNK)


def _heads_a_step(heads: int, most: int, groups: int = 1) -> int:
    """Heads one grid step takes, at most ``most``: the largest divisor of a
    group's heads, or where a group has fewer than ``most`` the heads of as many
    whole groups as fit (at most ``PAD``: a group is a row of the update's
    contraction).  A step so never cuts a group it does not lie inside."""
    if heads % groups:
        raise ValueError(f"ssd: {heads} heads do not divide into {groups} B/C groups")
    a_group = heads // groups
    if a_group >= most:
        return max(h for h in range(1, most + 1) if a_group % h == 0)
    return a_group * max(g for g in range(1, min(most // a_group, PAD) + 1) if groups % g == 0)


def _grouped(v, x):
    """``B`` or ``C`` with its group axis: ``[.., N]`` (one group) -> ``[.., 1, N]``."""
    return v[..., None, :] if v.ndim == x.ndim - 1 else v


def _dot(a, b, dims, dtype):
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


NN = ((1, ), (0, ))  # a @ b
NT = ((1, ), (1, ))  # a @ b^T
TN = ((0, ), (0, ))  # a^T @ b


# ------------------------------------------------------- the state, off the TPU
def _by_value(leaf, at, begins, step, live=None):
    """The by-reference contract where no kernel indexes the slots: the rows'
    matrices gathered (zeros where a sequence begins), ``step(rows) -> (y,
    rows)``, and the rows scattered back to their slots; a row that is not
    ``live`` writes nothing."""
    y, rows = step(jnp.where(begins.reshape(-1, 1, 1, 1), 0.0, leaf[at]))
    if live is not None:
        at = jnp.where(live, at, leaf.shape[0])  # out of bounds: dropped
    return y, leaf.at[at].set(rows, mode="drop")


# ------------------------------------------------------------------- one token
def ssd_update(x, dt, A, B, C, D, leaf, at, begins, passed=None):
    """One token a row.  x ``[N, H, P]``, dt ``[N, H]`` float32 (after its
    softplus), A, D ``[H]``, B, C ``[N, G, Ns]`` (or ``[N, Ns]``: one group);
    leaf ``[slots, H, P, Ns]``
    float32, the carried state whole, at ``[N]`` the rows' slots (distinct, but
    for the dead rows' one trash slot), begins ``[N]`` bool -> (y ``[N, H, P]``
    float32, leaf): every row's slot updated, no other touched.  ``passed``
    ``[N]`` bool (None: no row): rows that hold no token of this kernel's and
    name the trash slot (:func:`ssd_chunks`); y is nothing at them, and on the TPU
    every grid step of such a row names ONE block of the trash slot, so a run of
    them moves it once (a bucket's plain dead rows move it a row each)."""
    B, C = _grouped(B, x), _grouped(C, x)
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * A.astype(jnp.float32))  # [N, H]
    dtx = dt[..., None] * x.astype(jnp.float32)
    if _pallas.use_pallas():
        flags = begins.astype(jnp.int32)
        if passed is not None:
            flags = flags + 2 * passed.astype(jnp.int32)
        y, leaf = _update_pallas(at.astype(jnp.int32), flags, dtx.astype(x.dtype),
                                 decay, B.astype(x.dtype), C.astype(x.dtype), leaf,
                                 interpret=_pallas.INTERPRET)
    else:
        of_head = lambda v: jnp.repeat(v.astype(jnp.float32), x.shape[1] // v.shape[1],
                                       axis=1)[:, :, None, :]  # [N, G, Ns] -> [N, H, 1, Ns]

        def step(rows):
            rows = rows * decay[..., None, None] + dtx[..., None] * of_head(B)
            return jnp.sum(rows * of_head(C), axis=-1), rows

        y, leaf = _by_value(leaf, at, begins, step,
                            live=None if passed is None else jnp.logical_not(passed))
    return y + D.astype(jnp.float32)[None, :, None] * x.astype(jnp.float32), leaf


def _update_body(groups, at_ref, flags_ref, dtx_ref, decay_ref, b_ref, c_ref, state_ref, y_ref,
                 out_ref):
    """``groups``: the B/C groups the step's heads span.  One: ``b_ref`` / ``c_ref`` hold the one
    row.  More: ``PAD`` rows, row ``j`` group ``j``'s (zeros past the last group)."""
    heads, p, ns = state_ref.shape[1:]
    dtype = dtx_ref.dtype
    dtx, b, c = jnp.broadcast_to(dtx_ref[0], (PAD, heads * p)), b_ref[0, 0], c_ref[0, 0]
    if groups == 1:
        first = jax.lax.broadcasted_iota(jnp.int32, (PAD, ns), 0) == 0
        b, c = jnp.where(first, b.astype(jnp.float32), 0.0), jnp.broadcast_to(c, (PAD, ns))
    else:  # row j of the contraction: group j's B against dt x of group j's heads alone
        column = jax.lax.broadcasted_iota(jnp.int32, (PAD, heads * p), 1)
        begin = jax.lax.broadcasted_iota(jnp.int32, (PAD, heads * p), 0) * (heads // groups * p)
        mine = (column >= begin) & (column < begin + heads // groups * p)
        dtx = jnp.where(mine, dtx.astype(jnp.float32), 0.0)  # in float32: the mask is laid out as int32's
    # (dt x) B^T of every head of the step at once: [PAD, heads P]^T [PAD, Ns], one live row a group
    outer = _dot(dtx, b, TN, dtype)
    begins = flags_ref[pl.program_id(0)] > 0  # a row passed by writes, like one that begins, what it read not

    @pl.when(jnp.logical_not(begins))
    def _continues():
        for h in range(heads):
            out_ref[0, h] = state_ref[0, h] * decay_ref[0, h:h + 1, :] + outer[h * p:(h + 1) * p]

    @pl.when(begins)
    def _begins():  # what the slot held is not read: it may be anything
        for h in range(heads):
            out_ref[0, h] = outer[h * p:(h + 1) * p]

    # y = S C as C S^T: the result lies along lanes, one row of PAD alike (with groups: row j is
    # group j's C over every head, read at group j's heads)
    y = _dot(c, out_ref[0].reshape(heads * p, ns), NT, dtype)
    y_ref[0] = y[0:1] if groups == 1 else jnp.sum(jnp.where(mine, y, 0.0), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", ), inline=True)
def _update_pallas(at, flags, dtx, decay, b, c, leaf, *, interpret):
    """``flags`` ``[N]``: 1 a row whose sequence begins, 2 or 3 a row passed by; b, c ``[N, G, Ns]``."""
    n, (heads, p, ns), groups = at.shape[0], leaf.shape[1:], b.shape[1]
    step = _heads_a_step(heads, UPDATE_HEADS, groups)
    a_group = heads // groups
    spans = max(step // a_group, 1)  # the groups a step's heads span
    if spans == 1:  # a step inside one group: its one row
        of_group = lambda r, g, at, flags: (r, g * step // a_group, 0, 0)
        b, c = b[:, :, None], c[:, :, None]
    else:  # a step's groups as the first rows of PAD
        of_group = lambda r, g, at, flags: (r, g, 0, 0)
        b, c = (jnp.pad(v.reshape(n, groups // spans, spans, ns), ((0, 0), (0, 0), (0, PAD - spans), (0, 0)))
                for v in (b, c))
    of_heads = lambda r, g, at, flags: (r, 0, g)
    in_slot = lambda r, g, at, flags: (at[r], jnp.where(flags[r] > 1, 0, g), 0, 0)
    y, leaf = pl.pallas_call(
        functools.partial(_update_body, spans),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n, heads // step),
            in_specs=[pl.BlockSpec((1, 1, step * p), of_heads),
                      pl.BlockSpec((1, step, ns), lambda r, g, at, flags: (r, g, 0)),
                      pl.BlockSpec((1, 1) + b.shape[2:], of_group),
                      pl.BlockSpec((1, 1) + c.shape[2:], of_group),
                      pl.BlockSpec((1, step, p, ns), in_slot)],
            out_specs=[pl.BlockSpec((1, 1, step * p), of_heads),
                       pl.BlockSpec((1, step, p, ns), in_slot)]),
        out_shape=[jax.ShapeDtypeStruct((n, 1, heads * p), jnp.float32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        input_output_aliases={6: 1},  # the leaf, in place: a row's slot alone is read and written
        compiler_params=CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssd_update",
    )(at, flags, dtx.reshape(n, 1, heads * p), jnp.broadcast_to(decay[..., None], (n, heads, ns)),
      b, c, leaf)
    return y.reshape(n, heads, p), leaf


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


# ------------------------------------------------------------- a pass of chunks
def ssd_chunks(x, dt, A, B, C, D, leaf, at, begins, trash, n_tokens, row=None, col=None):
    """A step whose rows hold any number of tokens (a prompt's pieces, and beside
    them decode rows and a prompt's last piece of one token): :func:`ssd_scan`'s
    arguments and ``trash``, a slot of ``leaf`` that no row names.  The rows of
    ONE token are :func:`ssd_update`'s, each from its first slot of the axes
    (begun or continued, as a decode step's rows); the scan walks the rows that
    hold more and nothing else, so a one-token row costs the read and the write
    of its matrices and no chunk.  Which row is which is ``n_tokens == 1``, data;
    either kernel passes the other's rows by on the trash slot (the update is
    told which, ``passed``: a run of them moves one block of it once), so the two
    touch disjoint slots of the one leaf.  Returns (y ``[b, s, H, P]`` in x's
    dtype, leaf)."""
    single = n_tokens == 1
    if row is None:
        first = lambda v: v[:, 0]
    else:  # the compacted axis: a row begins where the rows before it end
        start = jnp.minimum(jnp.cumsum(n_tokens) - n_tokens, x.shape[1] - 1)
        first = lambda v: v[0, start]
    with jax.named_scope("ssm_update"), jax.named_scope("ssm_state"):
        y_single, leaf = ssd_update(first(x), first(dt), A, first(B), first(C), D, leaf,
                                    jnp.where(single, at, trash), begins, jnp.logical_not(single))
    with jax.named_scope("ssm_scan"):
        y, leaf = ssd_scan(x, dt, A, B, C, D, leaf, jnp.where(single, trash, at), begins, n_tokens,
                           row, col, walked=n_tokens > 1)
        y_single = y_single.astype(y.dtype)
        if row is None:
            return y.at[:, 0].set(jnp.where(single[:, None, None], y_single, y[:, 0])), leaf
        # a row of another count lands nowhere
        return y.at[0, jnp.where(single, start, x.shape[1])].set(y_single, mode="drop"), leaf


# ----------------------------------------------------------------- the chunked scan
def ssd_scan(x, dt, A, B, C, D, leaf, at, begins, n_tokens, row=None, col=None, walked=None):
    """The chunked scan over a step's tokens.  x ``[b, s, H, P]``, dt ``[b, s,
    H]`` float32 (after its softplus), A, D ``[H]``, B, C ``[b, s, G, Ns]`` (or ``[b, s, Ns]``: one group), ``[b,
    s]`` = ``[N, T]`` (``row`` None) or the compacted ``[1, S]`` (``row``,
    ``col`` ``[1, S]``); leaf ``[slots, H, P, Ns]`` float32, the carried state
    whole, at ``[N]`` the rows' slots, begins ``[N]`` bool (a sequence that
    begins walks from zeros whatever its slot holds); n_tokens ``[N]``;
    ``walked`` ``[N]`` bool, the rows this scan walks (None: every row that
    holds a token).  A row not walked keeps its tokens' places on the axes and
    nothing else: it takes no chunk, y is zero at its tokens, and its ``at``
    names a slot no walked row names (the trash slot).
    Returns (y ``[b, s, H, P]`` in x's dtype, leaf).  A row with no token
    writes no slot.

    The padded layout is a row's ``ceil(T / CHUNK)`` chunks in place.  The
    compacted one is sized by the tokens of the pass and not by its rows:
    ``ceil(S / CHUNK) + WINDOW`` chunks hold ANY ``WINDOW`` rows' tokens, each
    row on a chunk's edge (:func:`_lay_window`), and a pass that walks more rows
    than that runs the walk again over the rows left (a ``while_loop`` whose
    body is traced once, :func:`walk_trips` trips: none where no row is walked;
    nothing has a capacity, nothing is dropped)."""
    heads = x.shape[2]
    B, C = _grouped(B, x), _grouped(C, x)
    dt = dt.astype(jnp.float32)
    counts = n_tokens if walked is None else jnp.where(walked, n_tokens, 0)

    def walk(leaf, table, laid, chunks, at, begins, live):
        """One layout walked: ``at``, ``begins``, ``live`` of the sequences the table's ``SEQ``
        row names (``live``: the sequence holds a token)."""
        xa = laid(x)  # [H, chunks C, P]
        ba, ca = laid(B), laid(C)  # [G, chunks C, Ns]
        dta = laid(dt[..., None])[..., 0].reshape(heads, chunks, CHUNK)  # 0 where no token sits
        la = jnp.cumsum(dta * A.astype(jnp.float32)[:, None, None], axis=-1)
        d = jnp.broadcast_to(D.astype(jnp.float32)[:, None, None], la.shape)
        if not _pallas.use_pallas():
            step = lambda rows: _walk_scan(table, xa, ba, ca, (la, dta, d), rows)
            return _by_value(leaf, at, begins, step, live=live)
        return _walk_pallas(_slot_table(table, at, begins), xa, ba, ca, (la, dta, d), leaf,
                            interpret=_pallas.INTERPRET)

    if row is None:
        table, laid, back, chunks = lay_on_chunk_edges(counts, x.shape[:2], None, None, CHUNK)
        y, leaf = walk(leaf, table, laid, chunks, at, begins, counts > 0)
        # the chunks no step computed hold whatever was there: a row's dead positions read zero
        held = jnp.arange(x.shape[1])[None, :] < counts[:, None]
        return jnp.where(held[:, :, None, None], back(jnp.moveaxis(y, 0, 1)), 0), leaf
    walked = counts > 0
    chunks = scan_chunks(0, 0, x.shape[1])

    def trip(carry):
        w, y, leaf = carry
        table, laid, back, rows, held = _lay_window(n_tokens, walked, w, chunks, row, col)
        o, leaf = walk(leaf, table, laid, chunks, at[rows], begins[rows], held)
        return w + 1, back(jnp.moveaxis(o, 0, 1), y), leaf

    trips = walk_trips(jnp.sum(walked, dtype=jnp.int32))
    _, y, leaf = jax.lax.while_loop(lambda carry: carry[0] < trips, trip,
                                    (jnp.int32(0), jnp.zeros_like(x), leaf))
    return y, leaf


def _slot_table(table, at, begins):
    """The chunk table a by-reference scan kernel prefetches, ``[6, chunks]``: a chunk's SLOT in
    its sequence's place, ``FIRST``, ``LAST``, ``LIVE``, whether the sequence begins, and the
    chunk whose blocks its grid step names: its own, or for an empty chunk the last live one's
    (as ``_chunk_table`` names that one's sequence: nothing is fetched, nothing stored)."""
    seq, c = table[SEQ], jnp.arange(table.shape[1], dtype=jnp.int32)
    alive = table[LIVE] > 0
    before = jax.lax.cummax(jnp.where(alive, c, -1))
    return jnp.concatenate([
        at.astype(jnp.int32)[seq][None], table[SEQ + 1:], begins.astype(jnp.int32)[seq][None],
        jnp.where(before >= 0, before, jnp.argmax(alive).astype(jnp.int32))[None]])


def _lay_window(n_tokens, walked, trip, chunks: int, row, col):
    """Trip ``trip`` of a compacted walk: the walked rows from the ``trip x
    WINDOW``-th on, each laid to begin on a chunk's edge of a layout of
    ``chunks`` chunks (``ceil(S / CHUNK) + WINDOW``: what ``WINDOW`` rows of at
    most ``S`` tokens in all can take).  ``n_tokens`` ``[N]`` every row's tokens
    (a row not walked still takes its places on the flat axis), ``walked``
    ``[N]``, ``row`` / ``col`` ``[1, S]``.  Returns ``(table, laid, back, rows,
    held)``: the chunk table ``[4, chunks]`` whose ``SEQ`` row names a PLACE OF
    THE WINDOW, ``laid`` as ``lay_on_chunk_edges``', ``back(o [chunks * CHUNK,
    H, d], y [1, S, H, d]) -> y`` with the window's rows' tokens taken from ``o``
    and every other slot as it was, ``rows`` ``[WINDOW]`` the row of the bucket
    each place holds and ``held`` ``[WINDOW]`` whether it holds one (a place
    past the walked rows does not: no token, no chunk)."""
    place = jnp.cumsum(walked) - 1 - trip * WINDOW  # a walked row's place in this window
    w = jnp.arange(WINDOW, dtype=jnp.int32)
    holds = walked[None, :] & (place[None, :] == w[:, None])  # [WINDOW, N]
    rows, held = jnp.argmax(holds, axis=1), jnp.any(holds, axis=1)
    count = jnp.where(held, n_tokens[rows], 0)
    start = (jnp.cumsum(n_tokens) - n_tokens)[rows]  # the row's first flat slot
    per_seq = -(-count // CHUNK)
    ends = jnp.cumsum(per_seq)
    first_chunk = ends - per_seq
    c = jnp.arange(chunks, dtype=jnp.int32)
    seq = jnp.minimum(jnp.sum((c[:, None] >= ends[None, :]).astype(jnp.int32), axis=1), WINDOW - 1)
    nth = c - first_chunk[seq]
    table = gated_delta._chunk_table(seq, nth, jnp.clip(count[seq] - nth * CHUNK, 0, CHUNK), per_seq)
    p = jnp.arange(chunks * CHUNK, dtype=jnp.int32)
    live = (p % CHUNK) < table[LIVE][p // CHUNK]
    source = jnp.where(live, start[seq[p // CHUNK]] + nth[p // CHUNK] * CHUNK + p % CHUNK, 0)

    def laid(a):  # [1, S, H, d] -> [H, chunks * C, d], zero where no token sits
        a = a[0][source]
        mask = live.reshape((-1, ) + (1, ) * (a.ndim - 1))
        return jnp.moveaxis(jnp.where(mask, a, jnp.zeros((), a.dtype)), 1, 0)

    mine = place[row[0]]  # a flat slot's row's place: in this window from 0 to WINDOW - 1
    here = walked[row[0]] & (mine >= 0) & (mine < WINDOW)
    spot = first_chunk[jnp.clip(mine, 0, WINDOW - 1)] * CHUNK + col[0]  # where a flat slot's token went

    def back(o, y):
        taken = o[jnp.clip(spot, 0, chunks * CHUNK - 1)]
        return jnp.where(here.reshape((-1, ) + (1, ) * (taken.ndim - 1)), taken, y[0])[None]

    return table, laid, back, rows, held


def _walk_scan(table, x, b, c, scalars, state):
    """The walk in XLA: a ``lax.scan`` over the chunks, every head at once.
    ``scalars``: l, dt and D, each ``[H, chunks, C]``."""
    heads, p = x.shape[0], x.shape[-1]
    chunks, (groups, _, ns) = table.shape[1], b.shape
    causal = _causal(CHUNK)
    # C B^T once a group; a group's heads against it
    per_head = jax.vmap(_head, in_axes=(0, None, None, None, 0, 0, 0, 0, 0, 0, None))
    per_group = jax.vmap(per_head, in_axes=(0, ) * 10 + (None, ))
    of_group = lambda a: a.reshape((groups, heads // groups) + a.shape[1:])

    def one(carry, inp):
        s, states = carry
        (seq, first, last, live), xc, bc, cc, (lc, tc, dc) = inp
        s = jnp.where(first > 0, states[seq], s)
        scores = jax.vmap(lambda cg, bg: _dot(cg, bg, NT, x.dtype))(cc, bc)
        y, s1 = per_group(of_group(xc), scores, bc, cc, *map(of_group, (
            lc[:, None, :], lc[:, :, None], tc[:, None, :], tc[:, :, None], dc[:, :, None], s)), causal)
        y, s1 = y.reshape((heads, ) + y.shape[2:]), s1.reshape(s.shape)
        s = jnp.where(live > 0, s1, s)
        states = states.at[jnp.where(last > 0, seq, states.shape[0])].set(s, mode="drop")
        return (s, states), jnp.where(live > 0, y, 0.0).astype(x.dtype)

    (_, state), y = jax.lax.scan(
        one, (jnp.zeros((heads, p, ns), jnp.float32), state),
        (table.T, jnp.moveaxis(x.reshape(heads, chunks, CHUNK, p), 1, 0),
         jnp.moveaxis(b.reshape(groups, chunks, CHUNK, ns), 1, 0),
         jnp.moveaxis(c.reshape(groups, chunks, CHUNK, ns), 1, 0),
         tuple(jnp.moveaxis(a, 1, 0) for a in scalars)))
    return jnp.moveaxis(y, 0, 1).reshape(heads, chunks * CHUNK, p), state


def _scan_body(table_ref, x_ref, b_ref, c_ref, rows_ref, cols_ref, state_ref, y_ref, out_state_ref,
               s_ref):
    k = pl.program_id(1)
    heads = x_ref.shape[0]

    @pl.when((table_ref[FIRST, k] > 0) & (table_ref[BEGINS, k] == 0))
    def _load():
        s_ref[...] = state_ref[0]

    @pl.when((table_ref[FIRST, k] > 0) & (table_ref[BEGINS, k] > 0))
    def _from_zero():  # what the slot held is not read: it may be anything
        s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)

    @pl.when(table_ref[LIVE, k] > 0)
    def _compute():
        causal = _causal(b_ref.shape[1])
        rows, cols = rows_ref[0, 0], cols_ref[0, 0]  # [3 heads, C], [C, 3 heads]: l, dt, D
        row = lambda at: rows[at:at + 1]
        column = lambda at: cols[:, at:at + 1]
        groups = b_ref.shape[0]  # the step's groups: C B^T once each, then its heads
        for g in range(groups):
            b, c = b_ref[g], c_ref[g]
            scores = _dot(c, b, NT, b.dtype)
            for h in range(g * heads // groups, (g + 1) * heads // groups):
                y, s1 = _head(x_ref[h], scores, b, c, row(h), column(h), row(heads + h),
                              column(heads + h), column(2 * heads + h), s_ref[h], causal)
                y_ref[h] = y.astype(y_ref.dtype)
                s_ref[h] = s1

    @pl.when(table_ref[LAST, k] > 0)
    def _store():
        out_state_ref[0] = s_ref[...]


# jitted for its trace cache: every chunk program of a cell traces the kernel once
@functools.partial(jax.jit, static_argnames=("interpret", ), inline=True)
def _walk_pallas(table, x, b, c, scalars, leaf, *, interpret):
    """x ``[H, chunks C, P]``, b, c ``[G, chunks C, Ns]``.
    ``table`` ``[6, chunks]``: ``SEQ`` holds each chunk's SLOT of ``leaf``, ``BEGINS`` whether
    its sequence begins, ``BLOCK`` the chunk whose blocks of x, B, C, the scalars and y its grid
    step names: an empty chunk's is the last live one's, so it moves nothing and computes
    nothing, and the positions of y that no live chunk covers are never written."""
    heads, p = x.shape[0], x.shape[-1]
    chunks, (groups, _, ns) = table.shape[1], b.shape
    size = scalars[0].shape[-1]  # a chunk's positions
    step = _heads_a_step(heads, SCAN_HEADS, groups)
    a_group = heads // groups
    spans = max(step // a_group, 1)  # the groups a step's heads span: its blocks of B and C
    # a step's l, dt and D side by side: [groups, chunks, 3 heads, C], and transposed
    rows = jnp.concatenate([a.reshape(heads // step, step, chunks, size) for a in scalars], axis=1)
    rows = jnp.moveaxis(rows, 1, 2)
    of_heads = lambda g, k, table: (g, table[BLOCK, k], 0)
    of_chunk = lambda g, k, table: (g * step // (a_group * spans), table[BLOCK, k], 0)
    of_both = lambda g, k, table: (g, table[BLOCK, k], 0, 0)
    in_slot = lambda g, k, table: (table[SEQ, k], g, 0, 0)
    return pl.pallas_call(
        _scan_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(heads // step, chunks),
            in_specs=[pl.BlockSpec((step, size, p), of_heads),
                      pl.BlockSpec((spans, size, ns), of_chunk),
                      pl.BlockSpec((spans, size, ns), of_chunk),
                      pl.BlockSpec((1, 1, 3 * step, size), of_both),
                      pl.BlockSpec((1, 1, size, 3 * step), of_both),
                      pl.BlockSpec((1, step, p, ns), in_slot)],
            out_specs=[pl.BlockSpec((step, size, p), of_heads),
                       pl.BlockSpec((1, step, p, ns), in_slot)],
            scratch_shapes=[pltpu.VMEM((step, p, ns), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        input_output_aliases={6: 1},  # the leaf, in place: a slot no chunk names is never touched
        compiler_params=CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(table, x, b, c, rows, jnp.moveaxis(rows, 2, 3), leaf)
