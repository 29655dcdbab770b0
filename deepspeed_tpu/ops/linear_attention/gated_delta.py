"""The gated delta rule (Gated DeltaNet; FLA's ``chunk_gated_delta_rule`` and
``fused_recurrent_gated_delta_rule`` are the reference's kernels): a linear
attention whose memory of a sequence is one matrix ``S`` ``[dk, dv]`` a head,

    S <- alpha_t S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t

with ``alpha_t = exp(g_t)`` a decay and ``beta_t`` a write strength, both one a
head a token.  Two forms:

**One token** (:func:`gated_delta_step`): a decode row and a burst's step.  The
recurrence as written, float32, element-wise over the state.

**A chunked scan** (:func:`gated_delta_scan`): the tokens of a step in chunks of
``CHUNK`` = 64.  With ``gamma_i`` the chunk's running sum of ``g`` and ``Gamma_ij
= exp(gamma_i - gamma_j)`` (i >= j), the ``C`` sequential updates of a chunk are

    A  = (I + tril(diag(beta) (Gamma * K K^T), -1))^-1        (unit lower triangular)
    W  = A (beta * exp(gamma) * K),   U = A (beta * V)
    V' = U - W S_0                                            ([C, dk] x [dk, dv])
    O  = (exp(gamma) * Q) S_0 + tril(Gamma * Q K^T) V'        ([C, dk] x [dk, dv])
    S_1 = exp(gamma_C) S_0 + (exp(gamma_C - gamma) * K)^T V'

and a sequence's chunks are walked in order with ``S`` carried.  The inverse is
taken without a sequential solve (``_unit_lower_inverse``): the strict lower
triangle's diagonal blocks of 16 are nilpotent, so each is inverted by ``(I -
D)(I + D^2)(I + D^4)(I + D^8)``, all at once as one block-diagonal
matrix, and the blocks are then joined two by two by the block formula: ten
products.  Accumulations are float32 everywhere; the
products' operands are in the dtype q, k and v come in (bfloat16 on the chip,
as FLA's: the state is float32 in memory and rounded where a product reads it),
but for the inverse's chain, whose float32 operands are split into a bfloat16
head and tail and multiplied in three passes (about 16 bits).  With float32
inputs every product is float32.

**The value heads of a key head together** (``_chunk``).  Key head ``j`` serves
value heads ``j * rep .. j * rep + rep - 1``; ``K K^T`` and ``Q K^T`` do not
depend on the value head, ``beta`` and ``gamma`` (so ``N``, ``Gamma``) and the
state do.  A chunk of the algebra takes ``r`` of them at once
(``heads_a_step``: the largest divisor of ``rep`` with ``r x C <= 128``, the
MXU's rows: 2 for Qwen3-Next's 32 value heads over 16 key heads, 1 where
``rep`` is odd), their chunks one under another as ``r C`` rows: ``K K^T`` and
``Q K^T`` once, ``I + N`` ONE block-diagonal ``[r C, r C]`` matrix with a
head's ``[C, C]`` a block (what is between the blocks is ``exp(-1e30)``, an
exact zero, and stays one through every product: no head leaks into another)
whose inverse is the same ten products, joined up to ``C`` and no further;
``W``, ``U`` and ``tril(Gamma * Q K^T) V'`` one product each with the heads'
right-hand sides stacked; ``W S_0``, ``(exp(gamma) * Q) S_0`` and ``K^T V'``
a product a head, each head having its own ``S``.  Products a chunk a key
head at r = 2: 2 + 10 + 3 + 6 = 21 (41 passes of the MXU, the chain's ten in
three each; 15 of the 21 are ``[128, 128] x [128, 128]``), against 2 x (10 + 8)
= 36 (76 passes) of ``[64, 64 | 128]`` a head at a time.  The chain keeps its
three passes: in one pass it reads NaN on keys that resemble one another, a
correction step after it or not, and is no faster (PERF.md, PR 46).

**Sequences on one axis.**  A step's tokens come as ``[N, T]`` (a row a
sequence, ``n_tokens`` live) or compacted onto one flat axis ``[1, S]`` (``row``
/ ``col``: whose token a flat slot holds; ``models/transformer.py
paged_forward``).  Either way every sequence is laid out to BEGIN ON A CHUNK'S
EDGE (a gather, outside the kernel): chunk ``c`` belongs to one sequence, the
first chunk of a sequence loads its carried matrix, the last stores it, and a
sequence's last chunk is padded with positions that change nothing (k = v = q
= 0, beta = 0, g = 0).  A flat axis of S slots over N sequences is at most
``ceil(S / C) + N`` chunks (:func:`scan_chunks`, which the serving counters ask
too); the chunks past the live ones are skipped.

On the TPU (or with ``_pallas.INTERPRET``) the walk is a Pallas kernel,
``gdn_scan``: grid (key head x ``rep / r``, chunk), a step the ``r`` value
heads of one key head (q and k ``[C, dk]`` once through the index map, not
repeated in memory; v and o ``[r, C, dv]``, the rows ``[r, 2, C]``), the chunk
table scalar-prefetched, the carried states ``[r, dk, dv]`` of the chunk's
sequence fetched by their ``BlockSpec`` when the sequence changes, ``S`` in
VMEM scratch (``[r, dk, dv]`` float32) between a sequence's chunks, the new
states stored (aliased onto the old) at a sequence's last chunk.  Off the TPU
the same chunk mathematics, the same ``r`` heads at once, runs under
``lax.scan``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...compat import CompilerParams
from .. import _pallas

CHUNK = 64
SEQ, FIRST, LAST, LIVE = range(4)  # rows of the chunk table


def scan_chunks(n: int, t: int, flat=None, chunk: int = CHUNK) -> int:
    """Chunks the scan walks for a ``[n, t]`` bucket (``flat``: the flat slots
    it is compacted onto, or None): none for a step of one token a row (the
    one-token update), a row's ``ceil(t / chunk)`` padded, ``ceil(flat / chunk)
    + n`` compacted (every sequence begins on a chunk's edge)."""
    if flat is not None:
        return -(-flat // chunk) + n
    return 0 if t == 1 else n * -(-t // chunk)


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row.  q, k ``[N, H, dk]``, v ``[N, H, dv]``, g, beta ``[N,
    H]``, state ``[N, H, dk, dv]`` float32 -> (o ``[N, H, dv]`` float32, state)."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = state * jnp.exp(g.astype(jnp.float32))[..., None, None]
    d = beta.astype(jnp.float32)[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * d[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


# ------------------------------------------------------------ one chunk's algebra
def _dot(a, b, dims, dtype):
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_f32(a, b):
    """``a @ b`` of float32 ``[C, C]`` matrices to about 16 bits on an MXU that
    multiplies bfloat16: head x head + head x tail + tail x head."""
    a_hi, b_hi = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    return mm(a_hi, b_hi) + mm(a_hi, b_lo) + mm(a_lo, b_hi)


def _same_block(r, col, width):
    """Where row ``r`` and column ``col`` lie in one diagonal block of ``width``."""
    return (r // width) == (col // width)


def _unit_lower_inverse(n, r, col, mm, top: int, leaf: int = 16):
    """``(I + N)^-1`` of a strictly lower triangular ``N`` that is block
    diagonal in blocks of ``top`` rows (a chunk's ``[C, C]``, or the chunks of
    several heads on one diagonal; ``r``, ``col`` its row and column indices,
    ``mm`` the product) without a sequential solve and without the growth of
    one: ``N``'s diagonal blocks of ``leaf`` rows, all at once as one
    block-diagonal matrix ``D`` (``D^leaf = 0``), by ``(I - D)(I + D^2)(I +
    D^4) ...``, whose partial products stay within the binomials of ``leaf``
    and not of ``C`` (over the whole chunk they reach 1e17 for keys that
    resemble one another, and the cancellation that brings them back to the
    inverse's O(1) entries is lost in float32: my chip run, PR 43); then the
    blocks are joined two by two, ``[[A, 0], [E, B]]^-1 = [[A^-1, 0], [-B^-1 E
    A^-1, B^-1]]``, again for every pair at once: ``P - P E P`` with ``E`` the
    part of ``N`` between the halves of each pair, up to blocks of ``top`` and
    no further.  Six products for the leaves of 16, two a doubling: ten for
    chunks of 64, however many of them lie on the diagonal."""
    same = functools.partial(_same_block, r, col)
    diag = jnp.where(same(leaf), n, 0.0)
    inv, power, reach = jnp.where(r == col, 1.0, 0.0) - diag, diag, 2
    while reach < leaf:
        power = mm(power, power)
        inv = inv + mm(inv, power)
        reach *= 2
    width = leaf
    while width < top:
        between = jnp.where(same(2 * width) & jnp.logical_not(same(width)), n, 0.0)
        inv = inv - mm(mm(inv, between), inv)
        width *= 2
    return inv


def heads_a_step(rep: int) -> int:
    """The value heads of a key head that one chunk of the algebra takes
    together: the largest divisor of ``rep`` whose chunks fill no more than the
    MXU's 128 rows (2 where ``rep`` is even, else 1)."""
    return max(r for r in range(1, rep + 1) if rep % r == 0 and r * CHUNK <= 128)


def _chunk(q, k, v, rows, s0):
    """One chunk of the ``r`` value heads that share a key head.  q, k ``[C,
    dk]``, v ``[r, C, dv]`` (their dtype is the products' operand dtype), rows
    ``[r, 2, C]`` float32 (a head's running sum of g, its write strengths), s0
    ``[r, dk, dv]`` float32.  The heads' chunks lie one under another as ``r x
    C`` rows: ``K K^T`` and ``Q K^T`` are taken once for all of them, ``I + N``
    is one block-diagonal matrix with a head's ``[C, C]`` a block, inverted as
    one, and ``W``, ``U`` and the chunk's own part of ``O`` are one product
    each; what meets the state is a product a head.  Returns (o ``[r, C, dv]``
    float32, s1)."""
    heads, c, dtype = v.shape[0], q.shape[0], q.dtype
    size = heads * c
    r = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
    stacked = lambda parts, axis: parts[0] if heads == 1 else jnp.concatenate(parts, axis=axis)
    gamma = stacked([rows[h, 0:1] for h in range(heads)], 1)  # [1, r C], head after head
    beta = stacked([rows[h, 1:2] for h in range(heads)], 1)
    # a row vector as a column: the diagonal of its broadcast
    column = lambda row: jnp.sum(jnp.where(r == col, row, 0.0), axis=1, keepdims=True)
    gamma_c, beta_c = column(gamma), column(beta)
    # Gamma: zero above the diagonal and between the heads
    decay = jnp.exp(jnp.where((r >= col) & _same_block(r, col, c), gamma_c - gamma, -1e30))
    q, k = stacked([q] * heads, 0), stacked([k] * heads, 0)  # a head's rows meet its own block alone
    nt = (((1, ), (1, )))  # a @ b^T
    n = jnp.where(r > col, beta_c * decay * _dot(k, k, nt, dtype), 0.0)
    inv = _unit_lower_inverse(n, r, col, _dot_f32 if dtype != jnp.float32 else functools.partial(
        jnp.dot, preferred_element_type=jnp.float32), top=c)
    k32, e = k.astype(jnp.float32), jnp.exp(gamma_c)
    nn = (((1, ), (0, )))  # a @ b
    tn = (((0, ), (0, )))  # a^T @ b
    w = _dot(inv, beta_c * e * k32, nn, dtype)
    u = _dot(inv, beta_c * v.reshape(size, -1).astype(jnp.float32), nn, dtype)
    of = lambda a, h: a[h * c:(h + 1) * c]  # head h's rows
    v_new = stacked([of(u, h) - _dot(of(w, h), s0[h], nn, dtype) for h in range(heads)], 0)
    within = jnp.where(r >= col, decay * _dot(q, k, nt, dtype), 0.0)
    inner = _dot(within, v_new, nn, dtype)
    eq = e * q.astype(jnp.float32)
    o, s1 = [], []
    for h in range(heads):
        o.append(_dot(of(eq, h), s0[h], nn, dtype) + of(inner, h))
        total = jnp.sum(gamma[:, (h + 1) * c - 1:(h + 1) * c])  # the chunk's whole decay, a scalar
        s1.append(jnp.exp(total) * s0[h] + _dot(jnp.exp(total - of(gamma_c, h)) * of(k32, h),
                                                of(v_new, h), tn, dtype))
    return jnp.stack(o), jnp.stack(s1)


# ------------------------------------------------------- sequences on a chunk's edge
def _chunk_table(seq, nth, count, per_seq):
    """``[4, chunks]`` int32 from each chunk's sequence, its place among the
    sequence's chunks and its live positions (0: an empty chunk, skipped).  SEQ
    of an empty chunk is the nearest live chunk's before it (the first live
    one's where none is): its grid step then names blocks that are there
    already, fetches nothing and stores nothing."""
    c = jnp.arange(seq.shape[0], dtype=jnp.int32)
    live = count > 0
    before = jax.lax.cummax(jnp.where(live, c, -1))
    named = seq[jnp.where(before >= 0, before, jnp.argmax(live))]
    return jnp.stack([named, live & (nth == 0), live & (nth == per_seq[seq] - 1),
                      count]).astype(jnp.int32)


def lay_on_chunk_edges(n_tokens, shape, row=None, col=None, chunk: int = CHUNK):
    """Every sequence of a step laid out to begin on a chunk's edge.  ``shape``
    is the ``[b, s]`` the step's tokens come in: ``[N, T]`` (``row`` None) or the
    compacted ``[1, S]`` (``row``, ``col`` ``[1, S]``); ``n_tokens`` ``[N]``.
    Returns ``(table, laid, back, chunks)``: the chunk table ``[4, chunks]``,
    ``laid(a [b, s, H, ...]) -> [H, chunks * chunk, ...]`` (heads first, zero
    where no token sits) and ``back(o [chunks * chunk, H, d]) -> [b, s, H, d]``."""
    n = n_tokens.shape[0]
    per_seq = -(-n_tokens // chunk)
    if row is None:
        t = shape[1]
        per_row = -(-t // chunk)
        chunks, width = n * per_row, per_row * chunk

        def aligned(a):  # [N, T, ...] -> [N * T', ...], T' whole chunks
            a = jnp.pad(a, ((0, 0), (0, width - t)) + ((0, 0), ) * (a.ndim - 2))
            return a.reshape((n * width, ) + a.shape[2:])

        live = aligned(jnp.arange(t)[None, :] < n_tokens[:, None])
        # a row's chunks in place, the empty chunks of rows that hold fewer among them
        c = jnp.arange(chunks, dtype=jnp.int32)
        seq, nth = c // per_row, c % per_row
        table = _chunk_table(seq, nth, jnp.clip(n_tokens[seq] - nth * chunk, 0, chunk), per_seq)
        back = lambda o: o.reshape((n, width) + o.shape[1:])[:, :t]
    else:
        slots = shape[1]
        chunks = scan_chunks(n, 0, slots, chunk)
        ends = jnp.cumsum(per_seq)
        first_chunk = ends - per_seq
        c = jnp.arange(chunks, dtype=jnp.int32)
        seq = jnp.minimum(jnp.sum((c[:, None] >= ends[None, :]).astype(jnp.int32), axis=1), n - 1)
        nth = c - first_chunk[seq]  # past a sequence's chunks where c is past the last sequence's
        table = _chunk_table(seq, nth, jnp.clip(n_tokens[seq] - nth * chunk, 0, chunk), per_seq)
        p = jnp.arange(chunks * chunk, dtype=jnp.int32)
        live = (p % chunk) < table[LIVE][p // chunk]
        # the position's token: its place in its sequence's chunk of this step, on the flat axis
        source = jnp.where(live, (jnp.cumsum(n_tokens) - n_tokens)[seq[p // chunk]]
                           + nth[p // chunk] * chunk + p % chunk, 0)
        aligned = lambda a: a[0][source]
        place = first_chunk[row[0]] * chunk + col[0]  # where a flat slot's token went
        back = lambda o: o[jnp.clip(place, 0, chunks * chunk - 1)][None]

    def laid(a):  # [b, s, H, d] -> [H, chunks * C, d], zero where no token sits
        a = aligned(a)
        mask = live.reshape((-1, ) + (1, ) * (a.ndim - 1))
        return jnp.moveaxis(jnp.where(mask, a, jnp.zeros((), a.dtype)), 1, 0)

    return table, laid, back, chunks


def gated_delta_scan(q, k, v, g, beta, state, n_tokens, row=None, col=None):
    """The chunked scan over a step's tokens.  q, k ``[b, s, Hk, dk]`` (l2-
    normalised, q scaled), v ``[b, s, Hv, dv]``, g, beta ``[b, s, Hv]`` float32,
    ``[b, s]`` = ``[N, T]`` (``row`` None) or the compacted ``[1, S]`` (``row``,
    ``col`` ``[1, S]``); state ``[N, Hv, dk, dv]`` float32, each row's carried
    matrix (zeros for a sequence that begins); n_tokens ``[N]``.  Returns (o
    ``[b, s, Hv, dv]`` in v's dtype, the rows' new states).  A row with no
    token keeps its state."""
    hk, hv = q.shape[2], v.shape[2]
    table, laid, back, chunks = lay_on_chunk_edges(n_tokens, q.shape[:2], row, col)
    qa, ka, va = laid(q), laid(k), laid(v)
    ga = laid(g.astype(jnp.float32)).reshape(hv, chunks, CHUNK)
    ba = laid(beta.astype(jnp.float32)).reshape(hv, chunks, CHUNK)
    rows = jnp.stack([jnp.cumsum(ga, axis=-1), ba], axis=2)  # [Hv, chunks, 2, C]
    walk = _walk_kernel if _pallas.use_pallas() else _walk_scan
    o, state = walk(table, qa, ka, va, rows, state.astype(jnp.float32), hv // hk)
    return back(jnp.moveaxis(o, 0, 1)).astype(v.dtype), state


def _walk_scan(table, q, k, v, rows, state, rep):
    """The walk in XLA: a ``lax.scan`` over the chunks, every head at once."""
    hv, dk, dv = v.shape[0], q.shape[-1], v.shape[-1]
    chunks, r = table.shape[1], heads_a_step(rep)
    q, k = (jnp.repeat(a, rep // r, axis=0).reshape(hv // r, chunks, CHUNK, dk) for a in (q, k))
    v = v.reshape(hv // r, r, chunks, CHUNK, dv)
    rows = rows.reshape(hv // r, r, chunks, 2, CHUNK)
    per_step = jax.vmap(_chunk)

    def one(carry, inp):
        s, states = carry
        (seq, first, last, live), qc, kc, vc, rc = inp
        s = jnp.where(first > 0, states[seq], s)
        o, s1 = per_step(qc, kc, vc, rc, s.reshape(hv // r, r, dk, dv))
        s = jnp.where(live > 0, s1.reshape(hv, dk, dv), s)
        states = states.at[jnp.where(last > 0, seq, states.shape[0])].set(s, mode="drop")
        return (s, states), jnp.where(live > 0, o.reshape(hv, CHUNK, dv), 0.0)

    (_, state), o = jax.lax.scan(
        one, (jnp.zeros((hv, dk, dv), jnp.float32), state),
        (table.T, *(jnp.moveaxis(a, -3, 0) for a in (q, k, v, rows))))  # the chunks first
    return jnp.moveaxis(o, 0, 1).reshape(hv, chunks * CHUNK, dv), state


def _scan_body(table_ref, q_ref, k_ref, v_ref, rows_ref, state_ref, o_ref, out_state_ref, s_ref):
    c = pl.program_id(1)

    @pl.when(table_ref[FIRST, c] > 0)
    def _load():
        s_ref[...] = state_ref[0]

    @pl.when(table_ref[LIVE, c] > 0)
    def _compute():
        o, s1 = _chunk(q_ref[0], k_ref[0], v_ref[...], rows_ref[:, 0], s_ref[...])
        o_ref[...] = o.astype(o_ref.dtype)
        s_ref[...] = s1

    @pl.when(table_ref[LIVE, c] == 0)
    def _empty():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(table_ref[LAST, c] > 0)
    def _store():
        out_state_ref[0] = s_ref[...]


# jitted for its trace cache: every chunk program of a cell traces the kernel once a layer kind
@functools.partial(jax.jit, static_argnames=("rep", "interpret"), inline=True)
def _walk_pallas(table, q, k, v, rows, state, *, rep, interpret):
    hv, dk, dv = v.shape[0], q.shape[-1], v.shape[-1]
    chunks, r = table.shape[1], heads_a_step(rep)
    per_key = rep // r  # grid steps a key head a chunk
    heads = lambda h, c, table: (h, c, 0)
    seq_state = lambda h, c, table: (table[SEQ, c], h, 0, 0)
    return pl.pallas_call(
        _scan_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(hv // r, chunks),
            in_specs=[pl.BlockSpec((1, CHUNK, dk), lambda h, c, table: (h // per_key, c, 0)),
                      pl.BlockSpec((1, CHUNK, dk), lambda h, c, table: (h // per_key, c, 0)),
                      pl.BlockSpec((r, CHUNK, dv), heads),
                      pl.BlockSpec((r, 1, 2, CHUNK), lambda h, c, table: (h, c, 0, 0)),
                      pl.BlockSpec((1, r, dk, dv), seq_state)],
            out_specs=[pl.BlockSpec((r, CHUNK, dv), heads),
                       pl.BlockSpec((1, r, dk, dv), seq_state)],
            scratch_shapes=[pltpu.VMEM((r, dk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},  # the carried states, in place: a row with no chunk keeps its own
        compiler_params=CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gdn_scan",
    )(table, q, k, v, rows, state)


def _walk_kernel(table, q, k, v, rows, state, rep):
    return _walk_pallas(table, q, k, v, rows, state, rep=rep, interpret=_pallas.INTERPRET)
