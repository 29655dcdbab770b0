"""Kimi Delta Attention's recurrence (Kimi Linear, arXiv:2510.26692; FLA's
``fla/ops/kda`` are the reference's kernels): the gated delta rule with a decay
A CHANNEL of the key, so a head's memory of a sequence is one matrix ``S`` ``[dk,
dv]`` float32 and

    S <- diag(alpha_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t

with ``alpha_t = exp(g_t)`` a VECTOR over the ``dk`` key channels (Gated
DeltaNet's, ``gated_delta.py``, is one number a head) and ``beta_t`` a write
strength a head.  ``g`` is BOUNDED below: ``LOWER_BOUND <= g <= 0`` (the
published ``kda_safe_gate``: ``g = kda_lower_bound x sigmoid(..)``, -5), which is
what lets the chunked form below stay inside float32; a family checks its bound
against :data:`LOWER_BOUND`.

**The state by reference**, as ``ssd.py`` states it: every function here takes
the carried state as the WHOLE flat leaf ``[slots, H, dk, dv]`` float32, the
rows' slots ``at`` ``[rows]`` and a flag a row, ``begins`` (what the slot of a
sequence that begins holds counts as zero), and returns the leaf: each row's slot
holds its new matrices, every slot no live row names is bit for bit what it was.
On the TPU the kernels index the slots themselves (slots and flags
scalar-prefetched, the leaf aliased in and out: a row's 2 MB a layer at 32 heads
of 128 x 128 read where they lie once and written there once); off it
``ssd._by_value`` gathers, updates and scatters.

**One token** (:func:`kda_step`): a decode row, a burst's step, a one-token row
of a mixed pass.  The recurrence as written, float32, element-wise over the
state.  On the TPU (or with ``_pallas.INTERPRET``) the Pallas kernel
``kda_update``: grid (row, ``UPDATE_HEADS`` heads); a head's ``alpha``, ``k``,
``beta k`` and ``q`` come as ROWS ``[4, dk]`` (a column costs a lane tile an
element in memory) and are turned into columns in registers (the diagonal of a
row's broadcast), so ``S^T k`` and ``S^T q`` are sums over sublanes and the
outer product a broadcast: no product on the MXU, nothing rounded.

**A chunked scan** (:func:`kda_scan`): the tokens of a step in chunks of
``CHUNK`` = 64.  With ``gamma_i`` ``[dk]`` the chunk's running sum of ``g``, the
algebra is Gated DeltaNet's with ``exp(gamma)`` a ``[C, dk]`` matrix and the
decay's ratio INSIDE the contractions:

    A_ij = sum_c k_i[c] k_j[c] exp(gamma_i[c] - gamma_j[c])      (i > j)
    B_ij = sum_c q_i[c] k_j[c] exp(gamma_i[c] - gamma_j[c])      (i >= j)
    M  = (I + tril(diag(beta) A, -1))^-1          (``gated_delta._unit_lower_inverse``)
    W  = M (beta (K * exp(gamma))),   U = M (beta V),   V' = U - W S_0
    O  = (Q * exp(gamma)) S_0 + tril(B) V'
    S_1 = diag(exp(gamma_C)) S_0 + (K * exp(gamma_C - gamma))^T V'

``A`` and ``B`` are taken a ROW BLOCK of ``SUB`` = 16 at a time as ONE product of
two factors, ``(k_i exp(gamma_i - rho))`` and ``(k_j exp(rho - gamma_j))`` with
``rho`` the block's first row of ``gamma``.  ``gamma`` falls along the chunk, so
the first factor is at most 1, the second at most 1 for every EARLIER block's
keys and at most ``exp((SUB - 1) x 5) = 3.7e32`` for the block's own, which
float32 and bfloat16 hold (their largest: 3.4e38); the keys of LATER blocks are
not in the product at all (a static slice: their factor would pass float32, and
the causal mask takes them anyway; zeros stand in their columns).  That is what
the bound of -5 is for and why a block is 16 rows: ``exp(rho - gamma_j)`` over a
whole chunk of 64 would reach ``exp(315)``.  Accumulations are float32; the
products' operands are in the dtype q, k and v come in (bfloat16 on the chip),
but for the inverse's chain (three passes, as Gated DeltaNet's).

**A pair of heads together** (``_chunk``, as ``gated_delta.py``'s "The value
heads of a key head together").  A chunk of the algebra takes ``p`` heads at
once (``_pair``: ``gated_delta.heads_a_step``'s rule over a grid step's heads,
2 where they are even, 1 where odd), their chunks one under another as ``p C``
= 128 rows, the MXU's.  KDA's heads have their own keys, so less is shared than
in Gated DeltaNet.  *A head's own:* its four row blocks of ``A`` and ``B``
(``[2 SUB, dk] x [p C, dk]^T``, the factors above exactly) and what meets its
state: ``W S_0``, ``(Q e^gamma) S_0``, ``K^T V'``.  *Once a pair:* ``I + N`` as
ONE block-diagonal ``[p C, p C]`` with a head's ``[C, C]`` a block, inverted by
the same ten products, joined up to ``C`` and no further; ``W``, ``U`` and
``tril(B) V'`` one product each with the heads' right-hand sides stacked.
**The zeros between the blocks are PLACED, never computed**: a row block's
right factor is the head's keys up to the block's last with zero ROWS above and
below (the later blocks' and the partner's), so the product's other columns
are exact zeros and stay zeros through every product.  One head's rows against
its partner's column factor is never taken: that factor is not bounded by the
block rule, and ``inf x 0`` reads NaN (Gated DeltaNet got its zeros from
``exp(-1e30)``; here they come from the layout).  Passes of the MXU a pair a
chunk: 8 + 30 + 3 + 6 = 47 (the chain's ten products in three each), against 2
x (4 + 30 + 6) = 80 a head at a time, and the chain's ladder of eight stages is
walked once a pair.  The pairs of a grid step go through the algebra in
LOCK-STEP (``_chunks``: one ``vmap``, so each stage of every pair's ladder
stands beside the others' in the program and the MXU is fed by one while
another's result drains; a Python loop over them walks the ladders one after
another, PERF.md PR 61).  The result is the one-head algebra's to the bit: the
stacked products sum the same terms and exact zeros.

**Sequences on one axis** and **a pass of chunks** (:func:`kda_chunks`) are
``ssd.py``'s, shared and not copied: the padded layout is
``gated_delta.lay_on_chunk_edges`` (a row's chunks in place), the compacted one
``ssd._lay_window`` (sized by the TOKENS of the pass: ``ceil(S / CHUNK) +
WINDOW`` chunks hold any ``WINDOW`` walked rows, more rows run the walk again),
the chunk table ``gated_delta._chunk_table``'s with the scan kernel's three rows
more (the chunk's slot, whether its sequence begins, the chunk whose blocks an
empty grid step names); a row of ONE token is the update's, a row of more the
scan's, each passing the other's rows by on the trash slot.  On the TPU the walk
is the Pallas kernel ``kda_scan``: grid (``SCAN_HEADS`` = 8 heads = four pairs a
step, chunk), q, k, v ``[heads, C, d]``, ``gamma`` ``[heads, C, dk]`` float32,
``beta`` a row ``[heads, C]``, ``S`` in VMEM scratch between a sequence's
chunks.  Off the TPU the same chunk mathematics, the same pairs, every pair at
once, under ``lax.scan``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...compat import CompilerParams
from .. import _pallas
from .gated_delta import (FIRST, LAST, LIVE, SEQ, _dot_f32, _unit_lower_inverse, heads_a_step,
                          lay_on_chunk_edges)
from .ssd import (BEGINS, BLOCK, CHUNK, NN, NT, TN, _by_value, _dot, _heads_a_step, _lay_window,
                  _slot_table, scan_chunks, walk_trips)

SUB = 16            # rows of a block of A and B: one reference row ``rho`` a block
LOWER_BOUND = -5.0  # the least ``g`` a token a channel: (SUB - 1) x 5 = 75 < 88, float32's exponent
SCAN_HEADS = 8      # heads one grid step of ``kda_scan`` takes: four pairs in lock-step (PERF.md, PR 61)
UPDATE_HEADS = 8    # heads one grid step of ``kda_update`` takes: 8 x [128, 128] float32 = 512 KiB
assert CHUNK % SUB == 0 and -(SUB - 1) * LOWER_BOUND < 87.0


def _column(row, eye):
    """A row vector ``[1, n]`` as a column ``[n, 1]``: the diagonal of its broadcast
    (``eye`` ``[n, n]`` bool)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _eye(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


# ------------------------------------------------------------------- one token
def kda_step(q, k, v, g, beta, leaf, at, begins, passed=None):
    """One token a row.  q, k ``[N, H, dk]`` (l2-normalised, q scaled), v ``[N, H,
    dv]``, g ``[N, H, dk]`` float32 (``LOWER_BOUND <= g <= 0``), beta ``[N, H]``;
    leaf ``[slots, H, dk, dv]`` float32, the carried state whole, at ``[N]`` the
    rows' slots (distinct, but for the dead rows' one trash slot), begins ``[N]``
    bool -> (o ``[N, H, dv]`` float32, leaf): every row's slot updated, no other
    touched.  ``passed`` ``[N]`` bool (None: no row): rows that hold no token of
    this function's and name the trash slot (:func:`kda_chunks`); o is nothing at
    them."""
    q, k, v, g = (a.astype(jnp.float32) for a in (q, k, v, g))
    beta = beta.astype(jnp.float32)[..., None]
    if _pallas.use_pallas():
        flags = begins.astype(jnp.int32)
        if passed is not None:
            flags = flags + 2 * passed.astype(jnp.int32)
        keys = jnp.stack([jnp.exp(g), k, beta * k, q], axis=2)  # [N, H, 4, dk]
        return _update_pallas(at.astype(jnp.int32), flags, keys, (beta * v)[:, :, None], leaf,
                              interpret=_pallas.INTERPRET)

    def step(rows):
        s = rows * jnp.exp(g)[..., None]
        d = beta * (v - jnp.sum(s * k[..., None], axis=-2))
        s = s + k[..., None] * d[..., None, :]
        return jnp.sum(s * q[..., None], axis=-2), s

    return _by_value(leaf, at, begins, step,
                     live=None if passed is None else jnp.logical_not(passed))


def _update_body(at_ref, flags_ref, keys_ref, bv_ref, state_ref, o_ref, out_ref):
    heads, dk, _ = state_ref.shape[1:]
    eye = _eye(dk)

    def update(continues: bool):
        for h in range(heads):
            keys = keys_ref[0, h]  # [4, dk]: alpha, k, beta k, q as rows
            alpha, k, bk, q = (_column(keys[i:i + 1], eye) for i in range(4))
            if continues:
                s = state_ref[0, h] * alpha
                s = s + k * (bv_ref[0, h] - jnp.sum(s * bk, axis=0, keepdims=True))
            else:  # a sequence that begins: S = 0, so d = beta v
                s = k * bv_ref[0, h]
            out_ref[0, h] = s
            o_ref[0, h] = jnp.sum(s * q, axis=0, keepdims=True)

    begins = flags_ref[pl.program_id(0)] > 0  # a row passed by writes, like one that begins, what it read not

    @pl.when(jnp.logical_not(begins))
    def _continues():
        update(True)

    @pl.when(begins)
    def _begins():  # what the slot held is not read: it may be anything
        update(False)


@functools.partial(jax.jit, static_argnames=("interpret", ), inline=True)
def _update_pallas(at, flags, keys, bv, leaf, *, interpret):
    """``flags`` ``[N]``: 1 a row whose sequence begins, 2 or 3 a row passed by."""
    n, (heads, dk, dv) = at.shape[0], leaf.shape[1:]
    step = _heads_a_step(heads, UPDATE_HEADS)
    of_heads = lambda r, g, at, flags: (r, g, 0, 0)
    in_slot = lambda r, g, at, flags: (at[r], jnp.where(flags[r] > 1, 0, g), 0, 0)
    o, leaf = pl.pallas_call(
        _update_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n, heads // step),
            in_specs=[pl.BlockSpec((1, step, 4, dk), of_heads),
                      pl.BlockSpec((1, step, 1, dv), of_heads),
                      pl.BlockSpec((1, step, dk, dv), in_slot)],
            out_specs=[pl.BlockSpec((1, step, 1, dv), of_heads),
                       pl.BlockSpec((1, step, dk, dv), in_slot)]),
        out_shape=[jax.ShapeDtypeStruct((n, heads, 1, dv), jnp.float32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        input_output_aliases={4: 1},  # the leaf, in place: a row's slot alone is read and written
        compiler_params=CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_update",
    )(at, flags, keys, bv, leaf)
    return o[:, :, 0], leaf


# ------------------------------------------------------------ one chunk's algebra
def _chunk(q, k, v, gamma, beta, s0):
    """One chunk of ``p`` heads (:func:`_pair`: 2 or 1), their chunks one under another as
    ``p C`` rows.  q, k ``[p, C, dk]``, v ``[p, C, dv]`` (their dtype is the products'
    operand dtype), gamma ``[p, C, dk]`` float32 (the chunk's running sum of g), beta ``[p,
    C]`` float32, s0 ``[p, dk, dv]`` float32.  A head's row blocks of ``A`` and ``B`` are its
    own products, laid on the diagonal of ``[p C, p C]`` beside PLACED zeros; ``I + N`` is
    inverted as one, ``W``, ``U`` and the chunk's own part of ``O`` are one product each;
    what meets the state is a product a head.  Returns (o ``[p, C, dv]`` float32, s1)."""
    heads, c, dk = q.shape
    dtype, size = q.dtype, heads * c
    r = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
    stacked = lambda parts: parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    of = lambda a, h: a[h * c:(h + 1) * c]  # head h's rows
    eye, eye_k = _eye(c), _eye(dk)
    beta_c = stacked([_column(beta[h:h + 1], eye) for h in range(heads)])
    q32, k32 = (a.reshape(size, dk).astype(jnp.float32) for a in (q, k))
    gamma = gamma.reshape(size, dk)
    zeros = lambda n: [jnp.zeros((n, dk), dtype)] if n else []
    a_rows, b_rows = [], []
    for top in range(0, size, c):  # a head: its first row
        for lo in range(top, top + c, SUB):
            rho = gamma[lo:lo + 1]  # the block's first row: every factor below is at most exp(75)
            ahead = jnp.exp(gamma[lo:lo + SUB] - rho)  # <= 1
            # the head's keys up to the block's last: <= 1 before the block, up to exp((SUB - 1)
            # x 5) inside it.  The later blocks' (their factor would pass float32, and the causal
            # mask takes them anyway) and the other head's are never multiplied: zeros stand there
            upto = slice(top, lo + SUB)
            behind = (k32[upto] * jnp.exp(rho - gamma[upto])).astype(dtype)
            behind = jnp.concatenate(zeros(top) + [behind] + zeros(size - lo - SUB), axis=0)
            both = _dot(jnp.concatenate([k32[lo:lo + SUB] * ahead, q32[lo:lo + SUB] * ahead], axis=0),
                        behind, NT, dtype)  # [2 SUB, p C]
            a_rows.append(both[:SUB])
            b_rows.append(both[SUB:])
    n = jnp.where(r > col, beta_c * jnp.concatenate(a_rows, axis=0), 0.0)
    inv = _unit_lower_inverse(n, r, col, _dot_f32 if dtype != jnp.float32 else functools.partial(
        jnp.dot, preferred_element_type=jnp.float32), top=c)
    e = jnp.exp(gamma)
    w = _dot(inv, beta_c * e * k32, NN, dtype)
    u = _dot(inv, beta_c * v.reshape(size, -1).astype(jnp.float32), NN, dtype)
    v_new = stacked([of(u, h) - _dot(of(w, h), s0[h], NN, dtype) for h in range(heads)])
    within = jnp.where(r >= col, jnp.concatenate(b_rows, axis=0), 0.0)
    inner = _dot(within, v_new, NN, dtype)
    eq = e * q32
    o, s1 = [], []
    for h in range(heads):
        o.append(_dot(of(eq, h), s0[h], NN, dtype) + of(inner, h))
        last = gamma[(h + 1) * c - 1:(h + 1) * c]  # the chunk's whole decay, a row over the channels
        s1.append(_column(jnp.exp(last), eye_k) * s0[h]
                  + _dot(jnp.exp(last - of(gamma, h)) * of(k32, h), of(v_new, h), TN, dtype))
    return jnp.stack(o), jnp.stack(s1)


def _pair(heads: int) -> int:
    """The heads one chunk of the algebra takes together: ``gated_delta.heads_a_step``'s rule
    over the heads of a grid step (2 where they are even and ``2 x CHUNK <= 128``, else 1)."""
    return heads_a_step(_heads_a_step(heads, SCAN_HEADS))


def _chunks(q, k, v, gamma, beta, s0):
    """:func:`_chunk` over heads (the first axis of every argument), :func:`_pair` of them at
    a time and the pairs in LOCK-STEP: one ``vmap``, so stage by stage the pairs' inverse chains
    stand side by side in the program, and one pair's product enters the MXU while another's
    leaves it (a Python loop over the pairs walks the ladders one after another: PERF.md,
    PRs 46 and 61)."""
    pair = _pair(q.shape[0])
    paired = lambda a: a.reshape((a.shape[0] // pair, pair) + a.shape[1:])
    whole = lambda a: a.reshape((-1, ) + a.shape[2:])
    o, s1 = jax.vmap(_chunk)(*map(paired, (q, k, v, gamma, beta, s0)))
    return whole(o), whole(s1)


# ------------------------------------------------------------- a pass of chunks
def kda_chunks(q, k, v, g, beta, leaf, at, begins, trash, n_tokens, row=None, col=None):
    """A step whose rows hold any number of tokens (a prompt's pieces, and beside
    them decode rows and a prompt's last piece of one token): :func:`kda_scan`'s
    arguments and ``trash``, a slot of ``leaf`` that no row names.  The rows of
    ONE token are :func:`kda_step`'s, each from its first slot of the axes; the
    scan walks the rows that hold more and nothing else (``ssd.ssd_chunks``
    states the division: which row is which is ``n_tokens == 1``, data; either
    passes the other's rows by on the trash slot).  Returns (o ``[b, s, H, dv]``
    in v's dtype, leaf)."""
    single = n_tokens == 1
    if row is None:
        first = lambda a: a[:, 0]
    else:  # the compacted axis: a row begins where the rows before it end
        start = jnp.minimum(jnp.cumsum(n_tokens) - n_tokens, q.shape[1] - 1)
        first = lambda a: a[0, start]
    with jax.named_scope("kda_update"), jax.named_scope("kda_state"):
        o_single, leaf = kda_step(first(q), first(k), first(v), first(g), first(beta), leaf,
                                  jnp.where(single, at, trash), begins, jnp.logical_not(single))
    with jax.named_scope("kda_scan"):
        o, leaf = kda_scan(q, k, v, g, beta, leaf, jnp.where(single, trash, at), begins, n_tokens,
                           row, col, walked=n_tokens > 1)
        o_single = o_single.astype(o.dtype)
        if row is None:
            return o.at[:, 0].set(jnp.where(single[:, None, None], o_single, o[:, 0])), leaf
        # a row of another count lands nowhere
        return o.at[0, jnp.where(single, start, q.shape[1])].set(o_single, mode="drop"), leaf


# ----------------------------------------------------------------- the chunked scan
def kda_scan(q, k, v, g, beta, leaf, at, begins, n_tokens, row=None, col=None, walked=None):
    """The chunked scan over a step's tokens.  q, k ``[b, s, H, dk]`` (l2-
    normalised, q scaled), v ``[b, s, H, dv]``, g ``[b, s, H, dk]`` float32
    (``LOWER_BOUND <= g <= 0``), beta ``[b, s, H]`` float32, ``[b, s]`` = ``[N,
    T]`` (``row`` None) or the compacted ``[1, S]`` (``row``, ``col`` ``[1,
    S]``); leaf ``[slots, H, dk, dv]`` float32, the carried state whole, at
    ``[N]`` the rows' slots, begins ``[N]`` bool; n_tokens ``[N]``; ``walked``
    ``[N]`` bool, the rows this scan walks (None: every row that holds a token;
    a row not walked takes no chunk, o is zero at its tokens, and its ``at``
    names a slot no walked row names).  Returns (o ``[b, s, H, dv]`` in v's
    dtype, leaf).  A row with no token writes no slot."""
    heads, dk = q.shape[2:]
    counts = n_tokens if walked is None else jnp.where(walked, n_tokens, 0)

    def walk(leaf, table, laid, chunks, at, begins, live):
        """One layout walked: ``at``, ``begins``, ``live`` of the sequences the table's ``SEQ``
        row names."""
        qa, ka, va = laid(q), laid(k), laid(v)  # [H, chunks C, d]
        gamma = jnp.cumsum(laid(g.astype(jnp.float32)).reshape(heads, chunks, CHUNK, dk), axis=2)
        gamma = gamma.reshape(heads, chunks * CHUNK, dk)
        ba = laid(beta.astype(jnp.float32)[..., None])[..., 0].reshape(heads, chunks, CHUNK)
        if not _pallas.use_pallas():
            step = lambda rows: _walk_scan(table, qa, ka, va, gamma, ba, rows)
            return _by_value(leaf, at, begins, step, live=live)
        return _walk_pallas(_slot_table(table, at, begins), qa, ka, va, gamma, ba, leaf,
                            interpret=_pallas.INTERPRET)

    if row is None:
        table, laid, back, chunks = lay_on_chunk_edges(counts, q.shape[:2], None, None, CHUNK)
        o, leaf = walk(leaf, table, laid, chunks, at, begins, counts > 0)
        # the chunks no step computed hold whatever was there: a row's dead positions read zero
        held = jnp.arange(q.shape[1])[None, :] < counts[:, None]
        return jnp.where(held[:, :, None, None], back(jnp.moveaxis(o, 0, 1)), 0), leaf
    walked = counts > 0
    chunks = scan_chunks(0, 0, q.shape[1])

    def trip(carry):
        w, o, leaf = carry
        table, laid, back, rows, held = _lay_window(n_tokens, walked, w, chunks, row, col)
        new, leaf = walk(leaf, table, laid, chunks, at[rows], begins[rows], held)
        return w + 1, back(jnp.moveaxis(new, 0, 1), o), leaf

    trips = walk_trips(jnp.sum(walked, dtype=jnp.int32))
    _, o, leaf = jax.lax.while_loop(lambda carry: carry[0] < trips, trip,
                                    (jnp.int32(0), jnp.zeros_like(v), leaf))
    return o, leaf


def _walk_scan(table, q, k, v, gamma, beta, state):
    """The walk in XLA: a ``lax.scan`` over the chunks, every head at once.  q, k, v, gamma
    ``[H, chunks C, d]``, beta ``[H, chunks, C]``, state ``[N, H, dk, dv]``."""
    heads, dk, dv = q.shape[0], q.shape[-1], v.shape[-1]
    chunks = table.shape[1]
    by_chunk = lambda a: jnp.moveaxis(a.reshape(heads, chunks, CHUNK, -1), 1, 0)

    def one(carry, inp):
        s, states = carry
        (seq, first, last, live), qc, kc, vc, gc, bc = inp
        s = jnp.where(first > 0, states[seq], s)
        o, s1 = _chunks(qc, kc, vc, gc, bc, s)  # the kernel's pairs
        s = jnp.where(live > 0, s1, s)
        states = states.at[jnp.where(last > 0, seq, states.shape[0])].set(s, mode="drop")
        return (s, states), jnp.where(live > 0, o, 0.0).astype(v.dtype)

    (_, state), o = jax.lax.scan(
        one, (jnp.zeros((heads, dk, dv), jnp.float32), state),
        (table.T, by_chunk(q), by_chunk(k), by_chunk(v), by_chunk(gamma),
         jnp.moveaxis(beta, 1, 0)))
    return jnp.moveaxis(o, 0, 1).reshape(heads, chunks * CHUNK, dv), state


def _scan_body(table_ref, q_ref, k_ref, v_ref, gamma_ref, beta_ref, state_ref, o_ref, out_state_ref,
               s_ref):
    c = pl.program_id(1)

    @pl.when((table_ref[FIRST, c] > 0) & (table_ref[BEGINS, c] == 0))
    def _load():
        s_ref[...] = state_ref[0]

    @pl.when((table_ref[FIRST, c] > 0) & (table_ref[BEGINS, c] > 0))
    def _from_zero():  # what the slot held is not read: it may be anything
        s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)

    @pl.when(table_ref[LIVE, c] > 0)
    def _compute():
        o, s1 = _chunks(q_ref[...], k_ref[...], v_ref[...], gamma_ref[...], beta_ref[0, 0], s_ref[...])
        o_ref[...] = o.astype(o_ref.dtype)
        s_ref[...] = s1

    @pl.when(table_ref[LAST, c] > 0)
    def _store():
        out_state_ref[0] = s_ref[...]


# jitted for its trace cache: every chunk program of a cell traces the kernel once
@functools.partial(jax.jit, static_argnames=("interpret", ), inline=True)
def _walk_pallas(table, q, k, v, gamma, beta, leaf, *, interpret):
    """``table`` ``[6, chunks]`` as ``ssd._walk_pallas`` takes it: ``SEQ`` holds each chunk's
    SLOT of ``leaf``, ``BEGINS`` whether its sequence begins, ``BLOCK`` the chunk whose blocks
    its grid step names (an empty chunk's is the last live one's: it moves nothing, computes
    nothing, and the positions of o that no live chunk covers are never written)."""
    heads, dk, dv = q.shape[0], q.shape[-1], v.shape[-1]
    chunks = table.shape[1]
    step = _heads_a_step(heads, SCAN_HEADS)
    of_heads = lambda g, c, table: (g, table[BLOCK, c], 0)
    in_slot = lambda g, c, table: (table[SEQ, c], g, 0, 0)
    beta = jnp.moveaxis(beta.reshape(heads // step, step, chunks, CHUNK), 1, 2)
    return pl.pallas_call(
        _scan_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(heads // step, chunks),
            in_specs=[pl.BlockSpec((step, CHUNK, dk), of_heads),
                      pl.BlockSpec((step, CHUNK, dk), of_heads),
                      pl.BlockSpec((step, CHUNK, dv), of_heads),
                      pl.BlockSpec((step, CHUNK, dk), of_heads),
                      pl.BlockSpec((1, 1, step, CHUNK), lambda g, c, table: (g, table[BLOCK, c], 0, 0)),
                      pl.BlockSpec((1, step, dk, dv), in_slot)],
            out_specs=[pl.BlockSpec((step, CHUNK, dv), of_heads),
                       pl.BlockSpec((1, step, dk, dv), in_slot)],
            scratch_shapes=[pltpu.VMEM((step, dk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        input_output_aliases={6: 1},  # the leaf, in place: a slot no chunk names is never touched
        compiler_params=CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_scan",
    )(table, q, k, v, gamma, beta, leaf)
