"""Kimi Delta Attention's chunked scan and one-token update
(``ops/linear_attention/kda.py``) against the recurrence token by token
(``chipbench/references/bailing_hybrid.py delta_rule``, which shares no algebra
with them): the Pallas kernels in interpret mode and the ``jax.numpy`` forms,
sequences as rows of a padded ``[N, T]`` and compacted onto one flat axis,
lengths on and off a chunk's edge, with and without a carried state, a sequence
over several passes, the state BY REFERENCE (slots in any order, a sequence that
begins over whatever its slot holds, every slot no live row names left bit for
bit), a pass of chunks whose one-token rows go to the update, and what float32
must survive at the published size: decays AT THE BOUND of -5 in every channel,
keys that resemble one another, SiLU's one orthant, at heads of 128; and the PAIR of
heads a chunk of the algebra takes on the MXU's 128 rows: no head reaches its partner,
and an odd count of heads still runs one head a chunk."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references.bailing_hybrid import delta_rule
from deepspeed_tpu.ops import _pallas
from deepspeed_tpu.ops.linear_attention import kda
from deepspeed_tpu.ops.linear_attention.kda import LOWER_BOUND, kda_chunks, kda_scan, kda_step
from deepspeed_tpu.ops.linear_attention.ssd import CHUNK, WINDOW

from .compiled import entry

H, DK, DV = 8, 16, 16  # 8 heads: one grid step of the scan kernel, four pairs in lock-step (16, two steps: the pair's test)
TOL = 2e-5  # float32 throughout, of the largest value: a chunk's products and its inverse against 64 steps


def near(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=tol * max(1.0, float(np.abs(want).max())), rtol=0)


@pytest.fixture(params=["numpy", "kernel"])
def form(request, monkeypatch):
    monkeypatch.setattr(_pallas, "INTERPRET", request.param == "kernel")
    return request.param


@pytest.fixture
def kernel(monkeypatch):
    """The Pallas kernels interpreted (the ``jax.numpy`` form of the same algebra is held by the
    cases that take ``form``)."""
    monkeypatch.setattr(_pallas, "INTERPRET", True)


def unit(x):
    return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def draw(rng, s, h=H, dk=DK, dv=DV, g=None):
    """(q, k, v, g, beta) of one sequence: unit keys and queries, decays a token a channel
    from 0.9999 down to the bound's exp(-5)."""
    q = unit(rng.normal(size=(s, h, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(s, h, dk)))
    v = rng.normal(size=(s, h, dv))
    if g is None:
        g = LOWER_BOUND * rng.uniform(0, 1, size=(s, h, dk)) ** 4
    beta = rng.uniform(0.05, 1.0, size=(s, h))
    return tuple(np.asarray(a, np.float32) for a in (q, k, v, np.broadcast_to(g, (s, h, dk)), beta))


@jax.jit
def _rule(q, k, v, g, beta, state):  # one program a length, not an op at a time
    return delta_rule(q, k, v, jnp.exp(g), beta, state)


def token_by_token(seq, state):
    o, last = _rule(*(jnp.asarray(a) for a in seq), None if state is None else jnp.asarray(state))
    return np.asarray(o), np.asarray(last)


def padded(seqs, counts, t):
    fill = lambda a, c: np.concatenate([a[:c], np.full((t - c, ) + a.shape[1:], -0.7, np.float32)])
    return [jnp.asarray(np.stack([fill(s[i], c) for s, c in zip(seqs, counts)])) for i in range(5)]


def flat(seqs, counts, slots):
    """(arrays [1, S, ...], row, col): the rows' live tokens one after another, the tail dead."""
    row = np.repeat(np.arange(len(counts)), counts)
    col = np.concatenate([np.arange(c) for c in counts])
    dead = slots - len(row)
    arrays = [jnp.asarray(np.concatenate(
        [np.concatenate([s[i][:c] for s, c in zip(seqs, counts)]),
         np.full((dead, ) + seqs[0][i].shape[1:], -0.5, np.float32)]))[None] for i in range(5)]
    at = lambda a: jnp.asarray(np.concatenate([a, np.zeros(dead, int)]))[None]
    return arrays, at(row), at(col)


def scan(arrays, state, counts, row=None, col=None, at=None, begins=None, trash=None):
    """``kda_scan`` over rows in their own slots, none beginning; with ``trash`` (a slot no row
    names) ``kda_chunks``."""
    n = len(counts)
    at = jnp.arange(n, dtype=jnp.int32) if at is None else jnp.asarray(at, jnp.int32)
    begins = jnp.zeros(n, bool) if begins is None else jnp.asarray(begins)
    fn, more = (kda_scan, ()) if trash is None else (kda_chunks, (jnp.int32(trash), ))
    return entry(fn)(*arrays, jnp.asarray(state), at, begins, *more, jnp.asarray(counts, jnp.int32), row, col)


@pytest.mark.parametrize("counts,carried", [((130, 0, 1, 64, 77), True), ((64, 5), False)],
    ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else str(v))
@pytest.mark.parametrize("layout", ["padded", "flat"])
def test_the_chunked_scan_is_the_recurrence_token_by_token(form, layout, counts, carried):
    """Several sequences a pass, each from its own carried matrices, each laid onto a
    chunk's edge; what lies in the dead slots reaches nothing; a row with no token
    keeps its state."""
    rng = np.random.default_rng(sum(counts))
    seqs = [draw(rng, max(c, 1)) for c in counts]
    state = (rng.normal(size=(len(counts), H, DK, DV)) if carried
             else np.zeros((len(counts), H, DK, DV))).astype(np.float32)
    if layout == "padded":
        o, last = scan(padded(seqs, counts, max(counts)), state, counts)
        mine = lambda i, c: np.asarray(o[i, :c])
    else:
        arrays, row, col = flat(seqs, counts, sum(counts) + 11)
        o, last = scan(arrays, state, counts, row, col)
        starts = np.cumsum((0, ) + counts)
        mine = lambda i, c: np.asarray(o[0, starts[i]:starts[i] + c])
    for i, c in enumerate(counts):
        if c == 0:
            np.testing.assert_array_equal(np.asarray(last[i]), state[i])
            continue
        want, want_last = token_by_token([a[:c] for a in seqs[i]], state[i])
        near(mine(i, c), want)
        near(last[i], want_last)


def test_a_sequence_over_several_passes_continues_from_its_slot(kernel):
    """200 tokens as passes of 70, 1, 64 and 65 beside another row, the state by
    reference in a leaf of more slots than rows: slot 5 and slot 2; the first pass begins
    over a slot full of NaN, and the slots no row names stay bit for bit."""
    rng = np.random.default_rng(3)
    whole, other = draw(rng, 200), draw(rng, 130)
    leaf = rng.normal(size=(7, H, DK, DV)).astype(np.float32)
    leaf[5] = np.nan
    untouched = leaf.copy()
    want, want_last = token_by_token(whole, None)
    done, got = 0, []
    for n, (count, begins) in enumerate(zip((70, 1, 64, 65), (True, False, False, False))):
        counts = (count, 130 if n == 0 else 0)
        seqs = [tuple(a[done:done + count] for a in whole), other]
        o, leaf = scan(padded(seqs, counts, max(counts)), leaf, counts, at=(5, 2),
                       begins=(begins, False), trash=6)
        got.append(np.asarray(o[0, :count]))
        done += count
    near(np.concatenate(got), want)
    near(leaf[5], want_last)
    for slot in (0, 1, 3, 4):
        np.testing.assert_array_equal(np.asarray(leaf[slot]), untouched[slot])


@pytest.mark.parametrize("layout", ["padded", "flat"])
def test_a_pass_of_chunks_hands_its_one_token_rows_to_the_update(form, layout):
    """Six rows of which three hold one token (one of them begins), more walked rows than
    one window holds in the flat layout: the update serves the single rows, the scan walks
    the others in two trips, and every row reads the recurrence's values."""
    counts = (1, 70, 1, 3, 1, 9, 66, 2) if layout == "flat" else (1, 70, 1, 3, 1, 9)
    assert sum(c > 1 for c in counts) > WINDOW or layout == "padded"
    rng = np.random.default_rng(11)
    seqs = [draw(rng, c) for c in counts]
    n = len(counts)
    leaf = rng.normal(size=(n + 1, H, DK, DV)).astype(np.float32)
    begins = np.zeros(n, bool)
    begins[[2, 3]] = True
    order = rng.permutation(n)
    if layout == "padded":
        o, new = scan(padded(seqs, counts, max(counts)), leaf, counts, at=order, begins=begins, trash=n)
        mine = lambda i, c: np.asarray(o[i, :c])
    else:
        arrays, row, col = flat(seqs, counts, sum(counts) + 5)
        o, new = scan(arrays, leaf, counts, row, col, at=order, begins=begins, trash=n)
        starts = np.cumsum((0, ) + counts)
        mine = lambda i, c: np.asarray(o[0, starts[i]:starts[i] + c])
    for i, c in enumerate(counts):
        want, want_last = token_by_token(seqs[i], None if begins[i] else leaf[order[i]])
        near(mine(i, c), want)
        near(new[order[i]], want_last)


def test_the_one_token_update_is_the_recurrence(form):
    """Rows in any slots of a larger leaf, one beginning over NaN, two dead rows on the
    trash slot and one passed by: every live row's slot updated, every other slot bit for
    bit."""
    rng = np.random.default_rng(5)
    n = 6
    q, k, v, g, beta = draw(rng, n)
    leaf = rng.normal(size=(9, H, DK, DV)).astype(np.float32)
    leaf[4] = np.nan
    at = np.array([7, 4, 8, 0, 8, 8])  # rows 2, 4 dead and row 5 passed by, all on the trash slot 8
    begins = np.array([False, True, False, False, False, False])
    passed = np.array([False, False, False, False, False, True])
    o, new = entry(kda_step)(*(jnp.asarray(a) for a in (q, k, v, g, beta)), jnp.asarray(leaf),
                      jnp.asarray(at, jnp.int32), jnp.asarray(begins), jnp.asarray(passed))
    for row in (0, 1, 3):
        want, want_last = token_by_token([a[row:row + 1] for a in (q, k, v, g, beta)],
                                         None if begins[row] else leaf[at[row]])
        near(o[row], want[0])
        near(new[at[row]], want_last)
    for slot in (1, 2, 3, 5, 6):
        np.testing.assert_array_equal(np.asarray(new[slot]), leaf[slot])


ADVERSARIAL = {
    # every channel of every token at the bound: exp(rho - gamma) reaches exp(75) inside a block
    "at_the_bound": dict(g=LOWER_BOUND),
    # the bound in half the channels and no decay at all in the others
    "bound_and_none": dict(g="halves"),
    # keys that resemble one another: one direction and a tenth of noise (PR 43's NaN)
    "resemble": dict(keys="alike"),
    # what a SiLU leaves: keys and queries in one orthant
    "one_orthant": dict(keys="positive"),
    # all of it at once
    "all": dict(g=LOWER_BOUND, keys="alike_positive"),
}


def adversarial(rng, s, h, d, g=None, keys=None):
    if isinstance(g, str):
        g = np.where(np.arange(d) % 2 == 0, LOWER_BOUND, 0.0).astype(np.float32)
    q, k, v, g, beta = draw(rng, s, h, d, d, g=g)
    if keys:
        base = rng.normal(size=(1, h, d))
        k = base + 0.1 * rng.normal(size=(s, h, d))
        q = base + 0.1 * rng.normal(size=(s, h, d))
        if "positive" in keys:
            k, q = np.abs(k), np.abs(q)
        k, q = unit(k).astype(np.float32), (unit(q) * d ** -0.5).astype(np.float32)
        beta = np.full_like(beta, 0.95)
    return q, k, v, g, beta


@pytest.mark.parametrize("case", sorted(ADVERSARIAL))
def test_what_float32_must_survive_at_small_heads(kernel, case):
    rng = np.random.default_rng(7)
    seq = adversarial(rng, 150, 4, 16, **ADVERSARIAL[case])
    state = rng.normal(size=(1, 4, 16, 16)).astype(np.float32)
    o, last = scan(padded([seq], (150, ), 150), state, (150, ))
    want, want_last = token_by_token(seq, state[0])
    near(o[0], want, 1e-4)
    near(last[0], want_last, 1e-4)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 0.05)])
def test_heads_of_128_with_decays_at_the_bound_and_keys_that_resemble(dtype, tol, monkeypatch):
    """The published head size, the kernel interpreted, in float32 and with the chip's
    bfloat16 operands (the inverse's three-pass chain): keys alike in one orthant, every
    channel at the bound in half the heads and near no decay in the others, by the head's
    PARITY: so inside every pair that a chunk of the algebra stacks, one head is at the
    bound and its partner is not.  An inverse by squarings over the whole chunk, a factor
    taken over more than a block of 16, or a product of one head's rows with its partner's
    column factor reads NaN or inf here."""
    monkeypatch.setattr(_pallas, "INTERPRET", True)
    rng = np.random.default_rng(9)
    s, h, d = 144, 4, 128
    q, k, v, g, beta = adversarial(rng, s, h, d, keys="alike_positive")
    g = np.where(np.arange(h)[None, :, None] % 2 == 0, LOWER_BOUND, g).astype(np.float32)
    seq = (q, k, v, g, beta)
    cast = lambda a, i: jnp.asarray(a, dtype if i < 3 else "float32")
    arrays = [cast(a[None], i) for i, a in enumerate(seq)]
    o, last = entry(kda_scan)(*arrays, jnp.zeros((1, h, d, d), jnp.float32), jnp.zeros(1, jnp.int32),
                       jnp.ones(1, bool), jnp.asarray([s], jnp.int32))
    rounded = [np.asarray(cast(a, i), np.float32) for i, a in enumerate(seq)]
    want, want_last = token_by_token(rounded, None)
    near(o[0], want, tol)
    near(last[0], want_last, tol)


def test_an_odd_count_of_heads_runs_one_head_a_chunk(form):
    """Three heads are no pairs (``gated_delta.heads_a_step``'s rule over a grid step's heads):
    the algebra of one head's ``[64, 64]`` is still run, over two chunks and a carried state."""
    heads = 3
    assert (kda._pair(heads), kda._pair(H), kda._pair(16)) == (1, 2, 2)
    rng = np.random.default_rng(13)
    seq = draw(rng, 70, h=heads)
    state = rng.normal(size=(1, heads, DK, DV)).astype(np.float32)
    o, last = scan(padded([seq], (70, ), 70), state, (70, ))
    want, want_last = token_by_token(seq, state[0])
    near(o[0], want)
    near(last[0], want_last)


def test_no_head_leaks_into_its_pair(form):
    """A pair in the SECOND grid step of sixteen heads (heads 8 and 9, one under another in
    one chunk of the algebra): the first holds every channel at the bound with keys alike in
    one orthant, the second holds ``g = 0``.  Each reads the recurrence, and reads the same
    when its partner's q, k and v are replaced by others (the values a thousand times as
    large): what stands between the heads' blocks is placed zeros, never a product (one
    head's rows against its partner's column factor is not bounded by the block rule: ``inf
    x 0``)."""
    rng = np.random.default_rng(17)
    s, heads, first, second = 70, 16, 8, 9
    assert heads == 2 * kda.SCAN_HEADS and kda._pair(heads) == 2
    q, k, v, g, beta = (a.copy() for a in draw(rng, s, h=heads))
    hard = adversarial(rng, s, 1, DK, g=LOWER_BOUND, keys="alike_positive")
    for a, b in zip((q, k, v, g, beta), hard):
        a[:, first] = b[:, 0]
    g[:, second] = 0.0
    state = rng.normal(size=(1, heads, DK, DV)).astype(np.float32)
    run = lambda *seq: scan(padded([seq], (s, ), s), state, (s, ))
    o, last = run(q, k, v, g, beta)
    want, want_last = token_by_token((q, k, v, g, beta), state[0])
    for mine, partner in ((first, second), (second, first)):
        near(o[0, :, mine], want[:, mine], 1e-4)
        near(last[0, mine], want_last[mine], 1e-4)
        other = [a.copy() for a in (q, k, v)]
        for a, b, scale in zip(other, draw(rng, s, h=1), (1.0, 1.0, 1e3)):  # unit keys, values of 1e3
            a[:, partner] = scale * b[:, 0]
        o_other, last_other = run(*other, g, beta)
        near(o_other[0, :, mine], o[0, :, mine])
        near(last_other[0, mine], last[0, mine])


def test_the_layouts_are_ssds_and_the_chunk_is_64():
    assert (CHUNK, kda.SUB, LOWER_BOUND, kda.SCAN_HEADS) == (64, 16, -5.0, 8)
    assert kda.lay_on_chunk_edges.__module__.endswith("gated_delta")
    assert kda._lay_window.__module__.endswith("ssd")
