"""The state-space duality's chunked scan (``ops/linear_attention/ssd.py``)
against the recurrence token by token (``chipbench/references/
granite_moe_hybrid.py selective_scan``, which shares no algebra with it): the
Pallas kernels in interpret mode and the ``jax.numpy`` forms, sequences as rows
of a padded ``[N, T]`` and compacted onto one flat axis, lengths that are and
are not multiples of the chunk, with and without a carried state, decays near 0
and near 1, a scan continued from its state, and the one-token update; and the
state BY REFERENCE (ISSUE 53): the rows' slots of a leaf of more slots than
rows, in any order, a sequence that begins over whatever its slot holds, dead
rows on one trash slot, and every slot no live row names left bit for bit; and
a pass of chunks (ISSUE 55, ``ssd_chunks``): the rows of one token served by the
update kernel, the rest walked in windows of ``WINDOW`` rows whose layout is
sized by the pass's tokens, in as many trips as the rows need."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references.granite_moe_hybrid import selective_scan
from deepspeed_tpu.ops import _pallas
from deepspeed_tpu.ops.linear_attention import ssd
from deepspeed_tpu.ops.linear_attention.ssd import (CHUNK, WINDOW, scan_chunks, ssd_chunks, ssd_scan,
                                                    ssd_update, walk_trips)

from .compiled import entry

H, P, NS = 16, 8, 16  # 16 heads: two grid steps of the scan kernel's eight
TOL = 1e-5  # float32 throughout, of the largest value (``near``): a chunk's products against 64 steps


def near(got, want):
    """1e-5 of the largest value (outputs reach 60 under a carried state of unit scale: sums of 16
    to 64 float32 products; sound forms read 2e-6 apart)."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, atol=TOL * max(1.0, float(np.abs(want).max())), rtol=0)


@pytest.fixture(params=["numpy", "kernel"])
def form(request, monkeypatch):
    monkeypatch.setattr(_pallas, "INTERPRET", request.param == "kernel")
    return request.param


RNG = np.random.default_rng(0)
A = -RNG.uniform(1e-3, 2.0, size=H).astype(np.float32)  # with dt: decays a token from 0.05 to 0.9999
D = RNG.normal(size=H).astype(np.float32)


def draw(rng, s):
    x = rng.normal(size=(s, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 1.5, size=(s, H)).astype(np.float32)
    b, c = (rng.normal(size=(s, NS)).astype(np.float32) for _ in range(2))
    return x, dt, b, c


_rule = jax.jit(selective_scan)  # one program a length, not an op at a time


def token_by_token(seq, state):
    x, dt, b, c = (jnp.asarray(a) for a in seq)
    y, last = _rule(x, dt, jnp.asarray(A), b, c, jnp.asarray(D),
                    None if state is None else jnp.asarray(state))
    return np.asarray(y), np.asarray(last)


def padded(seqs, counts, t):
    fill = lambda a, c: np.concatenate([a[:c], np.full((t - c, ) + a.shape[1:], 7.0, np.float32)])
    return [jnp.asarray(np.stack([fill(s[i], c) for s, c in zip(seqs, counts)])) for i in range(4)]


def flat(seqs, counts, slots):
    """(arrays [1, S, ...], row, col): the rows' live tokens one after another, the tail dead."""
    row = np.repeat(np.arange(len(counts)), counts)
    col = np.concatenate([np.arange(c) for c in counts])
    dead = slots - len(row)
    arrays = [jnp.asarray(np.concatenate(
        [np.concatenate([s[i][:c] for s, c in zip(seqs, counts)]),
         np.full((dead, ) + seqs[0][i].shape[1:], 5.0, np.float32)]))[None] for i in range(4)]
    at = lambda a: jnp.asarray(np.concatenate([a, np.zeros(dead, int)]))[None]
    return arrays, at(row), at(col)


def in_order(n):
    """Row r in slot r of a leaf of as many slots as rows, none beginning: the rows by value."""
    return jnp.arange(n, dtype=jnp.int32), jnp.zeros(n, bool)


def scan(arrays, state, counts, row=None, col=None, at=None, begins=None):
    x, dt, b, c = arrays
    own = in_order(len(counts))
    return entry(ssd_scan)(x, dt, jnp.asarray(A), b, c, jnp.asarray(D), jnp.asarray(state),
                    own[0] if at is None else jnp.asarray(at, jnp.int32),
                    own[1] if begins is None else jnp.asarray(begins),
                    jnp.asarray(counts, jnp.int32), row, col)


@pytest.mark.parametrize("counts,carried", [
    ((130, 0, 1, 64, 77), True), ((130, 0, 1, 64, 77), False), ((64, 128), True), ((5, ), False),
    ((0, 0, 9), True)], ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else str(v))
@pytest.mark.parametrize("layout", ["padded", "flat"])
def test_the_chunked_scan_is_the_recurrence_token_by_token(form, layout, counts, carried):
    """Several sequences a pass, each from its own carried matrices (zeros where
    it begins), each laid onto a chunk's edge; what lies in the dead slots
    (sevens, fives) reaches nothing; a row with no token keeps its state."""
    rng = np.random.default_rng(sum(counts))
    seqs = [draw(rng, max(c, 1)) for c in counts]
    state = (rng.normal(size=(len(counts), H, P, NS)) if carried
             else np.zeros((len(counts), H, P, NS))).astype(np.float32)
    if layout == "padded":
        y, last = scan(padded(seqs, counts, max(counts)), state, counts)
        mine = lambda i, c: np.asarray(y[i, :c])
    else:
        arrays, row, col = flat(seqs, counts, sum(counts) + 11)
        y, last = scan(arrays, state, counts, row, col)
        starts = np.cumsum((0, ) + counts)
        mine = lambda i, c: np.asarray(y[0, starts[i]:starts[i] + c])
    for i, c in enumerate(counts):
        if c == 0:
            np.testing.assert_array_equal(np.asarray(last[i]), state[i])
            continue
        want, want_last = token_by_token([a[:c] for a in seqs[i]], state[i])
        near(mine(i, c), want)
        near(np.asarray(last[i]), want_last)


def test_a_scan_continued_from_its_state_is_the_whole_scan(form):
    rng = np.random.default_rng(3)
    seq = draw(rng, 150)
    zero = np.zeros((1, H, P, NS), np.float32)
    whole, end = scan(padded([seq], (150, ), 150), zero, (150, ))
    head, kept = scan(padded([seq], (70, ), 70), zero, (70, ))
    tail, last = scan(padded([[a[70:] for a in seq]], (80, ), 80), kept, (80, ))
    near(np.concatenate([head[0], tail[0]]), np.asarray(whole[0]))
    near(np.asarray(last), np.asarray(end))


def test_the_one_token_update_is_a_scan_of_one(form):
    rng = np.random.default_rng(4)
    n = 3
    seqs = [draw(rng, 1) for _ in range(n)]
    state = rng.normal(size=(n, H, P, NS)).astype(np.float32)
    x, dt, b, c = padded(seqs, (1, ) * n, 1)
    y, last = entry(ssd_update)(x[:, 0], dt[:, 0], jnp.asarray(A), b[:, 0], c[:, 0], jnp.asarray(D),
                                jnp.asarray(state), *in_order(n))
    scanned, scanned_last = scan((x, dt, b, c), state, (1, ) * n)
    near(np.asarray(y), np.asarray(scanned[:, 0]))
    near(np.asarray(last), np.asarray(scanned_last))
    for i, seq in enumerate(seqs):
        want, want_last = token_by_token(seq, state[i])
        near(np.asarray(y[i]), want[0])
        near(np.asarray(last[i]), want_last)


SLOTS = 11  # of the flat leaf: more than a step's rows; the last is the dead rows' trash slot
BY_REFERENCE = {  # counts (the update: 1 a row, 0 a dead row), the rows' slots, which rows begin
    "scrambled-slots": ((70, 5, 1, 130), (7, 2, 9, 0), (False, ) * 4),
    "begins-over-garbage": ((70, 5, 1, 130), (3, 8, 1, 6), (True, False, True, True)),
    "dead-rows-share-the-trash-slot": ((0, 70, 0, 9), (SLOTS - 1, 4, SLOTS - 1, 5),
                                       (True, False, True, False)),
}


@pytest.mark.parametrize("case", list(BY_REFERENCE))
@pytest.mark.parametrize("kernel", ["update", "scan-padded", "scan-flat"])
def test_the_state_is_read_and_written_in_the_rows_slots_alone(form, kernel, case):
    """The leaf whole, the rows' slots and their flags equal the rows by value
    (slot r for row r, zeros where a sequence begins: what the kernels took
    before ISSUE 53) in what is computed and in what the rows' slots hold after;
    NaNs in the slot of a sequence that begins reach nothing; two dead rows on
    the one trash slot disturb no other (a dead row of a scan walks no chunk and
    writes not even the trash slot); every slot no live row names is bit for bit
    what it was."""
    counts, at, begins = BY_REFERENCE[case]
    rng = np.random.default_rng(len(case))
    leaf = rng.normal(size=(SLOTS, H, P, NS)).astype(np.float32)
    for slot, fresh in zip(at, begins):
        if fresh:
            leaf[slot] = np.nan
    rows = np.where(np.asarray(begins)[:, None, None, None], np.float32(0), leaf[list(at)])
    live = [i for i, c in enumerate(counts) if c > 0]
    if kernel == "update":
        seqs = [draw(rng, 1) for _ in counts]
        x, dt, b, c = (a[:, 0] for a in padded(seqs, (1, ) * len(counts), 1))
        update = lambda state, at, begins: entry(ssd_update)(
            x, dt, jnp.asarray(A), b, c, jnp.asarray(D), jnp.asarray(state), at, begins)
        y, after = update(leaf, jnp.asarray(at, jnp.int32), jnp.asarray(begins))
        want, want_rows = update(rows, *in_order(len(counts)))
        written = set(at)  # a dead row updates the trash slot, as a padded bucket's always did
    else:
        seqs = [draw(rng, max(c, 1)) for c in counts]
        if kernel == "scan-padded":
            arrays, where = padded(seqs, counts, max(counts)), {}
        else:
            arrays, row, col = flat(seqs, counts, sum(counts) + 11)
            where = {"row": row, "col": col}
        y, after = scan(arrays, leaf, counts, at=at, begins=begins, **where)
        want, want_rows = scan(arrays, rows, counts, **where)
        written = {at[i] for i in live}
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    assert np.isfinite(np.asarray(y)).all()
    after = np.asarray(after)
    for i in live:
        np.testing.assert_array_equal(after[at[i]], np.asarray(want_rows[i]))
    for slot in set(range(SLOTS)) - written:
        np.testing.assert_array_equal(after[slot], leaf[slot])


MIXED = {  # one bucket's counts: rows of one token (begun and continued) beside rows that are walked
    "every-length-in-two-trips": (0, 1, 1, 2, 63, 64, 65, 300),
    "one-trip": (1, 70, 1, 5, 0, 1),
    "three-trips": (2, 3, 1, 5, 0, 70, 2, 9, 4, 1, 66, 130),
    "no-row-walked": (1, 0, 1),
}


@pytest.mark.parametrize("case", list(MIXED))
@pytest.mark.parametrize("layout", ["padded", "flat"])
def test_a_pass_of_chunks_is_the_recurrence_whatever_its_rows_hold(form, layout, case):
    """ISSUE 55: one pass whose rows hold 0, 1 (a sequence that begins over NaNs,
    one that continues from its slot), 2, 63, 64, 65 and 300 tokens, in slots
    that are neither the rows nor in order: the one-token rows through the update
    kernel and the rest through the scan, ``WINDOW`` rows a trip (five walked
    rows: two trips; ten: three; none: no trip), give the recurrence token by
    token for every row, each row's slot holds its new matrices, and every slot
    no live row names (the trash slot apart: the kernels pass rows by on it) is
    bit for bit what it was."""
    counts = MIXED[case]
    n, walked = len(counts), sum(c > 1 for c in counts)
    assert walk_trips(walked) == {"every-length-in-two-trips": 2, "one-trip": 1, "three-trips": 3,
                                  "no-row-walked": 0}[case]
    rng = np.random.default_rng(n)
    slots = n + 3  # two slots no row names, and the trash slot last
    at = rng.permutation(slots - 1)[:n]
    begins = rng.random(n) < 0.4
    begins[1:3] = (True, False)  # of two one-token rows, one begins and one continues
    at[[i for i, c in enumerate(counts) if c == 0]] = slots - 1  # a dead row: the trash slot
    leaf = rng.normal(size=(slots, H, P, NS)).astype(np.float32)
    leaf[at[begins]] = np.nan
    leaf[slots - 1] = 3.0
    seqs = [draw(rng, max(c, 1)) for c in counts]
    args = (jnp.asarray(A), jnp.asarray(D), jnp.asarray(leaf), jnp.asarray(at, jnp.int32),
            jnp.asarray(begins), jnp.int32(slots - 1), jnp.asarray(counts, jnp.int32))
    chunks = lambda x, dt, b, c, *where: entry(ssd_chunks)(x, dt, args[0], b, c, *args[1:], *where)
    if layout == "padded":
        y, after = chunks(*padded(seqs, counts, max(max(counts), 2)))
        mine = lambda i, c: np.asarray(y[i, :c])
    else:
        arrays, row, col = flat(seqs, counts, sum(counts) + 11)
        y, after = chunks(*arrays, row, col)
        starts = np.cumsum((0, ) + counts)
        mine = lambda i, c: np.asarray(y[0, starts[i]:starts[i] + c])
    assert np.isfinite(np.asarray(y)).all()
    after = np.asarray(after)
    for i, c in enumerate(counts):
        if c:
            want, want_last = token_by_token([a[:c] for a in seqs[i]], None if begins[i] else leaf[at[i]])
            near(mine(i, c), want)
            near(after[at[i]], want_last)
    for slot in set(range(slots - 1)) - {s for s, c in zip(at, counts) if c}:
        np.testing.assert_array_equal(after[slot], leaf[slot])


def test_bfloat16_operands_keep_the_state_and_the_decays_in_float32(form):
    """The chip's form: x, B and C bfloat16, dt and the state float32.  The new
    state is float32 and within bfloat16's rounding of the float32 scan's."""
    rng = np.random.default_rng(5)
    seq = draw(rng, 100)
    zero = np.zeros((1, H, P, NS), np.float32)
    x, dt, b, c = padded([seq], (100, ), 100)
    half = lambda a: a.astype(jnp.bfloat16)
    y, last = scan((half(x), dt, half(b), half(c)), zero, (100, ))
    want, want_last = scan((x, dt, b, c), zero, (100, ))
    assert (y.dtype, last.dtype) == (jnp.bfloat16, jnp.float32)
    scale = float(np.abs(np.asarray(want_last)).max())
    assert np.abs(np.asarray(last) - np.asarray(want_last)).max() < 0.02 * scale
    assert np.abs(np.asarray(y, np.float32) - np.asarray(want)).max() < 0.03 * float(np.abs(want).max())


def test_the_chunks_a_bucket_walks():
    assert CHUNK == 64 and ssd.SCAN_HEADS == 8 and ssd.UPDATE_HEADS == 32
    assert scan_chunks(32, 1) == 0  # a decode step: the one-token update
    assert scan_chunks(4, 256) == 16 and scan_chunks(4, 65) == 8
    # compacted: sized by the pass's tokens, WINDOW rows on chunk edges a trip, whatever the bucket's rows
    assert scan_chunks(32, 256, 512) == scan_chunks(4, 256, 512) == 8 + WINDOW == 12
    assert [scan_chunks(32, 256, 512, walked) for walked in (0, 1, 4, 5, 9)] == [0, 12, 12, 24, 36]
