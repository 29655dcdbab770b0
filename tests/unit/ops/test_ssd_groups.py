"""The state-space duality's kernels with B and C A GROUP of heads (ISSUE 62:
Nemotron-H's ``n_groups`` 8, where Granite 4.0-H has one group for every head):
``ssd_scan``, ``ssd_update`` and ``ssd_chunks`` with 2, 4 and 8 groups against the
recurrence token by token (``chipbench/references/nemotron_h.py
selective_scan``, which reads head ``h``'s group ``h // (H / G)`` and shares no
algebra with the chunked form): the Pallas kernels interpreted and the
``jax.numpy`` forms; chunk edges, several sequences a pass, a sequence over
several passes, a one-token row beside chunks; a grid step inside one group (the
scan at 8 heads a group) and a step that spans several (the update's
``UPDATE_HEADS`` over four groups, the scan's eight heads over four groups of
two); and one group given as ``[.., 1, N]`` bit for bit what ``[.., N]`` gives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references.nemotron_h import selective_scan
from deepspeed_tpu.ops import _pallas
from deepspeed_tpu.ops.linear_attention import ssd
from deepspeed_tpu.ops.linear_attention.ssd import ssd_chunks, ssd_scan, ssd_update

from .compiled import entry

P, NS = 8, 16
TOL = 1e-5  # float32 throughout, of the largest value: a chunk's products against 64 steps


def near(got, want):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, atol=TOL * max(1.0, float(np.abs(want).max())), rtol=0)


@pytest.fixture(params=["numpy", "kernel"])
def form(request, monkeypatch):
    monkeypatch.setattr(_pallas, "INTERPRET", request.param == "kernel")
    return request.param


def scalars(heads):
    rng = np.random.default_rng(heads)
    return (jnp.asarray(-rng.uniform(1e-3, 2.0, size=heads).astype(np.float32)),
            jnp.asarray(rng.normal(size=heads).astype(np.float32)))


def draw(rng, s, heads, groups):
    x = rng.normal(size=(s, heads, P)).astype(np.float32)
    dt = rng.uniform(0.01, 1.5, size=(s, heads)).astype(np.float32)
    b, c = (rng.normal(size=(s, groups, NS)).astype(np.float32) for _ in range(2))
    return x, dt, b, c


_rule = jax.jit(selective_scan)


def token_by_token(seq, state, a, d):
    y, last = _rule(*(jnp.asarray(v) for v in seq[:2]), a, *(jnp.asarray(v) for v in seq[2:]), d,
                    jnp.asarray(state))
    return np.asarray(y), np.asarray(last)


def padded(seqs, counts, t):
    fill = lambda v, c: np.concatenate([v[:c], np.full((t - c, ) + v.shape[1:], 7.0, np.float32)])
    return [jnp.asarray(np.stack([fill(s[i], c) for s, c in zip(seqs, counts)])) for i in range(4)]


def flat(seqs, counts, slots):
    row = np.repeat(np.arange(len(counts)), counts)
    col = np.concatenate([np.arange(c) for c in counts])
    dead = slots - len(row)
    arrays = [jnp.asarray(np.concatenate(
        [np.concatenate([s[i][:c] for s, c in zip(seqs, counts)]),
         np.full((dead, ) + seqs[0][i].shape[1:], 5.0, np.float32)]))[None] for i in range(4)]
    at = lambda v: jnp.asarray(np.concatenate([v, np.zeros(dead, int)]))[None]
    return arrays, at(row), at(col)


# (heads, groups): the scan's step of 8 heads lies inside a group (16, 2), is one group (64 heads
# in 8 would be; here 32, 4), spans four (16, 8); the update's step spans all of them
SHAPES = [(16, 2), (32, 4), (16, 8)]
COUNTS = (130, 0, 1, 64, 77)


@pytest.mark.parametrize("heads,groups", SHAPES, ids=lambda v: str(v))
@pytest.mark.parametrize("layout", ["padded", "flat"])
def test_a_pass_of_chunks_with_groups_is_the_recurrence_token_by_token(form, layout, heads, groups):
    """Several sequences a pass from slots in no order (one begins over a spoiled
    slot, one holds no token, one holds ONE token and goes to the update kernel
    beside the chunks), chunk edges at 64 and 128, a tail of 2 and of 13."""
    a, d = scalars(heads)
    rng = np.random.default_rng(heads * groups)
    seqs = [draw(rng, max(c, 1), heads, groups) for c in COUNTS]
    slots = len(COUNTS) + 2
    leaf = rng.normal(size=(slots, heads, P, NS)).astype(np.float32)
    at, trash = np.array([4, 1, 5, 0, 2]), slots - 1
    begins = np.array([False, False, False, True, False])
    spoiled = leaf.copy()
    spoiled[0] = np.nan  # the slot of the sequence that begins: never read
    if layout == "padded":
        arrays, row, col = padded(seqs, COUNTS, max(COUNTS)), None, None
        mine = lambda y, i, c: np.asarray(y[i, :c])
    else:
        arrays, row, col = flat(seqs, COUNTS, sum(COUNTS) + 11)
        starts = np.cumsum((0, ) + COUNTS)
        mine = lambda y, i, c: np.asarray(y[0, starts[i]:starts[i] + c])
    x, dt, b, c = arrays
    y, last = entry(ssd_chunks)(x, dt, a, b, c, d, jnp.asarray(spoiled), jnp.asarray(at, jnp.int32),
                                jnp.asarray(begins), jnp.int32(trash), jnp.asarray(COUNTS, jnp.int32), row, col)
    for i, count in enumerate(COUNTS):
        if count == 0:
            np.testing.assert_array_equal(np.asarray(last[at[i]]), leaf[at[i]])
            continue
        want, want_last = token_by_token([v[:count] for v in seqs[i]],
                                         np.zeros_like(leaf[0]) if begins[i] else leaf[at[i]], a, d)
        near(mine(y, i, count), want)
        near(np.asarray(last[at[i]]), want_last)
    np.testing.assert_array_equal(np.asarray(last[3]), leaf[3])  # a slot no row names


@pytest.mark.parametrize("heads,groups", SHAPES, ids=lambda v: str(v))
def test_a_sequence_over_several_passes_and_decode_steps_is_the_whole_recurrence(form, heads, groups):
    """A scan of 70, a scan of 79 continued from its state, then three one-token
    updates: the recurrence over all 152 tokens."""
    a, d = scalars(heads)
    rng = np.random.default_rng(3)
    seq = draw(rng, 152, heads, groups)
    want, want_last = token_by_token(seq, np.zeros((heads, P, NS), np.float32), a, d)
    leaf = jnp.asarray(rng.normal(size=(3, heads, P, NS)).astype(np.float32))
    at, got = jnp.asarray([1], jnp.int32), []
    for start, stop in ((0, 70), (70, 149)):
        x, dt, b, c = padded([[v[start:stop] for v in seq]], (stop - start, ), stop - start)
        y, leaf = entry(ssd_scan)(x, dt, a, b, c, d, leaf, at, jnp.asarray([start == 0]),
                                  jnp.asarray([stop - start], jnp.int32))
        got.append(np.asarray(y[0]))
    for t in range(149, 152):
        x, dt, b, c = (jnp.asarray(v[t:t + 1]) for v in seq)
        y, leaf = entry(ssd_update)(x, dt, a, b, c, d, leaf, at, jnp.asarray([False]))
        got.append(np.asarray(y))
    near(np.concatenate(got), want)
    near(np.asarray(leaf[1]), want_last)


def test_the_update_spans_several_groups_in_one_grid_step_and_the_scan_takes_whole_groups(monkeypatch):
    """The choice of a step's heads: never a part of one group and a part of another."""
    assert ssd._heads_a_step(64, ssd.UPDATE_HEADS, 8) == 32  # four groups of eight a step
    assert ssd._heads_a_step(64, ssd.SCAN_HEADS, 8) == 8     # exactly one group a step
    assert ssd._heads_a_step(8, ssd.SCAN_HEADS, 4) == 8      # four groups of two
    assert ssd._heads_a_step(128, ssd.UPDATE_HEADS, 1) == 32 and ssd._heads_a_step(128, ssd.SCAN_HEADS) == 8
    assert ssd._heads_a_step(24, ssd.SCAN_HEADS, 2) == 6     # a group of 12: a divisor of it
    assert ssd._heads_a_step(64, ssd.UPDATE_HEADS, 64) == 16  # a head a group: PAD rows of the contraction
    with pytest.raises(ValueError, match="groups"):
        ssd._heads_a_step(10, 8, 4)


@pytest.mark.parametrize("interpret", [False, True], ids=["numpy", "kernel"])
def test_one_group_with_its_axis_is_bit_for_bit_one_group_without(monkeypatch, interpret):
    """``[.., 1, N]`` and ``[.., N]`` are one program's two spellings: the same bits
    from the update, the scan and a pass of chunks, padded and compacted.  (Against
    the parent commit's module both read bit for bit too, float32 and bfloat16, in
    both forms: PERF.md, PR 62.)"""
    monkeypatch.setattr(_pallas, "INTERPRET", interpret)
    heads = 16
    a, d = scalars(heads)
    rng = np.random.default_rng(9)
    counts = (70, 1, 64)
    seqs = [draw(rng, c, heads, 1) for c in counts]
    leaf = jnp.asarray(rng.normal(size=(4, heads, P, NS)).astype(np.float32))
    at, begins = jnp.asarray([2, 0, 1], jnp.int32), jnp.asarray([False, True, False])
    n = jnp.asarray(counts, jnp.int32)
    for arrays, row, col in ((padded(seqs, counts, 70), None, None), flat(seqs, counts, 160)):
        x, dt, b, c = arrays
        with_axis = entry(ssd_chunks)(x, dt, a, b, c, d, leaf, at, begins, jnp.int32(3), n, row, col)
        without = entry(ssd_chunks)(x, dt, a, b[..., 0, :], c[..., 0, :], d, leaf, at, begins, jnp.int32(3), n,
                                    row, col)
        for got, want in zip(with_axis, without):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    x, dt, b, c = (v[:, 0] for v in padded(seqs, counts, 70))
    for got, want in zip(entry(ssd_update)(x, dt, a, b, c, d, leaf, at, begins),
                         entry(ssd_update)(x, dt, a, b[:, 0], c[:, 0], d, leaf, at, begins)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
