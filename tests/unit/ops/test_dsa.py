"""A learned selection of the cache (ISSUE 45): the exact top-k by bisection
(``ops/attention/dsa.py top_k_mask``), the index scores over a paged pool (the
interpreted Pallas kernel against plain ``jnp``), and what the
kernels fetch and count.  The paged attention kernel attending a selection alone
(interpreted against ``_dense_fallback``, in both layouts) is
``test_dsa_selection.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.fastpath import ServeCounters
from deepspeed_tpu.ops import _pallas
from deepspeed_tpu.ops.attention import dsa, paged

from .compiled import compiled


def top_k_by_sorting(scores, valid, k):
    want = np.zeros(scores.shape, bool)
    for r in range(scores.shape[0]):
        cols = np.nonzero(valid[r])[0]
        want[r, sorted(cols, key=lambda c: (-scores[r, c], c))[:k]] = True
    return want


def drawn_scores(kind, rows=12, cols=200, seed=0):
    rng = np.random.default_rng(seed)
    scores = rng.normal(size=(rows, cols)).astype(np.float32)
    if kind == "ties":  # runs of equal scores across the threshold, and whole rows of them
        scores[:, 50:60] = scores[:, 40:50]
        scores[5] = np.round(scores[5])
        scores[7] = 1.5
    elif kind == "zeros":  # -0.0 and 0.0 are one score: the lower position decides
        scores[:] = 0.0
        scores[::2, ::2] = -0.0
        scores[1, 3] = 1e-30
    elif kind == "extremes":
        scores[:, ::7] *= 1e30
        scores[:, 1::7] *= 1e-30
        scores[2] = -np.abs(scores[2])
    visible = np.array([3, 15, 16, 100, 199, 150, 0, 20, 40, 59, 55, 45])[:rows]
    valid = np.arange(cols)[None, :] <= visible[:, None]
    valid[6] = False  # a slot that holds no token selects nothing
    return scores, valid


@pytest.mark.parametrize("k", [1, 16, 64])
@pytest.mark.parametrize("kind", ["drawn", "ties", "zeros", "extremes"])
def test_the_top_k_is_exact_and_ties_go_to_the_lower_position(kind, k):
    scores, valid = drawn_scores(kind)
    got = np.asarray(jax.jit(lambda s, v: dsa.top_k_mask(s, v, k))(scores, valid))
    np.testing.assert_array_equal(got, top_k_by_sorting(scores, valid, k))
    assert (got.sum(-1) == np.minimum(valid.sum(-1), k)).all()  # rows shorter than k keep all


def test_the_ordered_image_orders_as_the_floats_do():
    x = np.array([-np.inf, -3e38, -1.0, -2e-38, -0.0, 0.0, 2e-38, 1.0, 3e38, np.inf], np.float32)
    image = np.asarray(dsa.ordered_image(jnp.asarray(x))).astype(np.uint64)
    assert (np.diff(image)[[0, 1, 2, 3, 5, 6, 7, 8]] > 0).all() and image[4] == image[5]
    assert image.min() >= 1  # zero is kept for what is not valid


def paged_case(seed, n, t, heads, di, bs, maxb, starts, counts):
    rng = np.random.default_rng(seed)
    nb = n * maxb + 1
    pool = jnp.asarray(rng.normal(size=(nb, 1, bs, di)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb - 1)[:n * maxb].reshape(n, maxb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(n, t, heads, di)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n, t, heads)), jnp.float32)
    start, count = jnp.asarray(starts, jnp.int32), jnp.asarray(counts, jnp.int32)
    pos = start[:, None] + jnp.arange(t)[None]
    seen = (jnp.arange(maxb * bs)[None, None] <= pos[..., None]) \
        & (jnp.arange(t)[None] < count[:, None])[..., None]
    return pool, tables, q, w, start, count, seen


def flat_of(count, t, *arrays):
    s = -(-(int(sum(count)) + 3) // 8) * 8
    row, col, live = paged._flat_slots(count, s)
    return [a[row, col] for a in arrays], np.asarray(live), (row, col)


@pytest.mark.parametrize("n,t,starts,counts", [(3, 8, [0, 13, 30], [8, 1, 5]),
                                               (4, 1, [5, 40, 0, 47], [1, 1, 0, 1]),
                                               (2, 80, [16, 3], [80, 7])],
                         ids=["chunks", "decode", "two_tiles"])
def test_the_index_score_kernel_is_the_plain_sum(interpreted_kernels, monkeypatch, n, t, starts, counts):
    """``sum_j w_j relu(q_j . k_s)`` over a sequence's own blocks: the Pallas
    kernel (tiles of tokens, steps of four blocks, dead steps skipped) in both
    layouts against the gathered table; a token's visible columns are compared."""
    bs, maxb = 8, max(-(-(s + c) // 8) for s, c in zip(starts, counts)) + 2
    pool, tables, q, w, start, count, seen = paged_case(1, n, t, 4, 16, bs, maxb, starts, counts)
    got = compiled(dsa.index_scores)(q, w, pool, tables, start, count)
    (qf, wf, seenf), live, at = flat_of(count, t, q, w, seen)
    gotf = compiled(dsa.index_scores, chunk=t)(qf, wf, pool, tables, start, count)
    monkeypatch.setattr(_pallas, "INTERPRET", False)
    want = dsa.index_scores(q, w, pool, tables, start, count)
    assert got.shape == want.shape == (n, t, maxb * bs)
    np.testing.assert_allclose(np.where(seen, got, 0), np.where(seen, want, 0), atol=2e-5)
    seenf = np.asarray(seenf) & live[:, None]
    np.testing.assert_allclose(np.where(seenf, gotf, 0), np.where(seenf, want[at], 0), atol=2e-5)
    sel = compiled(dsa.select_keys, topk=4, chunk=t)(qf, wf, pool, tables, start, count)
    pos = np.asarray(start)[at[0]] + np.asarray(at[1])
    assert (np.asarray(sel).sum(-1) == np.where(live, np.minimum(pos + 1, 4), 0)).all()


@pytest.mark.parametrize("blocks,slots,want", [(0, 4, [0, 0, 0, 0]), (1, 4, [0, 0, 0, 0]),
                                               (5, 4, [0, 1, 1, 1]), (8, 4, [0, 1, 1, 1]),
                                               (9, 4, [0, 1, 2, 2]), (3, 1, [0, 1, 2, 2]),
                                               (3, 2, [0, 1, 1, 1])])
def test_a_step_that_does_no_arithmetic_fetches_no_selection(blocks, slots, want):
    """The selection's window of a grid step past a sequence's last live block
    (every step of a row that holds no token) is the window of the last live
    step: the index does not change, so the pipeline copies nothing."""
    got = [int(paged._last_live_step(jnp.int32(b), jnp.int32(blocks), slots)) for b in range(4)]
    assert got == want


def test_a_head_count_that_is_no_whole_tile_is_a_readable_error(interpreted_kernels):
    pool = jnp.zeros((9, 1, 16, 48))
    with pytest.raises(ValueError, match="a selection needs"):
        paged.paged_attention(jnp.zeros((1, 4, 8, 48)), pool, None, jnp.zeros((1, 4), jnp.int32),
                              jnp.array([4]), jnp.array([0]), jnp.array([4]), block_size=16,
                              value_dim=32, selection=jnp.ones((1, 4, 64), bool))


def test_the_selection_counters_count_what_the_kernels_walk():
    """One row of 300 tokens from position 1,000 in a [1, 512] bucket over 16
    table slots of 128, then a burst of 3 decode passes: sums over positions,
    the index kernel's tiles of 64 in steps of 512 keys, the attention kernel's
    steps of ``kernel_slots`` blocks up to the sequence's length."""
    c = ServeCounters(kernel_slots=lambda t: 4, selected=(2048, 7, 128))
    c.count_slots(1, 512, 16, 300, 11, spans=[(1000, 300)])
    positions = np.arange(1000, 1300)
    assert c.dsa_causal_keys == 7 * int((positions + 1).sum())
    assert c.dsa_selected_keys == 7 * int(np.minimum(positions + 1, 2048).sum())
    tiles = [positions[i:i + 64] for i in range(0, 300, 64)]
    assert c.dsa_scored_keys == 7 * sum(len(tile) * -(-(tile[-1] + 1) // 512) * 512 for tile in tiles)
    assert c.dsa_attended_keys == 7 * 300 * 1536  # 1,300 positions in whole steps of 512
    before = c.snapshot()
    c.count_slots(1, 1, 20, 3, 11, passes=3, spans=[(2047, 1)])
    delta = c.delta_since(before)
    assert delta["dsa_causal_keys"] == 7 * (2048 + 2049 + 2050)
    assert delta["dsa_selected_keys"] == 7 * 3 * 2048
    assert delta["dsa_attended_keys"] == 7 * (2048 + 2560 + 2560)
    plain = ServeCounters()  # a family without a selection counts none of it
    plain.count_slots(1, 512, 16, 300, 11, spans=[(1000, 300)])
    assert plain.dsa_causal_keys == plain.dsa_attended_keys == 0
