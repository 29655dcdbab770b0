"""A windowed walk of the paged kernel begins at the first block a query of the
step can see (ISSUE 56): against the masked reference, interpreted, for windows
whose oldest visible key falls inside a block, on its first key and on its last,
in a decode bucket (a burst's body is one), a padded chunk and a compacted pass.
The blocks behind the walk are POISONED: a walk that fetched one would multiply
a NaN into ``p . v``."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import paged

from .compiled import compiled, dense_fallback
from .test_paged_slots import BS, drawn_case
from .test_paged_slots_flat import flat_of

# (length, n_tokens) a row: a chunk behind a long prefix, decode rows short and long, a row
# with no token, a sequence that begins
ROWS = {"decode": [(5, 1), (100, 1), (0, 0), (128, 1), (49, 1)],
        "chunk": [(128, 16), (100, 1), (0, 0), (9, 9), (77, 13)],
        "flat": [(128, 16), (100, 1), (0, 0), (9, 9), (77, 13)]}


def poisoned(case, window):
    """The case with every block behind each sequence's walk filled with NaN, and
    how many blocks that was."""
    q, kpool, vpool, tables, lengths, start_pos, n_tokens = case
    behind = [int(b) for row, start, count in zip(np.asarray(tables), np.asarray(start_pos),
                                                  np.asarray(n_tokens)) if count
              for b in row[:paged.walk_first_block(int(start), window, BS)]]
    at = jnp.asarray(behind, jnp.int32)
    return (q, kpool.at[at].set(jnp.nan), vpool.at[at].set(jnp.nan), tables, lengths, start_pos,
            n_tokens), len(behind)


# the oldest key of the first query: inside a block (41), a block's first key (33), its last (32)
@pytest.mark.parametrize("window", [41, 33, 32])
@pytest.mark.parametrize("layout", sorted(ROWS))
def test_a_windowed_walk_is_the_masked_reference_and_fetches_nothing_behind_it(monkeypatch, layout,
                                                                              window):
    from deepspeed_tpu.ops import _pallas
    t = 1 if layout == "decode" else 16
    case = drawn_case(ROWS[layout], t, 4, 2, 8)
    q, kpool, vpool, tables, lengths, start_pos, n_tokens = case
    scale = 1.0 / np.sqrt(q.shape[-1])
    want = np.asarray(dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens, scale, window))
    dirty, behind = poisoned(case, window)
    assert behind > 0
    monkeypatch.setattr(_pallas, "INTERPRET", True)
    valid = np.asarray(jnp.arange(t)[None, :] < n_tokens[:, None])
    if layout == "flat":
        flat, (row, col) = flat_of(dirty, spare=3)
        got = np.asarray(compiled(paged.paged_attention_flat, chunk=t, block_size=BS, window=window)(
            flat, *dirty[1:]))
        np.testing.assert_allclose(got[:len(row)], want[row, col], atol=2e-5)
    else:
        got = np.asarray(compiled(paged.paged_attention, block_size=BS, window=window)(*dirty))
        np.testing.assert_allclose(got[valid], want[valid], atol=2e-5)
    assert np.isfinite(got).all()
    # the same walk from the table's first slot meets the poison: the test can tell
    monkeypatch.setattr(paged, "_fetch_plan", lambda *a: _whole_walk(*a))
    if layout == "decode":
        assert not np.isfinite(np.asarray(
            compiled(paged.paged_attention, block_size=BS, window=window)(*dirty))).all()


_PLAN = paged._fetch_plan


def _whole_walk(lengths, n_tokens, row0, bs, maxb, group, rows, splits, start_pos, window):
    plan = _PLAN(lengths, n_tokens, row0, bs, maxb, group, rows, splits)
    return jnp.concatenate([plan, jnp.zeros_like(plan[:1])], 0)  # the walk begins at slot 0


@pytest.mark.parametrize("window", [None, 1, 16, 17, 4096])
def test_the_plan_and_the_counters_agree_on_where_a_walk_begins(window):
    starts = np.asarray([0, 1, 15, 16, 17, 31, 32, 100, 4095, 4096, 4097, 9000], np.int32)
    counts = np.where(np.arange(len(starts)) % 5 == 4, 0, 3).astype(np.int32)
    lengths, maxb = starts + counts, 1024
    want = [paged.walk_first_block(int(s), window, BS) for s in starts]
    if window is None:
        assert want == [0] * len(starts)
        return
    plan = np.asarray(paged._fetch_plan(jnp.asarray(lengths), jnp.asarray(counts),
                                        jnp.zeros_like(starts), BS, maxb, 2, 16, 1,
                                        jnp.asarray(starts), window))
    assert plan.shape == (3, len(starts) + 1) and plan[-1, :-1].tolist() == want
    live = np.where(counts > 0, -(-lengths // BS) - np.asarray(want), 0)
    assert plan[paged.BLOCKS, :-1].tolist() == live.tolist() and (live[counts > 0] > 0).all()
    # nothing a query of the step sees lies before the walk: its oldest visible key's block
    oldest = np.maximum(starts - (window - 1), 0)
    assert (np.asarray(want) == oldest // BS).all()
