"""The paged kernel with ``q`` on the flat token axis (ISSUE 40) against the padded
bucket over the same kernel, interpreted; split from ``test_paged_slots.py``,
whose drawn cases it shares.  Here: where the live rows and the dead slots lie
on the axis; ``test_paged_slots_flat_heads.py`` runs the same check over the head
groupings, the masks and the dtypes."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import paged

from .compiled import compiled, dense_fallback
from .test_paged_slots import BS, drawn_case


def flat_of(case, spare=0):
    """The case's live tokens on one flat axis, sequence after sequence, the
    tail dead: ``(flat q [S, H, Dk], (row, col) of the live slots)``."""
    q, n_tokens = case[0], np.asarray(case[-1])
    row = np.repeat(np.arange(len(n_tokens)), n_tokens)
    col = np.concatenate([np.arange(k) for k in n_tokens] or [np.zeros(0, int)])
    s = max(8, -(-(len(row) + spare) // 8) * 8)
    flat = jnp.zeros((s, ) + q.shape[2:], q.dtype).at[:len(row)].set(q[row, col])
    return flat, (row, col)


CHUNK = [(300, 225), (40, 1), (17, 1), (290, 1)]  # decode rows behind a chunk, t = 256


def _flat_cases():
    yield "decode-rows-behind-a-225-token-chunk", dict(rows=CHUNK, t=256, hq=4, kvh=2, maxb=20), {}
    yield "a-row-with-no-token-between-two", dict(
        rows=[(20, 3), (0, 0), (33, 1), (0, 0), (0, 0), (64, 16)], t=16, hq=4, kvh=2, maxb=5), {}
    # the flat axis full to its last slot: the last window ends where R's spare window begins
    yield "the-last-window-a-chunks", dict(rows=[(9, 1), (40, 7), (256, 248)], t=256, hq=4, kvh=2,
                                           maxb=20), {}
    yield "the-last-window-a-decode-rows", dict(rows=[(256, 247), (40, 8), (9, 1)], t=256, hq=4,
                                                kvh=2, maxb=20), {}
    yield "no-token-at-all", dict(rows=[(0, 0), (0, 0)], t=16, hq=4, kvh=2, maxb=3), {}


@pytest.mark.parametrize("path", ["kernel", "fallback"])
@pytest.mark.parametrize("name,case,how", list(_flat_cases()), ids=lambda v: v if isinstance(v, str) else "")
def test_q_on_the_flat_axis_is_the_padded_bucket_on_every_live_row(monkeypatch, name, case, how, path):
    flat_is_the_padded_bucket(monkeypatch, case, how, path)


def flat_is_the_padded_bucket(monkeypatch, case, how, path):
    """``paged_attention_flat`` over the pass's tokens on one axis against the
    padded entry over the same kernel (``row0 = n x rows``): bit for bit on every
    live row (a live row's tiles, products and order are the same), finite and,
    from the kernel, zero in the dead slots.  Both forms of ``_dense_fallback``
    likewise."""
    from deepspeed_tpu.ops import _pallas
    how = dict(how)
    monkeypatch.setattr(_pallas, "INTERPRET", path == "kernel")
    splits = how.pop("splits", 1)
    tile_args = (case["t"], case["hq"], case["kvh"], case.get("dk", 32), BS, case.get("dtype", jnp.float32),
                 case.get("dtype", jnp.float32), case.get("dv"))
    if splits > 1:
        monkeypatch.setattr(paged, "VMEM_BUDGET_BYTES", paged._step_vmem_bytes(
            1, 2048 // splits, 256, case["dk"], BS, 4, 4, case["dv"]))
    assert paged.step_tile(*tile_args)[2] == splits
    slopes = (jnp.asarray(2.0 ** -np.arange(1, case["hq"] + 1), jnp.float32)
              if how.pop("alibi", False) else None)
    facts = dict(block_size=BS, window=how.get("window"), alibi_slopes=slopes,
                 softmax_scale=how.get("scale"), value_dim=how.get("dv"))
    drawn = drawn_case(**case)
    padded = compiled(paged.paged_attention, **facts)(*drawn)
    for spare in (0, 9):  # the flat axis full to its last slot, and with dead slots behind
        flat, (row, col) = flat_of(drawn, spare)
        got = compiled(paged.paged_attention_flat, chunk=case["t"], **facts)(flat, *drawn[1:])
        assert got.shape == flat.shape[:2] + (how.get("dv") or flat.shape[-1], ) and got.dtype == flat.dtype
        got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, padded))
        np.testing.assert_array_equal(got[:len(row)], want[row, col])
        assert np.isfinite(got).all() and (path == "fallback" or (got[len(row):] == 0.0).all())


def test_the_flat_forms_copies_are_waited_for_and_in_the_grids_order(monkeypatch):
    """The interpreter that models DMA and semaphores, as above, over the flat
    form: a window's output rows lie over the sequences behind it, so a copy that
    left late, or one nobody waited for, shows as a race or a wrong row."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as interpreter
    from jax.experimental.pallas import tpu as pltpu

    from deepspeed_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, "INTERPRET", pltpu.InterpretParams(
        dma_execution_mode="on_wait", detect_races=True))
    drawn = drawn_case([(40, 1), (90, 30), (0, 0), (17, 1), (33, 2)], 32, 4, 2, 6)
    flat, (row, col) = flat_of(drawn, 3)
    got = paged.paged_attention_flat(flat, *drawn[1:], chunk=32, block_size=BS)
    ref = dense_fallback(*drawn, 1.0 / np.sqrt(32), None)
    np.testing.assert_allclose(np.asarray(got)[:len(row)], np.asarray(ref)[row, col], atol=2e-5)
    assert not interpreter.races.races_found
