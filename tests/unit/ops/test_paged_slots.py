"""The paged kernel with several table slots a grid step (PR 35): parity with
``_dense_fallback`` in interpret mode over table widths that are and are not
whole steps.  ``q`` on the flat axis is ``test_paged_slots_flat.py``'s; the tile
chooser, the counters and the kernel's traced size are
``test_paged_slots_chooser.py``'s (three files: under ``--dist loadfile`` a file
is one worker's from start to end, and every case here is an interpreted kernel
of its own shape)."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import paged

from .compiled import compiled, dense_fallback

BS = 16  # keys a block; a step of four slots holds 64


def drawn_case(rows, t, hq, kvh, maxb, dk=32, dv=None, dtype=jnp.float32, seed=0):
    """``rows``: one ``(length, n_tokens)`` a sequence of the bucket ``[len(rows), t]``
    over a pool of blocks of ``BS``; ``dv``: the value is the key's first ``dv`` columns."""
    rng = np.random.default_rng(seed)
    n, nb = len(rows), 2 * maxb * len(rows) + 1
    q = jnp.asarray(rng.normal(size=(n, t, hq, dk)), dtype)
    kpool = jnp.asarray(rng.normal(size=(nb, kvh, BS, dk)), dtype)
    vpool = None if dv else jnp.asarray(rng.normal(size=(nb, kvh, BS, dk)), dtype)
    tables = jnp.asarray(rng.permutation(nb - 1)[:n * maxb].reshape(n, maxb), jnp.int32)
    lengths = jnp.asarray([length for length, _ in rows], jnp.int32)
    n_tokens = jnp.asarray([k for _, k in rows], jnp.int32)
    return q, kpool, vpool, tables, lengths, lengths - n_tokens, n_tokens


def assert_kernel_is_the_fallback(case, window=None, slopes=None, dv=None, scale=None, atol=2e-5,
                                  call=compiled):
    q, kpool, vpool, tables, lengths, start_pos, n_tokens = case
    scale = scale or 1.0 / np.sqrt(q.shape[-1])
    ref = dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens, scale, window, slopes, dv)
    got = call(paged.paged_attention, block_size=BS, window=window, alibi_slopes=slopes,
               softmax_scale=float(scale), value_dim=dv)(q, kpool, vpool, tables, lengths, start_pos, n_tokens)
    assert got.shape == q.shape[:3] + (dv or q.shape[-1], ) and got.dtype == q.dtype
    valid = np.asarray(jnp.arange(q.shape[1])[None, :] < n_tokens[:, None])
    got, ref = (np.asarray(a.astype(jnp.float32)) for a in (got, ref))
    np.testing.assert_allclose(got[valid], ref[valid], atol=atol)
    assert np.isfinite(got).all() and (got[~valid] == 0.0).all()


def ends(maxb, t=1):
    """Sequences whose last live block is the first, a middle and the last slot of
    a step, one shorter than a step beside one that fills the table, and a row of
    the bucket that holds no token; a decode row rides in a chunk's bucket."""
    blocks = sorted({b for b in (1, 2, 4, 5, 7, maxb - 1, maxb) if 1 <= b <= maxb})
    rows = [((b - 1) * BS + 1 + (5 * b) % BS, 1) for b in blocks] + [(0, 0)]
    if t > 1:  # a chunk behind a cached prefix, and one that begins its sequence
        rows[-2] = (maxb * BS, min(t, maxb * BS))
        rows.append((min(t, maxb * BS) // 2 + 1, min(t, maxb * BS) // 2 + 1))
    return rows


def _parity_cases():
    for maxb in (1, 2, 3, 4, 5, 8, 40):  # tables that are and are not whole steps
        yield pytest.param(dict(rows=ends(maxb), t=1, hq=4, kvh=2, maxb=maxb), {}, id=f"table{maxb}-T1")
    for maxb in (3, 5, 40):
        yield pytest.param(dict(rows=ends(maxb, 16), t=16, hq=4, kvh=2, maxb=maxb), {},
                           id=f"table{maxb}-T16")
    for hq, kvh in ((32, 8), (16, 16), (8, 1)):  # GQA, MHA, MQA
        for t in (1, 16):
            yield pytest.param(dict(rows=ends(8, t), t=t, hq=hq, kvh=kvh, maxb=8), {},
                               id=f"{hq}q{kvh}kv-T{t}")
    for hq, kvh in ((32, 8), (8, 1)):  # whole row tiles, decode rows beside the chunk
        yield pytest.param(dict(rows=ends(20, 256), t=256, hq=hq, kvh=kvh, maxb=20), {},
                           id=f"{hq}q{kvh}kv-T256")
    # a window whose edge falls inside a step, on a step's boundary and two steps back
    for window, t in ((70, 1), (64, 1), (40, 16), (130, 16)):
        yield pytest.param(dict(rows=ends(12, t), t=t, hq=4, kvh=2, maxb=12), dict(window=window),
                           id=f"window{window}-T{t}")
    for hq, kvh, t in ((4, 2, 1), (8, 1, 16), (4, 4, 16)):  # a slope a q head over the wider key axis
        yield pytest.param(dict(rows=ends(6, t), t=t, hq=hq, kvh=kvh, maxb=6), dict(alibi=True),
                           id=f"alibi-{hq}q{kvh}kv-T{t}")
    for t in (1, 16):  # the value is the joined tile's leading columns (576 / 512)
        yield pytest.param(dict(rows=ends(6, t), t=t, hq=8, kvh=1, maxb=6, dk=576, dv=512),
                           dict(dv=512, scale=0.07), id=f"latent-576-512-T{t}")
    for t in (1, 16):
        yield pytest.param(dict(rows=ends(8, t), t=t, hq=32, kvh=8, maxb=8, dtype=jnp.bfloat16),
                           dict(atol=4e-2), id=f"bf16-32q8kv-T{t}")


@pytest.mark.parametrize("case,how", list(_parity_cases()))
def test_several_table_slots_a_step_are_the_fallbacks_numbers(interpreted_kernels, case, how):
    """One product over a step's ``slots * bs`` keys and one softmax update a
    step against the dense gather: a slot past ``maxb`` or past a sequence's
    last live block was never fetched and is masked."""
    how = dict(how)
    hq = case["hq"]
    slopes = jnp.asarray(2.0 ** -np.arange(1, hq + 1), jnp.float32) if how.pop("alibi", False) else None
    assert paged.step_tile(case["t"], hq, case["kvh"], case.get("dk", 32), BS, jnp.float32,
                           jnp.float32, case.get("dv"))[-1] == 4
    assert_kernel_is_the_fallback(drawn_case(**case), slopes=slopes, **how)


@pytest.mark.parametrize("maxb,t", [(3, 1), (5, 16), (40, 1)])
def test_two_table_slots_a_step_are_the_fallbacks_numbers(interpreted_kernels, monkeypatch, maxb, t):
    """Where four slots do not fit beside the step's heads and rows the chooser
    hands out two: the same numbers (here by leaving four out of the choice)."""
    monkeypatch.setattr(paged, "STEP_SLOTS", (2, 1))
    assert paged.step_tile(t, 4, 2, 32, BS, jnp.float32, jnp.float32)[-1] == 2
    assert_kernel_is_the_fallback(drawn_case(ends(maxb, t), t, 4, 2, maxb))


@pytest.mark.parametrize("hq,kvh,t,maxb", [(32, 8, 1, 20), (4, 2, 16, 5), (8, 1, 5, 12)])
def test_every_copy_is_waited_for_before_its_block_is_read(monkeypatch, hq, kvh, t, maxb):
    """The kernel's fetch as the chip runs it: the interpreter that models DMA
    and semaphores delivers a copy only when it is waited for, and looks for
    races between the copies and the arithmetic.  A step that read a block
    before its wait, or waited for a copy nobody started, fails here."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as interpreter
    from jax.experimental.pallas import tpu as pltpu

    from deepspeed_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, "INTERPRET", pltpu.InterpretParams(
        dma_execution_mode="on_wait", detect_races=True))
    # eagerly: the interpreter that models DMA runs op by op
    assert_kernel_is_the_fallback(drawn_case(ends(maxb, t), t, hq, kvh, maxb), call=functools.partial)
    assert not interpreter.races.races_found
