"""The paged kernel with several table slots a grid step (PR 35): parity with
``_dense_fallback`` in interpret mode over table widths that are and are not
whole steps, the tile chooser's ``slots`` beside the parent's heads and rows,
the counter that says how many slots a step took, and the kernel's traced size
(every program of a cell traces and lowers it once: warm ``setup_s``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import paged

BS = 16  # keys a block; a step of four slots holds 64


@pytest.fixture
def interpreted_kernels(monkeypatch):
    from deepspeed_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, "INTERPRET", True)


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


def assert_kernel_is_the_fallback(case, window=None, slopes=None, dv=None, scale=None, atol=2e-5):
    q, kpool, vpool, tables, lengths, start_pos, n_tokens = case
    scale = scale or 1.0 / np.sqrt(q.shape[-1])
    ref = paged._dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens, scale,
                                window, slopes, dv)
    got = paged.paged_attention(q, kpool, vpool, tables, lengths, start_pos, n_tokens,
                                block_size=BS, window=window, alibi_slopes=slopes,
                                softmax_scale=scale, value_dim=dv)
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
    assert_kernel_is_the_fallback(drawn_case(ends(maxb, t), t, hq, kvh, maxb))
    assert not interpreter.races.races_found


# ------------------------------------------------------------ q on the flat axis
def flat_of(case, spare=0):
    """The case's live tokens on one flat axis, sequence after sequence, the
    tail dead: ``(flat q [S, H, Dk], (row, col) of the live slots)``."""
    q, n_tokens = case[0], np.asarray(case[-1])
    row = np.repeat(np.arange(len(n_tokens)), n_tokens)
    col = np.concatenate([np.arange(k) for k in n_tokens] or [np.zeros(0, int)])
    s = max(8, -(-(len(row) + spare) // 8) * 8)
    flat = jnp.zeros((s, ) + q.shape[2:], q.dtype).at[:len(row)].set(q[row, col])
    return flat, (row, col)


def _flat_cases():
    chunk = [(300, 225), (40, 1), (17, 1), (290, 1)]  # decode rows behind a chunk, t = 256
    yield "decode-rows-behind-a-225-token-chunk", dict(rows=chunk, t=256, hq=4, kvh=2, maxb=20), {}
    yield "a-row-with-no-token-between-two", dict(
        rows=[(20, 3), (0, 0), (33, 1), (0, 0), (0, 0), (64, 16)], t=16, hq=4, kvh=2, maxb=5), {}
    # the flat axis full to its last slot: the last window ends where R's spare window begins
    yield "the-last-window-a-chunks", dict(rows=[(9, 1), (40, 7), (256, 248)], t=256, hq=4, kvh=2,
                                           maxb=20), {}
    yield "the-last-window-a-decode-rows", dict(rows=[(256, 247), (40, 8), (9, 1)], t=256, hq=4,
                                                kvh=2, maxb=20), {}
    yield "no-token-at-all", dict(rows=[(0, 0), (0, 0)], t=16, hq=4, kvh=2, maxb=3), {}
    yield "gqa-32q8kv", dict(rows=chunk, t=256, hq=32, kvh=8, maxb=20), {}
    yield "mha-16q16kv", dict(rows=[(30, 5), (18, 1), (70, 16), (3, 3)], t=16, hq=16, kvh=16, maxb=6), {}
    yield "packed-64-wide-heads-group8", dict(rows=[(30, 5), (18, 1), (70, 16)], t=16, hq=16, kvh=2,
                                              maxb=6, dk=128), {}
    yield "falcon-71q1kv", dict(rows=[(30, 5), (18, 1), (70, 9)], t=16, hq=71, kvh=1, maxb=6), {}
    yield "window-40", dict(rows=[(150, 14), (90, 1), (64, 16)], t=16, hq=4, kvh=2, maxb=12), dict(window=40)
    yield "alibi", dict(rows=[(30, 5), (18, 1), (70, 16)], t=16, hq=8, kvh=2, maxb=6), dict(alibi=True)
    yield "latent-576-512", dict(rows=[(30, 5), (18, 1), (70, 16)], t=16, hq=8, kvh=1, maxb=6, dk=576,
                                 dv=512), dict(dv=512, scale=0.07)
    # a row split: one KV head's 2,048 rows in four grid steps of 512 (the budget cut to force it)
    yield "value-dim-rows-split-in-four", dict(rows=[(100, 40), (18, 1), (64, 64), (70, 3)], t=64, hq=32,
                                               kvh=1, maxb=8, dk=64, dv=32), dict(dv=32, scale=0.1, splits=4)
    yield "bf16-32q8kv", dict(rows=chunk, t=256, hq=32, kvh=8, maxb=20, dtype=jnp.bfloat16), {}


@pytest.mark.parametrize("path", ["kernel", "fallback"])
@pytest.mark.parametrize("name,case,how", list(_flat_cases()), ids=lambda v: v if isinstance(v, str) else "")
def test_q_on_the_flat_axis_is_the_padded_bucket_on_every_live_row(monkeypatch, name, case, how, path):
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
    padded = paged.paged_attention(*drawn, **facts)
    for spare in (0, 9):  # the flat axis full to its last slot, and with dead slots behind
        flat, (row, col) = flat_of(drawn, spare)
        got = paged.paged_attention_flat(flat, *drawn[1:], chunk=case["t"], **facts)
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
    ref = paged._dense_fallback(*drawn, 1.0 / np.sqrt(32), None)
    np.testing.assert_allclose(np.asarray(got)[:len(row)], np.asarray(ref)[row, col], atol=2e-5)
    assert not interpreter.races.races_found


# ------------------------------------------------------------ the tile chooser
PARENTS_TILES = [  # (t, hq, kvh, dh, dv, (kvg, rows, splits, tile) at 7e19018, slots)
    (1, 32, 8, 128, None, (8, 16, 1, 16), 4), (9, 32, 8, 128, None, (8, 48, 1, 48), 4),
    (128, 32, 8, 128, None, (8, 512, 1, 256), 4), (256, 32, 8, 128, None, (8, 1024, 1, 256), 4),
    (512, 32, 8, 128, None, (4, 2048, 1, 256), 4), (1, 16, 16, 128, None, (16, 16, 1, 16), 4),
    (256, 16, 16, 128, None, (16, 256, 1, 256), 2), (1, 128, 1, 640, 512, (1, 128, 1, 128), 4),
    (16, 128, 1, 640, 512, (1, 2048, 1, 256), 4), (512, 128, 1, 640, 512, (1, 4096, 16, 256), 4),
    (1, 32, 4, 128, None, (4, 16, 1, 16), 4), (512, 32, 4, 128, None, (2, 4096, 1, 256), 4),
    (512, 64, 8, 128, None, (2, 4096, 1, 256), 4), (128, 71, 1, 64, None, (1, 9216, 1, 256), 4),
    (4096, 64, 8, 128, None, (1, 11008, 3, 256), 4), (512, 16, 16, 128, None, (16, 512, 1, 256), 2),
    (512, 64, 64, 128, None, (16, 512, 1, 256), 2), (1024, 32, 32, 128, None, (8, 1024, 1, 256), 4),
]


@pytest.mark.parametrize("t,hq,kvh,dh,dv,parents,slots", PARENTS_TILES, ids=lambda v: str(v))
def test_the_chooser_adds_slots_and_leaves_heads_and_rows_as_they_were(t, hq, kvh, dh, dv, parents,
                                                                       slots):
    """``slots`` never costs KV heads a step (PR 30's gain rests on them): the
    parent's ``(kvg, rows, splits, tile)`` at the cells' shapes (Mistral, OLMoE,
    DeepSeek-V2's latent pool, LFM2's packed heads) and at the widest the
    compile tests pin, with the most slots that reckon under ``VMEM_SLOTS_BYTES``."""
    got = paged.step_tile(t, hq, kvh, dh, 128, jnp.bfloat16, jnp.bfloat16, dv)
    assert got == parents + (slots, )
    kvg, rows, _, tile, _ = got
    need = {s: paged._step_vmem_bytes(kvg, rows, tile, dh, 128, 2, 2, dv, s) for s in (1, 2, 4)}
    assert need[1] <= paged.VMEM_BUDGET_BYTES and need[1] < need[2] < need[4]
    assert slots == 1 or need[slots] <= paged.VMEM_SLOTS_BYTES
    assert slots == 4 or need[2 * slots] > paged.VMEM_SLOTS_BYTES  # the wider step would not fit


def test_a_steps_reckoning_counts_the_wider_tiles():
    """Four slots: K and V tiles four times as large (two of each), and the
    scores, probabilities and masks of a row tile four times as wide."""
    one, four = (paged._step_vmem_bytes(8, 1024, 256, 128, 128, 2, 2, None, s) for s in (1, 4))
    tiles = 2 * 2 * 8 * 128 * 128 * 2
    work = 8 * 256 * 4 * 128 * 4
    assert four - one == 3 * tiles + 3 * work
    latent = [paged._step_vmem_bytes(1, 4096, 256, 640, 128, 2, 2, 512, s) for s in (1, 4)]
    assert latent[1] - latent[0] == 3 * (2 * 128 * 640 * 2) + 3 * (256 * 4 * 128 * 4)


# ------------------------------------------------------------ the traced size
def every_equation(jaxpr):
    """The equations of a jaxpr and of every jaxpr its equations hold (branches,
    loop bodies, the calls jnp makes of its own jitted helpers)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from every_equation(inner)


def count_equations(jaxpr) -> int:
    return sum(1 for _ in every_equation(jaxpr))


def kernel_equations(monkeypatch, n, t, hq, kvh, dh, maxb, dv=None, window=4096):
    """The kernel's body and its index maps, as ``paged_attention`` traces them."""
    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    shape = jax.ShapeDtypeStruct
    ints = [shape(s, jnp.int32) for s in ((n, maxb), (n, ), (n, ), (n, ))]
    q, pool = shape((n, t, hq, dh), jnp.bfloat16), shape((256, kvh, 128, dh), jnp.bfloat16)
    if dv is None:
        traced = jax.make_jaxpr(lambda q, k, v, *i: paged.paged_attention(
            q, k, v, *i, block_size=128, window=window))(q, pool, pool, *ints)
    else:
        traced = jax.make_jaxpr(lambda q, k, *i: paged.paged_attention(
            q, k, None, *i, block_size=128, softmax_scale=0.1147, value_dim=dv))(q, pool, *ints)
    (call, ) = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    maps = sum(count_equations(m.index_map_jaxpr.jaxpr) for m in call.params["grid_mapping"].block_mappings)
    return count_equations(call.params["jaxpr"]) + maps


# The parent's counts, read with ``count_equations`` at commit 7e19018 (one table
# slot a step: body 118 / 240 / 230, its two K/V index maps 17 each).
PARENTS_EQUATIONS = {"mistral-n32-T1": 152, "mistral-n32-T256": 274, "mla-n4-T512": 247}
SHAPES = {"mistral-n32-T1": (32, 1, 32, 8, 128, 20), "mistral-n32-T256": (32, 256, 32, 8, 128, 20),
          "mla-n4-T512": (4, 512, 128, 1, 640, 64, 512, None)}


@pytest.mark.parametrize("program", sorted(SHAPES))
def test_the_kernels_traced_size_is_held(monkeypatch, program):
    """A cell meets 38-70 programs and each traces and lowers the kernel once:
    what the body and its index maps cost there is warm ``setup_s`` (PR 30's
    first form and PR 34 were refused by it).  The count does not grow with
    the table's width, so not with ``slots``, and stays within a quarter of
    the parent's.  A proxy: the measured trace-and-lower time decides
    (CHANGES.md, PR 35: a jnp operator costs five times a ``lax`` primitive to
    trace, and a BlockSpec costs more than all of this body's equations)."""
    n, t, hq, kvh, dh, maxb, *rest = SHAPES[program]
    counts = {b: kernel_equations(monkeypatch, n, t, hq, kvh, dh, b, *rest) for b in (4, 20, 40, maxb)}
    assert len(set(counts.values())) == 1, counts
    assert counts[maxb] <= 1.25 * PARENTS_EQUATIONS[program], (counts, PARENTS_EQUATIONS[program])


# ------------------------------------------------------------ the counter
def test_kernel_steps_count_the_grids_table_axis():
    """``n x ceil(b / slots) x passes`` beside ``table_slots``, with the slots
    of the program's ``t``; host integers."""
    from deepspeed_tpu.inference.v2.fastpath import ServeCounters
    counters = ServeCounters(kernel_slots={1: 4, 256: 2}.__getitem__)
    counters.count_slots(32, 1, 20, live_tokens=32, live_blocks=90)
    counters.count_slots(32, 256, 18, live_tokens=256, live_blocks=90, flat=256)
    counters.count_slots(16, 1, 10, live_tokens=160, live_blocks=40, passes=10)
    assert counters.table_slots == 32 * 20 + 32 * 18 + 16 * 10 * 10
    assert counters.kernel_steps == 32 * 5 + 32 * 9 + 16 * 3 * 10
    assert ServeCounters().kernel_slots(7) == 1 and "kernel_steps" in counters.snapshot()


@pytest.mark.parametrize("launch,rows", [
    (dict(n=32, t=1, b=20, live_tokens=32, live_blocks=90), 32),
    (dict(n=32, t=256, b=18, live_tokens=256, live_blocks=90, flat=256), 32),
    (dict(n=32, t=256, b=18, live_tokens=256, live_blocks=90), 32),
    (dict(n=16, t=1, b=10, live_tokens=160, live_blocks=40, passes=10), 160),
    (dict(n=8, t=5, b=10, live_tokens=24, live_blocks=40, every_position=True), 40),
], ids=["decode-step", "compacted-chunk", "padded-chunk", "burst-of-ten", "spec-verify"])
def test_head_rows_count_a_last_row_a_sequence_a_pass(launch, rows):
    """``head_rows``: n a forward pass of a step or a burst whatever the bucket's
    ``t`` or its flat slots (ISSUE 44: the head runs over each row's last live
    token alone), every slot of a program that scores every position."""
    from deepspeed_tpu.inference.v2.fastpath import ServeCounters
    counters = ServeCounters()
    counters.count_slots(**launch)
    assert counters.head_rows == counters.snapshot()["head_rows"] == rows
    assert counters.head_rows <= counters.token_slots


@pytest.mark.parametrize("group,align", [(4, 4), (1, 16), (8, 2), (128, 1), (71, 16), (6, 8)])
def test_attention_slots_count_the_layout_the_kernel_was_handed(group, align):
    """``attn_token_slots``: n x t a padded pass and every pass of a burst, the
    flat row axis over ``group`` a compacted one: its S slots and, a sequence,
    the positions that begin it on a whole sublane tile of rows."""
    from deepspeed_tpu.inference.v2.fastpath import ServeCounters
    assert paged.flat_token_slots(32, 256, group) == 256 + 32 * (align - 1)
    counters = ServeCounters(attn_slots=lambda n, flat: paged.flat_token_slots(n, flat, group))
    counters.count_slots(32, 1, 20, live_tokens=32, live_blocks=90)  # padded
    assert (counters.attn_token_slots, counters.token_slots) == (32, 32)
    counters.count_slots(32, 256, 18, live_tokens=256, live_blocks=90, flat=256)  # flat
    assert counters.token_slots == 32 + 256
    assert counters.attn_token_slots == 32 + 256 + 32 * (align - 1)
    counters.count_slots(16, 1, 10, live_tokens=160, live_blocks=40, passes=10)  # a burst of ten
    assert counters.attn_token_slots == 32 + 256 + 32 * (align - 1) + 160
    assert counters.snapshot()["attn_token_slots"] == counters.attn_token_slots
    plain = ServeCounters()  # no kernel's word on it: the flat slots themselves
    plain.count_slots(32, 256, 18, live_tokens=256, live_blocks=90, flat=256)
    assert plain.attn_token_slots == 256


def _grid_of_the_kernel(module, config, n, t, b, stateful=False):
    """The grid of the ``paged_attention`` call in the family's traced forward."""
    kv = module.init_paged_cache(config, 8, BS, dtype=jnp.float32,
                                 **({"state_slots": n} if stateful else {}))
    params = jax.eval_shape(lambda: module.init_params(config, jax.random.PRNGKey(0)))
    ints = [jax.ShapeDtypeStruct(s, jnp.int32) for s in ((n, t), (n, ), (n, ), (n, b + stateful))]
    traced = jax.make_jaxpr(lambda p, kv, *i: module.forward_paged(
        config, p, *i, kv, block_size=BS))(params, kv, *ints)

    grids = {eqn.params["grid_mapping"].grid for eqn in every_equation(traced.jaxpr)
             if eqn.primitive.name == "pallas_call" and eqn.params["name"] == "paged_attention"}
    return kv, grids


@pytest.mark.parametrize("family,t,b", [("llama", 1, 6), ("llama", 16, 5), ("deepseek_v2", 1, 7),
                                        ("deepseek_v2", 16, 4), ("lfm2", 1, 6), ("lfm2", 16, 3)])
def test_the_engines_slots_are_the_launched_programs(monkeypatch, family, t, b):
    """``transformer.paged_step_slots`` works the kernel's slots out of the
    family's config and pool; the grid of the program the family traces has
    ``ceil(b / slots)`` steps along the table: K and V pools, a latent pool with
    its ``paged_value_dim``, packed heads beside a state column in the table."""
    import importlib

    from deepspeed_tpu.models.transformer import paged_step_slots
    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    module = importlib.import_module(f"deepspeed_tpu.models.{family}")
    config = {"llama": lambda: module.LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2),
              "deepseek_v2": lambda: module.DeepseekV2Config.tiny(local_experts=4),
              "lfm2": lambda: module.Lfm2Config.tiny()}[family]()
    kv, grids = _grid_of_the_kernel(module, config, 4, t, b, stateful=family == "lfm2")
    slots = paged_step_slots(module, config, kv, jnp.float32)[0](t)
    assert slots in paged.STEP_SLOTS and grids and {g[-1] for g in grids} == {-(-b // slots)}
