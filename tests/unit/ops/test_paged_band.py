"""A row tile of the paged kernel works only on the steps it sees (ISSUE 57): the
tile loop of a grid step runs over the tiles inside the step's band (a window's
near edge, a chunk's causal edge: ``paged.tile_band``) and a tile's softmax state
begins at the tile's own first step (``paged.tile_first_step``).  Here: the two
functions against the reference's mask, brute force; the kernel, interpreted,
against ``_dense_fallback`` where a sequence has several tiles AND several steps;
a state that must not be read from the sequence before; and the same calls bit
for bit with the parent's loop (every live tile at every live step, every state
begun at step 0)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepspeed_tpu.ops.attention import paged

from .compiled import compiled, dense_fallback
from .test_dsa import flat_of as selected_flat_of
from .test_dsa_selection import selected_case
from .test_paged_slots import BS, drawn_case
from .test_paged_slots_flat import flat_of

# ------------------------------------------------- (a) the bounds, brute force
# (tile, keys a step, keys a block): the benchmark's chunk shapes, a latent decode
# row's one tile of 128, and the small tiles the kernel cases below run
STEPS = {"256x512": (256, 512, 128), "128x512": (128, 512, 128), "32x64": (32, 64, 16),
         "16x64": (16, 64, 16)}


def reference_mask(start, ntok, length, window, span):
    """``_dense_fallback``'s mask of one sequence, token by key, over ``span`` keys."""
    qp = start + np.arange(ntok)[:, None]
    kpos = np.arange(span)[None, :]
    mask = (kpos <= qp) & (kpos < length) & (qp >= 0)
    if window is not None:
        mask &= kpos > qp - window
    return mask


def band_grid(tile, keys, bs, window, group):
    """``(steps, firsts)``: every ``(start, ntok, length, base, split r, step b)`` of a
    small grid with the brute-force answer, which tiles of the split see a key of the
    step; and, a ``(start, ntok, length, base, r)``, each tile's first such step."""
    rows = 2 * tile  # a row split of two tiles; r = 0, 1, 2 reach past the tokens
    edge = window if window is not None and window < 5000 else 3 * keys
    # among them a chunk whose first tile lies wholly before a step's first key
    starts = sorted({0, 1, keys - 1, keys, 2 * keys + 5, max(edge - 1, 0), edge, edge + keys + 3,
                     3 * keys - max(tile // group, 1) - 3})
    steps, firsts = [], []
    for start in starts:
        for ntok in (1, 2, 17, -(-rows // group), -(-3 * rows // (2 * group)) + 1):
            for length in (start + ntok, start + ntok + 5, start + max(ntok - 3, 1), max(start - 2, 1)):
                span = max(length, start + ntok) + keys
                mask = reference_mask(start, ntok, length, window, span + keys)
                for base in sorted({0, paged.walk_first_block(start, window, bs) * bs}):
                    for r in (0, 1, 2):
                        first_row = r * rows
                        live = max(min(ntok * group - first_row, rows), 0)
                        tiles = -(-live // tile)
                        first = np.full(max(tiles, 1), -1)
                        for b in range(-(-(span - base) // keys)):
                            k0 = base + b * keys
                            sees = []
                            for i in range(tiles):
                                lo = (first_row + i * tile) // group
                                hi = min((first_row + (i + 1) * tile - 1) // group, ntok - 1)
                                if mask[lo:hi + 1, k0:k0 + keys].any():
                                    sees.append(i)
                                    first[i] = b if first[i] < 0 else first[i]
                            steps.append((k0, first_row, live, start, ntok, length, base, b, tiles, sees))
                        firsts.append((first_row, start, base, tiles, first.tolist()))
    return steps, firsts


@pytest.mark.parametrize("group", [1, 4, 6, 128])
@pytest.mark.parametrize("window", [None, 1, 33, 4096])
@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_band_is_the_tiles_that_see_a_key_of_the_step(step, window, group):
    """``tile_band`` (the kernel's own expression) against ``any`` over the tile's
    rows and the step's keys of the reference's mask: where the context ends at the
    last query token (every caller's ``lengths``) the same tiles at every live step,
    one range; with a longer or shorter context never a tile too few, and none before
    ``tile_first_step``, which is the first step a tile works at."""
    tile, keys, bs = STEPS[step]
    steps, firsts = band_grid(tile, keys, bs, window, group)
    columns = [jnp.asarray(col, jnp.int32) for col in zip(*(c[:4] for c in steps))]
    band = jax.jit(jax.vmap(lambda *a: paged.tile_band(*a, tile=tile, group=group, keys=keys,
                                                       window=window)))
    lo, hi = (np.asarray(x) for x in band(*columns))
    # ``tile_first_step`` of every tile asked about below, as one program (a call a tile is a
    # dozen ops dispatched one by one, some 10,000 tiles a case)
    asked = sorted({(c[1] + i * tile, c[3], c[6]) for c, lo_, hi_ in zip(steps, lo, hi) for i in range(lo_, hi_)}
                   | {(first_row + i * tile, start, base) for first_row, start, base, tiles, _ in firsts
                      for i in range(tiles)})
    begins = jax.jit(jax.vmap(lambda *a: jnp.asarray(paged.tile_first_step(
        *a, group=group, keys=keys, window=window), jnp.int32)))
    first_step = dict(zip(asked, np.asarray(begins(*(jnp.asarray(col, jnp.int32) for col in zip(*asked)))).tolist()))
    skipped = exact = 0
    for (k0, first_row, _, start, ntok, length, base, b, tiles, sees), lo_, hi_ in zip(steps, lo, hi):
        assert lo_ >= 0 and hi_ <= tiles
        got = list(range(lo_, hi_))
        if window is not None:  # no tile is visited before the step its state begins at
            assert all(b >= first_step[first_row + i * tile, start, base] for i in got)
        if length == start + ntok and k0 < length:  # a live step of a call as the engine makes it
            assert got == sees, (step, window, group, (k0, first_row, start, ntok, length), got, sees)
            skipped += tiles - len(sees)
            exact += 1
        else:
            assert set(sees) <= set(got), (step, window, group, (k0, first_row, start, ntok, length), got, sees)
    assert skipped > 0 and exact > 100  # the grid holds live steps at which a live tile sees nothing
    for first_row, start, base, tiles, first in firsts:
        for i in range(tiles):
            if first[i] >= 0:  # a tile that sees nothing at all never starts
                assert first_step[first_row + i * tile, start, base] == first[i], \
                    (step, window, group, first_row, start, base, i)


def test_a_latent_decode_rows_one_tile_sees_every_live_step():
    """One token of 128 rows (DeepSeek-V2, GLM-5, LongCat at decode) enters the tile
    loop with one tile whose band is every live step: the parent's bounds."""
    for length in (1, 511, 512, 513, 40000):
        for b in range(-(-length // 512)):
            lo, hi = paged.tile_band(b * 512, 0, 128, length - 1, tile=128, group=128, keys=512,
                                     window=None)
            assert (int(lo), int(hi)) == (0, 1)
    assert paged.tile_first_step(0, 39999, 0, group=128, keys=512, window=None) == 0


# ------------------------------------- (b), (d) the kernel over tiles and steps
def parents_loop(monkeypatch):
    """The tile loop as the parent ran it: every tile that holds a token at every
    live step, every tile's state begun at the walk's step 0."""
    monkeypatch.setattr(paged, "tile_band", lambda k0, first_row, live, start, *, tile, **_: (
        0, lax.div(lax.add(live, tile - 1), tile)))
    monkeypatch.setattr(paged, "tile_first_step", lambda *_, **__: 0)


def worked(case, how, t, group, tile, keys):
    """``(visited, live)``: the (tile, step) pairs the band's loop visits and those
    the parent's did, over the case's sequences, by the kernel's own function on host
    integers (one row split, the walk from the window's first block)."""
    window = how.get("window")
    visited = live_pairs = 0
    for length, start, ntok in zip(*(np.asarray(a).tolist() for a in case[-3:])):
        if not ntok:
            continue
        base = 0 if how.get("selected") else paged.walk_first_block(start, window, BS) * BS
        tiles = -(-ntok * group // tile)
        for b in range(-(-(length - base) // keys)):
            lo, hi = paged.tile_band(base + b * keys, 0, ntok * group, start, tile=tile, group=group,
                                     keys=keys, window=window)
            visited += max(int(hi) - int(lo), 0)
            live_pairs += tiles
    return visited, live_pairs


# a chunk deep in a prompt beside a decode row, a row with no token, a sequence that begins and a
# chunk that ends inside a tile: (length, n_tokens) a row of a bucket of 64 tokens
DEEP = [(200, 64), (70, 1), (0, 0), (64, 64), (150, 37)]
KERNEL_CASES = {
    # name: (drawn_case's arguments, the call's, the row tile: None is ROW_TILE itself)
    "causal": (dict(rows=DEEP, t=64, hq=4, kvh=2, maxb=16), {}, 32),
    "window-20": (dict(rows=DEEP, t=64, hq=4, kvh=2, maxb=16), dict(window=20), 32),
    "window-64": (dict(rows=DEEP, t=64, hq=4, kvh=2, maxb=16), dict(window=64), 32),
    "window-97": (dict(rows=DEEP, t=64, hq=4, kvh=2, maxb=16), dict(window=97), 32),
    "mqa-window-33": (dict(rows=DEEP, t=64, hq=8, kvh=1, maxb=16), dict(window=33), 32),
    "group-6-window-50": (dict(rows=DEEP, t=64, hq=6, kvh=1, maxb=16), dict(window=50), 32),
    "value-dim": (dict(rows=DEEP, t=64, hq=8, kvh=1, maxb=16, dk=64, dv=32), dict(dv=32, scale=0.1), 32),
    "value-dim-rows-split-in-four": (dict(rows=[(300, 64), (18, 1), (64, 64), (170, 35)], t=64, hq=32, kvh=1,
                                          maxb=20, dk=64, dv=32), dict(dv=32, scale=0.1, splits=4), None),
    "rows-split-in-four-window-40": (dict(rows=[(300, 64), (18, 1), (64, 64), (170, 35)], t=64, hq=32, kvh=1,
                                          maxb=20, dk=64, dv=32),
                                     dict(dv=32, scale=0.1, splits=4, window=40), None),
    "row-tile-256": (dict(rows=[(400, 128), (70, 1), (128, 128), (333, 90)], t=128, hq=8, kvh=2, maxb=28),
                     {}, None),
    "row-tile-256-window-100": (dict(rows=[(400, 128), (70, 1), (128, 128), (333, 90)], t=128, hq=8, kvh=2,
                                     maxb=28), dict(window=100), None),
}


def kernel_and_parent(monkeypatch, name, layout):
    """The case's call by the band's loop and by the parent's, interpreted, with the
    reference: ``(got, parent, want, live)`` on the layout's own axis."""
    from deepspeed_tpu.ops import _pallas
    case, how, tile = KERNEL_CASES[name]
    how = dict(how)
    splits = how.pop("splits", 1)
    if tile is not None:
        monkeypatch.setattr(paged, "ROW_TILE", tile)
    if splits > 1:
        monkeypatch.setattr(paged, "VMEM_BUDGET_BYTES", paged._step_vmem_bytes(
            1, 2048 // splits, 256, case["dk"], BS, 4, 4, case["dv"]))
    t, group = case["t"], case["hq"] // case["kvh"]
    shape = paged.step_tile(t, case["hq"], case["kvh"], case.get("dk", 32), BS, jnp.float32,
                            jnp.float32, case.get("dv"))
    assert shape[2] == splits and shape[1] > shape[3] and case["maxb"] > shape[4]  # tiles AND steps
    drawn = drawn_case(**case)
    visited, live_pairs = worked(drawn, how, t, group, shape[3], shape[4] * BS)
    assert 0 < visited < live_pairs  # the case has (tile, step) pairs the band leaves out
    facts = dict(block_size=BS, window=how.get("window"), softmax_scale=how.get("scale"),
                 value_dim=how.get("dv"))
    q, kpool, vpool, tables, lengths, start_pos, n_tokens = drawn
    scale = how.get("scale") or 1.0 / np.sqrt(q.shape[-1])
    want = np.asarray(dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens,
                                     scale, how.get("window"), None, how.get("dv")))
    monkeypatch.setattr(_pallas, "INTERPRET", True)

    def call():
        if layout == "padded":
            return np.asarray(compiled(paged.paged_attention, **facts)(*drawn))
        flat, _ = flat_of(drawn, spare=5)
        return np.asarray(compiled(paged.paged_attention_flat, chunk=t, **facts)(flat, *drawn[1:]))

    got = call()
    parents_loop(monkeypatch)
    parent = call()
    if layout == "padded":
        live = np.asarray(jnp.arange(t)[None, :] < n_tokens[:, None])
        return got, parent, want, live
    row, col = flat_of(drawn)[1]
    live = np.arange(len(got)) < len(row)
    return got, parent, np.concatenate([want[row, col], np.zeros_like(got[len(row):])]), live


@pytest.mark.parametrize("layout", ["padded", "flat"])
@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_the_kernel_over_tiles_and_steps_is_the_reference_and_the_parents_bits(monkeypatch, name, layout):
    """(b) the band's loop against ``_dense_fallback`` where a sequence has several
    row tiles and several steps; (d) and bit for bit what the parent's loop gives: a
    (tile, step) pair outside the band left ``m``, ``l`` and ``acc`` as they were."""
    got, parent, want, live = kernel_and_parent(monkeypatch, name, layout)
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert np.isfinite(got).all() and (got[~live] == 0.0).all()
    np.testing.assert_array_equal(got, parent)


SELECTED = {  # heads (one KV head), t, starts, counts, window
    "selection": (16, 32, [170, 69, 0, 110], [32, 1, 32, 19], None),
    "selection-window-40": (16, 32, [170, 69, 0, 110], [32, 1, 32, 19], 40),
}


@pytest.mark.parametrize("name", sorted(SELECTED))
def test_the_band_under_a_selection_walks_from_the_tables_first_slot(monkeypatch, name):
    """With a selection the walk begins at slot 0 whatever the window (the
    selection's tiles lie by the table's own steps): the band's steps are counted
    from there, padded and flat, against the reference and the parent's bits."""
    from deepspeed_tpu.ops import _pallas
    heads, t, starts, counts, window = SELECTED[name]
    monkeypatch.setattr(paged, "ROW_TILE", 32)
    q, chosen, args, facts = selected_case(heads, t, len(starts), starts, counts, maxb=16)
    facts["window"] = window
    shape = paged.step_tile(t, heads, 1, q.shape[-1], 16, q.dtype, q.dtype, facts["value_dim"])
    assert shape[1] > shape[3] and 16 > shape[4]
    visited, live_pairs = worked(args, dict(window=window, selected=True), t, heads, shape[3],
                                 shape[4] * 16)
    assert 0 < visited < live_pairs
    want = dense_fallback(q, *args, facts["softmax_scale"], window, None, facts["value_dim"],
                          selection=chosen)
    (qf, chosenf), live, at = selected_flat_of(args[-1], t, q, chosen)
    monkeypatch.setattr(_pallas, "INTERPRET", True)
    got = compiled(paged.paged_attention, selection=chosen, **facts)(q, *args)
    gotf = compiled(paged.paged_attention_flat, chunk=t, selection=chosenf, **facts)(qf, *args)
    valid = np.asarray(jnp.arange(t)[None, :] < args[-1][:, None])
    np.testing.assert_allclose(np.asarray(got)[valid], np.asarray(want)[valid], atol=2e-6)
    np.testing.assert_allclose(np.asarray(gotf)[live], np.asarray(want[at])[live], atol=2e-6)
    parents_loop(monkeypatch)
    np.testing.assert_array_equal(got, compiled(paged.paged_attention, selection=chosen, **facts)(q, *args))
    np.testing.assert_array_equal(gotf, compiled(paged.paged_attention_flat, chunk=t, selection=chosenf,
                                                 **facts)(qf, *args))


# --------------------------------------------- (c) where a tile's state begins
@pytest.mark.parametrize("layout", ["padded", "flat"])
def test_a_tiles_state_begins_at_its_own_first_step(monkeypatch, layout):
    """Two sequences in one call: the first, long and with large scores, leaves large
    ``m``, ``l`` and ``acc`` in every row tile; the second's late tiles first work at a
    step past the walk's first (a window of 20 behind tokens 64 and more keys into the chunk).
    A state read there (``begun = b > 0``) would be the first sequence's."""
    from deepspeed_tpu.ops import _pallas
    monkeypatch.setattr(paged, "ROW_TILE", 32)
    window, t = 20, 128
    q, kpool, vpool, tables, lengths, start_pos, n_tokens = drawn_case([(320, 128), (328, 128)], t, 4, 2, 24)
    q = q.at[0].multiply(8.0)  # the first sequence's scores, and with them its m, are large
    case = (q, kpool, vpool, tables, lengths, start_pos, n_tokens)
    base = paged.walk_first_block(200, window, BS) * BS
    late = [int(paged.tile_first_step(r0, 200, base, group=2, keys=64, window=window))
            for r0 in range(0, 256, 32)]
    assert late[0] == 0 and late[-1] > 0, late  # the second sequence's last tile begins past step 0
    want = np.asarray(dense_fallback(*case, 1.0 / np.sqrt(q.shape[-1]), window))
    monkeypatch.setattr(_pallas, "INTERPRET", True)

    def call():
        if layout == "padded":
            return np.asarray(compiled(paged.paged_attention, block_size=BS, window=window)(*case))
        flat, (row, col) = flat_of(case)
        got = np.asarray(compiled(paged.paged_attention_flat, chunk=t, block_size=BS, window=window)(
            flat, *case[1:]))
        return _onto(got, row, col, want.shape)

    np.testing.assert_allclose(call(), want, atol=2e-5)
    # the band's loop with every state begun at step 0 reads the first sequence's: the test can tell
    monkeypatch.setattr(paged, "tile_first_step", lambda *_, **__: 0)
    assert not np.allclose(call()[1], want[1], atol=1e-2)  # (what no step wrote reads NaN interpreted)


def _onto(flat, row, col, shape):
    out = np.zeros(shape, flat.dtype)
    out[row, col] = flat[:len(row)]
    return out
