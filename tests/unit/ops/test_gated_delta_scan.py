"""The gated delta rule's chunked scan (``ops/linear_attention/gated_delta.py``)
against the rule token by token (``chipbench/references/qwen3_next.py
delta_rule``, which shares no algebra with it): the Pallas kernel in interpret
mode and the ``lax.scan`` form, sequences as rows of a padded ``[N, T]`` and
compacted onto one flat axis, lengths that are and are not multiples of the
chunk, with and without a carried state, decays near 0 and near 1, and the
one-token update; each at one, two and four value heads a key head (the kernel
takes one or two of a key head's value heads a grid step: ``heads_a_step``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references.qwen3_next import delta_rule
from deepspeed_tpu.ops import _pallas
from deepspeed_tpu.ops.linear_attention import (CHUNK, gated_delta_scan, gated_delta_step,
                                                scan_chunks)
from deepspeed_tpu.ops.linear_attention import gated_delta

HK, HV, DK, DV = 2, 4, 16, 8  # HK, HV: what the ``heads`` fixture sets for a test
TOL = 5e-6  # float32 throughout: the chunk's triangular inverse against 64 sequential updates


@pytest.fixture(params=[(2, 2), (2, 4), (1, 4)], ids=lambda v: f"hk{v[0]}-hv{v[1]}")
def heads(request, monkeypatch):
    """(key heads, value heads): one, two and four value heads a key head, so a
    step of one head, a key head in one step and a key head in two."""
    hk, hv = request.param
    monkeypatch.setitem(globals(), "HK", hk)
    monkeypatch.setitem(globals(), "HV", hv)
    assert gated_delta.heads_a_step(hv // hk) == (1 if hk == hv else 2)
    return request.param


@pytest.fixture(params=["scan", "kernel"])
def form(request, monkeypatch, heads):
    monkeypatch.setattr(_pallas, "INTERPRET", request.param == "kernel")
    return request.param


def draw(rng, s, decay=(1e-4, 3.0)):
    norm = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = (norm(rng.normal(size=(s, HK, DK))) * DK ** -0.5).astype(np.float32)
    k = norm(rng.normal(size=(s, HK, DK))).astype(np.float32)
    v = rng.normal(size=(s, HV, DV)).astype(np.float32)
    g = -rng.uniform(*decay, size=(s, HV)).astype(np.float32)
    beta = rng.uniform(0, 1, size=(s, HV)).astype(np.float32)
    return q, k, v, g, beta


@functools.partial(jax.jit, static_argnums=0)
def _rule(rep, q, k, v, g, beta, state):  # one program a length, not an op at a time
    return delta_rule(jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1), v, jnp.exp(g), beta, state)


def token_by_token(seq, state):
    o, last = _rule(HV // HK, *seq, state)
    return np.asarray(o), np.asarray(last)


def padded(seqs, counts, t):
    fill = lambda a, c: np.concatenate([a[:c], np.full((t - c, ) + a.shape[1:], 7.0, np.float32)])
    return [jnp.asarray(np.stack([fill(s[i], c) for s, c in zip(seqs, counts)])) for i in range(5)]


def flat(seqs, counts, slots):
    """(arrays [1, S, ...], row, col): the rows' live tokens one after another, the tail dead."""
    row = np.repeat(np.arange(len(counts)), counts)
    col = np.concatenate([np.arange(c) for c in counts])
    dead = slots - len(row)
    arrays = [jnp.asarray(np.concatenate(
        [np.concatenate([s[i][:c] for s, c in zip(seqs, counts)]),
         np.full((dead, ) + seqs[0][i].shape[1:], 5.0, np.float32)]))[None] for i in range(5)]
    at = lambda a: jnp.asarray(np.concatenate([a, np.zeros(dead, int)]))[None]
    return arrays, at(row), at(col)


@pytest.mark.parametrize("counts,carried", [
    ((130, 0, 1, 64, 77), True), ((130, 0, 1, 64, 77), False), ((64, 128), True), ((5, ), False),
    ((0, 0, 9), True)], ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else str(v))
@pytest.mark.parametrize("layout", ["padded", "flat"])
def test_the_chunked_scan_is_the_rule_token_by_token(form, layout, counts, carried):
    rng = np.random.default_rng(sum(counts))
    seqs = [draw(rng, max(c, 1)) for c in counts]
    states = [rng.normal(size=(HV, DK, DV)).astype(np.float32) if carried
              else np.zeros((HV, DK, DV), np.float32) for _ in counts]
    want = [token_by_token(tuple(a[:c] for a in s), jnp.asarray(s0)) for s, c, s0 in
            zip(seqs, counts, states)]
    if layout == "padded":
        arrays = padded(seqs, counts, t=-(-max(counts) // 8) * 8)
        o, new = jax.jit(gated_delta_scan)(*arrays, jnp.asarray(np.stack(states)), jnp.asarray(counts))
        outs = [np.asarray(o[r, :c]) for r, c in enumerate(counts)]
    else:
        arrays, row, col = flat(seqs, counts, slots=-(-(sum(counts) + 3) // 8) * 8)
        o, new = jax.jit(gated_delta_scan)(*arrays, jnp.asarray(np.stack(states)),
                                           jnp.asarray(counts), row, col)
        starts = np.cumsum(counts) - np.asarray(counts)
        outs = [np.asarray(o[0, s:s + c]) for s, c in zip(starts, counts)]
    assert np.isfinite(np.asarray(o)).all()  # the dead positions too: nothing of them is garbage
    for r, c in enumerate(counts):
        if c:
            np.testing.assert_allclose(outs[r], want[r][0], atol=TOL, rtol=0)
            np.testing.assert_allclose(np.asarray(new[r]), want[r][1], atol=TOL, rtol=0)
        else:  # a row with no token keeps its state, bit for bit
            np.testing.assert_array_equal(np.asarray(new[r]), states[r])


@pytest.mark.parametrize("decay", [(1e-7, 1e-5), (8.0, 20.0)], ids=["near-1", "near-0"])
def test_decays_near_one_and_near_zero(form, decay):
    """alpha within 1e-5 of one (a state that keeps everything over 200
    tokens) and under 4e-4 (one that forgets at once): the chunk's
    ``exp(gamma_i - gamma_j)`` neither overflows nor loses the small terms."""
    rng = np.random.default_rng(3)
    seq = draw(rng, 200, decay)
    state = rng.normal(size=(HV, DK, DV)).astype(np.float32)
    want_o, want_s = token_by_token(seq, jnp.asarray(state))
    o, new = jax.jit(gated_delta_scan)(*(jnp.asarray(a)[None] for a in seq), jnp.asarray(state)[None],
                                       jnp.asarray([200]))
    np.testing.assert_allclose(np.asarray(o[0]), want_o, atol=4 * TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(new[0]), want_s, atol=4 * TOL, rtol=0)


def test_keys_that_resemble_one_another_do_not_lose_the_inverse(form):
    """Keys within a few degrees of one another (all in one orthant, as SiLU's
    outputs lie), beta near one and no decay: the strict lower triangle's
    entries are all near one and of one sign, where an inverse by squarings
    over the whole chunk passes through 1e17 and comes back as noise (on the
    chip: 2e36 and then NaN; PERF.md, PR 43).  Blocks of 16 joined by the block
    formula stay within float32 of the rule token by token."""
    rng = np.random.default_rng(11)
    q, k, v, g, beta = draw(rng, 200, decay=(1e-6, 1e-4))
    norm = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    k = norm(np.abs(rng.normal(size=k.shape)) + 1.5).astype(np.float32)
    beta = rng.uniform(0.9, 1.0, size=beta.shape).astype(np.float32)
    want_o, want_s = token_by_token((q, k, v, g, beta), None)
    o, new = jax.jit(gated_delta_scan)(*(jnp.asarray(a)[None] for a in (q, k, v, g, beta)),
                                       jnp.zeros((1, HV, DK, DV), jnp.float32), jnp.asarray([200]))
    scale = max(1.0, np.abs(want_s).max())
    np.testing.assert_allclose(np.asarray(o[0]), want_o, atol=1e-3 * scale, rtol=0)
    np.testing.assert_allclose(np.asarray(new[0]), want_s, atol=1e-3 * scale, rtol=0)


def test_bfloat16_inputs_keep_float32_accumulations(form):
    """q, k, v in bfloat16, the state float32 in and out: the products'
    operands are bfloat16 (the inverse's chain in three passes), every sum
    float32, so the result is within bfloat16's rounding of the float32 rule
    over the same rounded inputs, not a hundred roundings worse."""
    rng = np.random.default_rng(5)
    q, k, v, g, beta = draw(rng, 150)
    q, k, v = (np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)) for a in (q, k, v))
    state = rng.normal(size=(HV, DK, DV)).astype(np.float32)
    want_o, want_s = token_by_token((q, k, v, g, beta), jnp.asarray(state))
    o, new = jax.jit(gated_delta_scan)(
        *(jnp.asarray(a, jnp.bfloat16)[None] for a in (q, k, v)), jnp.asarray(g)[None],
        jnp.asarray(beta)[None], jnp.asarray(state)[None], jnp.asarray([150]))
    assert o.dtype == jnp.bfloat16 and new.dtype == jnp.float32
    scale = np.abs(want_o).max()
    assert np.abs(np.asarray(o[0], np.float32) - want_o).max() < 0.03 * scale
    assert np.abs(np.asarray(new[0]) - want_s).max() < 0.03 * np.abs(want_s).max()


def test_the_heads_of_a_step_do_not_leak_into_each_other(form):
    """The value heads a step takes together lie on one diagonal and share its
    products: what is between their blocks is an exact zero, so the first
    head's output and new state are the same to the bit whether the others hold
    nothing (v = 0, beta = 0, no decay, no state) or write at full strength
    (beta = 1) values and a carried state a thousand times larger."""
    rng = np.random.default_rng(13)
    q, k, v, g, beta = draw(rng, 150)
    state = rng.normal(size=(HV, DK, DV)).astype(np.float32)

    def first_head(others_v, others_g, others_beta, others_state):
        vv, gg, bb, ss = v.copy(), g.copy(), beta.copy(), state.copy()
        vv[:, 1:], gg[:, 1:], bb[:, 1:], ss[1:] = others_v, others_g, others_beta, others_state
        o, new = jax.jit(gated_delta_scan)(*(jnp.asarray(a)[None] for a in (q, k, vv, gg, bb)),
                                           jnp.asarray(ss)[None], jnp.asarray([150]))
        return np.asarray(o[0, :, 0]), np.asarray(new[0, 0])

    quiet = first_head(0.0, 0.0, 0.0, 0.0)
    loud = first_head(1e3 * rng.normal(size=(150, HV - 1, DV)).astype(np.float32), g[:, 1:], 1.0,
                      1e3 * rng.normal(size=(HV - 1, DK, DV)).astype(np.float32))
    np.testing.assert_array_equal(quiet[0], loud[0])
    np.testing.assert_array_equal(quiet[1], loud[1])
    want_o, want_s = token_by_token((q, k, v, g, beta), jnp.asarray(state))
    np.testing.assert_allclose(quiet[0], want_o[:, 0], atol=TOL, rtol=0)
    np.testing.assert_allclose(quiet[1], want_s[0], atol=TOL, rtol=0)


def test_the_one_token_update_is_the_rule(heads):
    rng = np.random.default_rng(7)
    q, k, v, g, beta = draw(rng, 3)
    states = rng.normal(size=(3, HV, DK, DV)).astype(np.float32)
    o, new = gated_delta_step(jnp.repeat(q, HV // HK, 1), jnp.repeat(k, HV // HK, 1), v, g, beta,
                              jnp.asarray(states))
    for r in range(3):
        want_o, want_s = token_by_token(tuple(a[r:r + 1] for a in (q, k, v, g, beta)),
                                        jnp.asarray(states[r]))
        np.testing.assert_allclose(np.asarray(o[r]), want_o[0], atol=TOL, rtol=0)
        np.testing.assert_allclose(np.asarray(new[r]), want_s, atol=TOL, rtol=0)


def test_the_chunk_table_names_blocks_that_are_there():
    """Empty chunks (a padded row's tail, the chunks a bucket allows past the
    live ones) name the sequence of the live chunk before them, or of the first
    live one: the kernel's grid step then fetches and stores nothing for them."""
    seq = jnp.asarray([0, 0, 1, 1, 2, 2])
    nth = jnp.asarray([0, 1, 0, 1, 0, 1])
    count = jnp.asarray([0, 0, 64, 3, 0, 0])
    table = np.asarray(gated_delta._chunk_table(seq, nth, count, jnp.asarray([0, 2, 0])))
    assert table[gated_delta.SEQ].tolist() == [1, 1, 1, 1, 1, 1]
    assert table[gated_delta.FIRST].tolist() == [0, 0, 1, 0, 0, 0]
    assert table[gated_delta.LAST].tolist() == [0, 0, 0, 1, 0, 0]
    assert table[gated_delta.LIVE].tolist() == [0, 0, 64, 3, 0, 0]
    # what the serving counters ask: a decode step walks none, a padded bucket a row's chunks,
    # a compacted pass its slots' chunks and one more a sequence
    assert (scan_chunks(8, 1), scan_chunks(4, 100), scan_chunks(8, 2048, 2048)) == (0, 8, 40)
    assert CHUNK == 64
