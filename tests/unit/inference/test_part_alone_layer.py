"""The third kind of layer by itself (ISSUE 62): a layer whose parameters hold
``transformer.PART_ALONE`` touches NEITHER cache.  A toy family of this file's own
(a gated running sum as the state layer, a plain MLP as the part-alone layer, one
attention layer; one period ``S A * A``) through ``transformer.paged_forward``,
padded and compacted, against a dense computation over the whole sequence: the
part-alone layers take no state row and no pool row (the leaves are ``[L_mixer,
...]`` and ``[L_attention, ...]`` for twice as many layers), nothing but the stream
goes in or out of them, they may make a stack of their own, and with ``hand_on``
they stand in the period's chain."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import transformer
from deepspeed_tpu.models.transformer import PART_ALONE, STATE, STATE_MIXER

D, H, DH, V, BS, NB, SLOTS, REPEATS = 16, 2, 8, 32, 4, 24, 3, 2


def params(key):
    ks = iter(jax.random.split(key, 16))
    lin = lambda *shape: jax.random.normal(next(ks), shape) * shape[-2] ** -0.5
    state = {STATE_MIXER: {"decay": jax.random.uniform(next(ks), (REPEATS, D), minval=0.5, maxval=0.95),
                           "w": lin(REPEATS, D, D)}}
    alone = lambda: {PART_ALONE: {"w1": lin(REPEATS, D, 2 * D), "w2": lin(REPEATS, 2 * D, D)}}
    attn = {"attn": {"wq": lin(REPEATS, D, H * DH), "wk": lin(REPEATS, D, H * DH), "wv": lin(REPEATS, D, H * DH),
                     "wo": lin(REPEATS, H * DH, D)}}
    return {"embed": jax.random.normal(next(ks), (V, D)), "head": lin(D, V),
            "period": (state, alone(), attn, alone()), "tail": alone()}  # a stack of part-alone layers last


def forward(p, tokens, n_tokens, start_pos, tables, cache, bound=None, hand_on=False):
    def mix(lp, x, filtered, live, carried, places):  # s_t = decay s_{t-1} + x_t; y = x + s W
        m = lp[STATE_MIXER]
        if places.row is None:
            seq, counts = x, places.n_tokens
            steps = jnp.arange(x.shape[1])[None, :] < counts[:, None]

            def token(s, inp):
                x_t, live_t = inp
                s = jnp.where(live_t[:, None], m["decay"] * s + x_t, s)
                return s, s
            last, states = jax.lax.scan(token, carried, (jnp.moveaxis(seq, 1, 0), steps.T))
            return x + jnp.moveaxis(states, 0, 1) @ m["w"], last
        # compacted: slot j holds token col[j] of row row[j]
        def token(s, inp):
            x_t, r, ok = inp
            new = m["decay"] * s[r] + x_t
            s = jnp.where(ok, s.at[r].set(new), s)
            return s, new
        last, states = jax.lax.scan(token, carried, (x[0], places.row[0], live[0]))
        return x + (states @ m["w"])[None], last

    def alone(lp, x, live, handed=None):
        y = x + jnp.tanh(x @ lp[PART_ALONE]["w1"]) @ lp[PART_ALONE]["w2"]
        return (y, (0 if handed is None else handed) + 1) if hand_on else y

    def qkv(lp, x, safe_pos):
        a, lead = lp["attn"], x.shape[:2]
        return ((x @ a["wq"]).reshape(lead + (H, DH)), (x @ a["wk"]).reshape(lead + (H, DH)),
                (x @ a["wv"]).reshape(lead + (H, DH)), None)

    def finish(lp, x, kept, attn, live, handed=None):
        y = x + attn.reshape(x.shape[:2] + (H * DH, )) @ lp["attn"]["wo"]
        return (y, handed) if hand_on else y

    return transformer.paged_forward(
        [p["period"], p["tail"]], tokens, n_tokens, start_pos, tables, cache, block_size=BS,
        live_token_bound=bound, embed=lambda t, pos: p["embed"][t], qkv=qkv, finish=finish,
        head=lambda x: x @ p["head"], mix=mix, alone=alone, hand_on=hand_on)


def dense(p, ids):
    x = p["embed"][jnp.asarray(ids)]
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    for i in range(REPEATS):
        for lp in p["period"]:
            lp = at(lp, i)
            if STATE_MIXER in lp:
                s, states = jnp.zeros(D), []
                for x_t in x:
                    s = lp[STATE_MIXER]["decay"] * s + x_t
                    states.append(s)
                x = x + jnp.stack(states) @ lp[STATE_MIXER]["w"]
            elif PART_ALONE in lp:
                x = x + jnp.tanh(x @ lp[PART_ALONE]["w1"]) @ lp[PART_ALONE]["w2"]
            else:
                a = lp["attn"]
                q, k, v = ((x @ a[n]).reshape(-1, H, DH) for n in ("wq", "wk", "wv"))
                scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(DH)
                scores = jnp.where(jnp.tril(jnp.ones((len(ids), len(ids)), bool))[None], scores, -jnp.inf)
                x = x + jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v).reshape(-1, H * DH) @ a["wo"]
    for i in range(REPEATS):
        lp = at(p["tail"], i)
        x = x + jnp.tanh(x @ lp[PART_ALONE]["w1"]) @ lp[PART_ALONE]["w2"]
    return x @ p["head"]


def fresh_cache():
    cache = transformer.init_paged_kv_pool(REPEATS, H, DH, NB, BS, jnp.float32)  # ONE attention layer a period
    cache[STATE] = jnp.zeros((REPEATS, SLOTS + 1, D))                              # ONE state layer a period
    return cache


@pytest.mark.parametrize("bound", [None, 24], ids=["padded", "compacted"])
def test_a_part_alone_layer_takes_no_state_row_and_no_pool_row(bound):
    """Ten layers (``S A * A`` twice, then ``A A``): two state rows, two pool rows.  Three
    sequences in chunks and then a decode step, each as the dense computation gives it."""
    p = params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    seqs = [list(rng.integers(0, V, n)) for n in (13, 6, 9)]
    tables = np.full((4, 5), NB - 1, np.int32)
    tables[:, -1] = SLOTS
    for i, blocks in enumerate(([0, 1, 2, 3], [4, 5], [6, 7, 8])):
        tables[i, :len(blocks)], tables[i, -1] = blocks, (2, 0, 1)[i]
    cache = fresh_cache()
    assert cache["k"].shape[0] == cache[STATE].shape[0] == REPEATS  # for 10 layers
    fwd = jax.jit(lambda *a: forward(p, *a, bound=bound))

    def step(cache, pieces, starts, t):
        tokens, counts = np.zeros((4, t), np.int32), np.zeros(4, np.int32)
        for i, piece in enumerate(pieces):
            tokens[i, :len(piece)], counts[i] = piece, len(piece)
        return fwd(jnp.asarray(tokens), jnp.asarray(counts), jnp.asarray(starts, jnp.int32), jnp.asarray(tables), cache)

    want = [np.asarray(jax.jit(dense)(p, jnp.asarray(s))) for s in seqs]  # causal: every position's row at once
    _, cache = step(cache, [s[:5] for s in seqs], [0, 0, 0, 0], 8)
    logits, cache = step(cache, [s[5:-1] for s in seqs], [5, 5, 5, 0], 8)
    for i, s in enumerate(seqs):
        if len(s) > 6:
            np.testing.assert_allclose(np.asarray(logits[i, len(s) - 7]), want[i][-2], atol=2e-4)
    logits, after = step(cache, [s[-1:] for s in seqs], [len(s) - 1 for s in seqs] + [0], 1)
    for i, s in enumerate(seqs):
        np.testing.assert_allclose(np.asarray(logits[i, 0]), want[i][-1], atol=2e-4)
    assert after["k"].shape == cache["k"].shape and after[STATE].shape == cache[STATE].shape
    # each state row was written by its own state layer: the two rows of a sequence's slot differ
    assert np.abs(np.asarray(after[STATE][0, 2] - after[STATE][1, 2])).max() > 0.1


def test_a_part_alone_layer_stands_in_the_periods_chain_and_is_refused_without_its_callable():
    p = params(jax.random.PRNGKey(0))
    tokens, tables = jnp.zeros((2, 4), jnp.int32), jnp.asarray([[0, 1, 0], [2, 3, 1]], jnp.int32)
    args = (tokens, jnp.asarray([4, 3]), jnp.zeros(2, jnp.int32), tables, fresh_cache())
    plain, _ = forward(p, *args)
    chained, _, left = forward(p, *args, hand_on=True)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(chained))
    # what a period's chain ended with leaves the scan: two part-alone layers a period, two in the tail's
    assert np.asarray(left[0]).tolist() == [2, 2] and np.asarray(left[1]).tolist() == [1, 1]
    with pytest.raises(ValueError, match="alone"):
        transformer.paged_forward([p["period"]], *args, block_size=BS, live_token_bound=None,
                                  embed=lambda t, pos: p["embed"][t], qkv=None, finish=None, head=None,
                                  mix=lambda *a: (a[1], a[4]))
