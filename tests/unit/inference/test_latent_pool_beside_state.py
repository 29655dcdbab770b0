"""The cache combination by itself (ISSUE 58): ONE period of a layer that attends a
LATENT pool (``value_dim``: the value inside the one cached vector) and a layer
whose memory is a state leaf handed BY REFERENCE, through
``transformer.paged_forward`` in one scan, padded and compacted.  A toy family of
the test's own, so that what the contract's docstring says of the two caches
together (the pool's row counted over the attention layers alone, the state's over
the mixers alone; a ``StateRef`` in ``carried``, the new flat leaf back; a shift by
value beside it) is held for the next family and not by ``bailing_hybrid`` alone.

The toy: ``x = E[token]``; twice (attention, mixer).  Attention: ``q_h = x W_q``
against the cached vector ``l = x W_l`` itself, causal softmax, the value ``l[:DV]``.
Mixer: ``s <- 0.9 s + x_t``, ``y_t = s W_m`` with ``s`` ``[D]`` float32 a sequence
(by reference), plus ``0.5 x_{t-1}`` from a shift of one row (by value)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import transformer
from deepspeed_tpu.models.transformer import STATE, STATE_MIXER, StateRef

V, D, H, WIDTH, DV, DEPTH = 64, 16, 2, 128, 32, 2
NB, BS, MAXB, SLOTS = 24, 4, 12, 3
SCALE = 0.3


@pytest.fixture(scope="module")
def params():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    draw = lambda key, *shape: jax.random.normal(key, shape) * shape[-2] ** -0.5
    return {"embed": jax.random.normal(ks[0], (V, D)),
            "attn": {"wq": draw(ks[1], DEPTH, D, H * WIDTH), "wl": draw(ks[2], DEPTH, D, WIDTH),
                     "wo": draw(ks[3], DEPTH, H * DV, D)},
            "mix": {STATE_MIXER: {"wm": draw(ks[4], DEPTH, D, D)}},
            "head": draw(ks[5], D, V)}


def fresh_cache():
    return {"latent": jnp.zeros((DEPTH, NB, 1, BS, WIDTH)),
            STATE: {"shift": jnp.zeros((DEPTH, SLOTS + 1, 1, D)),
                    "sum": jnp.full((DEPTH, SLOTS + 1, D), 7.0)}}  # a slot is never zeroed: sevens


def forward(params, tokens, n_tokens, start_pos, tables, cache, *, bound):
    def mix(lp, x, filtered, live, carried, places):
        ref = carried["sum"]
        assert isinstance(ref, StateRef) and not isinstance(carried["shift"], StateRef)
        assert ref.leaf.shape == (DEPTH * (SLOTS + 1), D)  # the leaf whole and flat
        # a filter of two taps that weighs the token before at one and the token itself at nought
        before, last = filtered(x, carried["shift"], jnp.stack([jnp.ones(D), jnp.zeros(D)]))
        n = places.n_tokens.shape[0]
        # the toy's "kernel": the rows' slots read, the recurrence a row at a time, written back
        s0 = jnp.where(ref.begins[:, None], 0.0, ref.leaf[ref.at])
        if places.row is None:
            rows, held = x, jnp.arange(x.shape[1])[None, :] < places.n_tokens[:, None]
        else:  # the flat tokens onto rows, for the toy's own scan
            rows = jnp.zeros((n, x.shape[1], D)).at[places.row[0], places.col[0]].add(
                jnp.where(live[0][:, None], x[0], 0.0))
            held = jnp.arange(x.shape[1])[None, :] < places.n_tokens[:, None]

        def token(s, inp):
            x_t, on = inp
            s = jnp.where(on[:, None], 0.9 * s + x_t, s)
            return s, s

        s1, ys = jax.lax.scan(token, s0, (jnp.moveaxis(rows, 1, 0), held.T))
        ys = jnp.moveaxis(ys, 0, 1)
        y = ys if places.row is None else ys[places.row[0], places.col[0]][None]
        at = jnp.where(places.n_tokens > 0, ref.at, ref.trash)
        out = x + y @ lp[STATE_MIXER]["wm"] + 0.5 * before
        return out, {"shift": last, "sum": ref.leaf.at[at].set(s1)}

    def qkv(lp, x, safe_pos):
        return ((x @ lp["wq"]).reshape(x.shape[:2] + (H, WIDTH)), (x @ lp["wl"])[:, :, None, :], None)

    def finish(lp, x, kept, attn, live):
        assert attn.shape[-2:] == (H, DV)  # the value is the cached vector's leading columns
        return x + attn.reshape(x.shape[:2] + (H * DV, )) @ lp["wo"]

    return transformer.paged_forward(
        [(params["attn"], params["mix"])], tokens, n_tokens, start_pos, tables, cache, block_size=BS,
        live_token_bound=bound, embed=lambda tokens, pos: params["embed"][tokens], qkv=qkv,
        finish=finish, head=lambda x: x @ params["head"], mix=mix,
        by_reference={"shift": False, "sum": True}, softmax_scale=SCALE, value_dim=DV)


FORWARD = jax.jit(forward, static_argnames=("bound", ))


def oracle(params, ids):
    """The last token's logits of one whole sequence, densely."""
    x = np.asarray(params["embed"])[ids].astype(np.float64)
    causal = np.tril(np.ones((len(ids), len(ids)), bool))
    for layer in range(DEPTH):
        a = jax.tree_util.tree_map(lambda w: np.asarray(w[layer], np.float64), params["attn"])
        q, lat = (x @ a["wq"]).reshape(len(ids), H, WIDTH), x @ a["wl"]
        scores = np.where(causal[None], np.einsum("ihw,jw->hij", q, lat) * SCALE, -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        x = x + np.einsum("hij,jv->ihv", probs, lat[:, :DV]).reshape(len(ids), H * DV) @ a["wo"]
        s, ys = np.zeros(D), []
        for x_t in x:
            s = 0.9 * s + x_t
            ys.append(s)
        before = np.concatenate([np.zeros((1, D)), x[:-1]])
        x = x + np.stack(ys) @ np.asarray(params["mix"][STATE_MIXER]["wm"][layer], np.float64) + 0.5 * before
    return x[-1] @ np.asarray(params["head"], np.float64)


def step(params, cache, rows, t, bound=None):
    n = 1 << (len(rows) - 1).bit_length()
    tokens, counts = np.zeros((n, t), np.int32), np.zeros(n, np.int32)
    starts, tables = np.zeros(n, np.int32), np.full((n, MAXB + 1), NB - 1, np.int32)
    tables[:, -1] = SLOTS
    for i, (toks, start, blocks, slot) in enumerate(rows):
        tokens[i, :len(toks)], counts[i], starts[i] = toks, len(toks), start
        tables[i, :len(blocks)], tables[i, -1] = blocks, slot
    logits, cache = FORWARD(params, jnp.asarray(tokens), jnp.asarray(counts), jnp.asarray(starts),
                            jnp.asarray(tables), cache, bound=bound)
    return [np.asarray(logits[i, len(r[0]) - 1]) for i, r in enumerate(rows)], cache


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(0, V, n).tolist()


def close(got, want):
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max(), rtol=0)


@pytest.mark.parametrize("pieces", [(30, ), (9, 1, 20)], ids=["whole", "in-three-passes"])
def test_a_sequence_through_both_caches_is_the_dense_oracle(params, pieces):
    """Padded passes: each continues from the latents its blocks hold AND from the sum and
    the shift its slot holds, in both layers of either kind; the slot it begins over holds
    sevens and is not read."""
    ids, at, cache = ids_of(1, sum(pieces)), 0, fresh_cache()
    for piece in pieces:
        (got, ), cache = step(params, cache, [(ids[at:at + piece], at, list(range(2, 12)), 1)], t=32)
        at += piece
        close(got, oracle(params, ids[:at]))
    sums = np.asarray(cache[STATE]["sum"])
    assert (sums[:, (0, 2)] == 7.0).all() and not (sums[:, 1] == 7.0).any()  # its slot alone, in both layers
    latents = np.asarray(cache["latent"])
    assert np.abs(latents[:, 2:10]).max(axis=(1, 2, 3, 4)).min() > 0  # both attention layers' rows of the pool
    assert (latents[:, 12:NB - 1] == 0).all()


def test_a_compacted_pass_of_three_sequences_is_the_padded_pass_and_the_oracle(params):
    """Three rows (a continued prompt, a decode row, a prompt that begins) and a dead row on
    the flat axis: each reads what it reads alone and what the oracle gives; pool and state
    of one sequence reach no other; the slot and the blocks no row names are untouched."""
    seqs = [(ids_of(2, 26), list(range(0, 7)), 2), (ids_of(3, 9), [7, 8, 9], 0), (ids_of(4, 5), [10, 11], 1)]
    heads = (11, 8, 0)
    cache = fresh_cache()
    for (ids, blocks, slot), done in zip(seqs, heads):
        if done:
            _, cache = step(params, cache, [(ids[:done], 0, blocks, slot)], t=32)
    rows = [(ids[done:], done, blocks, slot) for (ids, blocks, slot), done in zip(seqs, heads)]
    mixed, after = step(params, cache, rows, t=32, bound=24)  # [4, 32] slots > 24: compacted
    padded, oracle_cache = step(params, cache, rows, t=32)
    for i, (ids, _, slot) in enumerate(seqs):
        close(mixed[i], padded[i])
        close(mixed[i], oracle(params, ids))
        for leaf in ("shift", "sum"):
            close(np.asarray(after[STATE][leaf][:, slot]), np.asarray(oracle_cache[STATE][leaf][:, slot]))
    live_blocks = sorted(b for _, blocks, _ in seqs for b in blocks)
    np.testing.assert_allclose(np.asarray(after["latent"][:, live_blocks]),
                               np.asarray(oracle_cache["latent"][:, live_blocks]), atol=1e-5)
    assert (np.asarray(after["latent"][:, 12:NB - 1]) == 0).all()


def test_without_by_reference_the_same_leaf_comes_by_value(params):
    """The family's statement decides: left out, ``carried["sum"]`` is the rows' slots by
    value (zeros where a sequence begins) and no ``StateRef``."""
    seen = {}

    def mix(lp, x, filtered, live, carried, places):
        seen.update(carried)
        return x, carried

    tables = np.full((2, MAXB + 1), NB - 1, np.int32)
    tables[:, -1] = (1, SLOTS)
    transformer.paged_forward(
        [(params["attn"], params["mix"])], jnp.zeros((2, 4), jnp.int32), jnp.asarray([4, 0]),
        jnp.asarray([0, 0]), jnp.asarray(tables), fresh_cache(), block_size=BS, live_token_bound=None,
        embed=lambda tokens, pos: params["embed"][tokens],
        qkv=lambda lp, x, pos: ((x @ lp["wq"]).reshape(x.shape[:2] + (H, WIDTH)),
                                (x @ lp["wl"])[:, :, None, :], None),
        finish=lambda lp, x, kept, attn, live: x, head=lambda x: x, mix=mix, softmax_scale=SCALE,
        value_dim=DV)
    assert seen["sum"].shape == (2, D) and seen["shift"].shape == (2, 1, D)
