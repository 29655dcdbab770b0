"""Serving-time mixture of experts: the sparse dispatch, the router and the
identity experts against dense oracles of the test's own.  The families through
the engine, the HF door and the programs they lower to are
``test_moe_serving_engine.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.serving import expert_rows, route, sparse_moe_ffn, window_trips

D, F = 32, 16


def dense_oracle(moe, x, top_k, renormalise, live):
    """Every expert over every token, combined with the router's weights at
    each token's picks: what the program's dense formulation computed."""
    probs = jax.nn.softmax(x @ moe["gate"]["wg"], axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, top_k)
    if renormalise:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    combine = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], top_idx].set(top_p)
    ex = moe["experts"]
    every = jnp.einsum("etf,efd->etd", jax.nn.silu(jnp.einsum("td,edf->etf", x, ex["w_gate"]))
                       * jnp.einsum("td,edf->etf", x, ex["w_up"]), ex["w_down"])
    return jnp.einsum("te,etd->td", combine, every) * live[:, None]


def drawn_moe(num_experts, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    moe = {"gate": {"wg": jax.random.normal(ks[0], (D, num_experts)) * D ** -0.5},
           "experts": {"w_gate": jax.random.normal(ks[1], (num_experts, D, F)) * D ** -0.5,
                       "w_up": jax.random.normal(ks[2], (num_experts, D, F)) * D ** -0.5,
                       "w_down": jax.random.normal(ks[3], (num_experts, F, D)) * F ** -0.5}}
    return moe, jax.random.normal(ks[4], (24, D))


def held_oracle(moe, x, top_k, held, live, n_group=1, topk_group=1, bias=None, identity=0):
    """A share's part of the layer, densely: the router over ALL its outputs
    (``route``, which has oracles of its own below), every held expert over
    every token, combined at each live token's picks on them; ``identity``
    outputs at the router's end add ``w x``."""
    weights, picks = route(moe["gate"]["wg"], x, top_k, False, n_group, topk_group, bias=bias)
    wide = moe["gate"]["wg"].shape[-1]
    combine = jnp.zeros((x.shape[0], wide)).at[jnp.arange(x.shape[0])[:, None], picks].set(weights)
    ex = {name: w[:held] for name, w in moe["experts"].items()}
    every = jnp.einsum("etf,efd->etd", jax.nn.silu(jnp.einsum("td,edf->etf", x, ex["w_gate"]))
                       * jnp.einsum("td,edf->etf", x, ex["w_up"]), ex["w_down"])
    out = jnp.einsum("te,etd->td", combine[:, :held], every)
    if identity:
        out = out + combine[:, wide - identity:].sum(-1, keepdims=True) * x
    return out * live[:, None], picks


SHARES = {  # held of routed, top_k, slots, n_group, topk_group, identity outputs, every pick sent here
    "a-quarter-of-grouped-experts": (8, 32, 6, 200, 8, 3, 0, False),
    "a-sixteenth": (4, 64, 8, 300, 1, 1, 0, False),
    "a-thirty-second-and-identity-experts": (2, 64 + 16, 6, 200, 1, 1, 16, False),
    "a-last-tile-part-full": (8, 32, 4, 37, 1, 1, 0, False),
    "every-pick-held-here": (4, 16, 4, 100, 1, 1, 0, True),
}


@pytest.mark.parametrize("interpreted", [False, True], ids=["xla", "interpreted-kernels"])
@pytest.mark.parametrize("share", SHARES)
def test_a_shares_compacted_dispatch_equals_the_dense_oracle(share, interpreted, monkeypatch):
    """One chip's share of an expert-parallel layer: the held picks alone are
    compacted into a window of ``expert_rows(.., held, routed)`` rows a trip,
    sorted, multiplied and added to their tokens; dead slots make holes; no
    pick is dropped when a selection bias sends EVERY pick here (the window
    runs again: the third tally counts the trips beyond the first)."""
    from deepspeed_tpu.ops import _pallas
    held, routed, top_k, slots, n_group, topk_group, identity, all_here = SHARES[share]
    moe, _ = drawn_moe(routed, seed=7)
    x = jax.random.normal(jax.random.PRNGKey(11), (slots, D))
    live = jnp.asarray(np.random.default_rng(2).random(slots) < 0.8)
    bias = jnp.where(jnp.arange(routed) < held, 10.0, 0.0) if all_here else None
    gate = {"wg": moe["gate"]["wg"], **({"bias": bias} if all_here else {})}
    experts = {name: w[:held] for name, w in moe["experts"].items()}
    monkeypatch.setattr(_pallas, "INTERPRET", interpreted)
    with jax.default_matmul_precision("highest"):
        got, tally = jax.jit(lambda m, a: sparse_moe_ffn(
            m, a, top_k, False, live, n_group=n_group, topk_group=topk_group,
            identity_experts=identity))({"gate": gate, "experts": experts}, x)
        monkeypatch.setattr(_pallas, "INTERPRET", False)
        want, picks = held_oracle(moe, x, top_k, held, live, n_group, topk_group, bias, identity)
    assert np.abs(np.asarray(got - want)).max() < 1e-5 * max(1.0, np.abs(np.asarray(want)).max())
    assert (np.asarray(got)[~np.asarray(live)] == 0).all()
    here = int(((np.asarray(picks) < held) & np.asarray(live)[:, None]).sum())
    assert np.asarray(tally).tolist()[1] == here > 0
    # more held picks than a window's rows only where every pick is sent here: then the
    # window ran again (``window_trips``), or the sum above would lack the picks past it
    window = expert_rows(slots, top_k, held, routed)
    assert (window_trips(here, window) > 1) == all_here
    if all_here:
        assert here == int(np.asarray(live).sum()) * top_k


def test_every_routed_expert_held_traces_no_loop_and_every_pick_a_row():
    """A layer whose leaves hold every routed expert is the program it was: no
    ``while``, no compaction, grouped matmuls over ``slots x top_k`` rows."""
    moe, _ = drawn_moe(8)
    x = jax.random.normal(jax.random.PRNGKey(1), (200, D))
    text = jax.jit(lambda m, a: sparse_moe_ffn(m, a, 2, True)).lower(moe, x).as_text()
    assert "stablehlo.while" not in text and f"tensor<{expert_rows(200, 2)}x{D}xf32>" in text
    held = {name: w[:2] for name, w in moe["experts"].items()}
    share = jax.jit(lambda m, a: sparse_moe_ffn(m, a, 2, True)).lower(
        {"gate": moe["gate"], "experts": held}, x).as_text()
    assert expert_rows(200, 2, 2, 8) == 128 < expert_rows(200, 2) == 512
    assert "stablehlo.while" in share and f"tensor<128x{D}xf32>" in share
    assert f"tensor<512x{D}xf32>" not in share


@pytest.mark.parametrize("routing", ["dead_slots", "one_expert_takes_every_token",
                                     "an_expert_takes_none"])
@pytest.mark.parametrize("num_experts,top_k", [(8, 2), (8, 4), (64, 8)])
def test_sparse_dispatch_equals_the_dense_oracle(num_experts, top_k, routing):
    moe, x = drawn_moe(num_experts)
    live = jnp.ones(24, bool)
    if routing == "dead_slots":
        live = jnp.asarray(np.random.default_rng(0).random(24) < 0.6)
    else:
        # a constant direction added to the tokens makes expert 3 every token's
        # first pick, or expert 5 no token's pick
        x = x + 4.0
        column, sign = (3, 1.0) if routing == "one_expert_takes_every_token" else (5, -1.0)
        moe["gate"]["wg"] = moe["gate"]["wg"].at[:, column].set(sign)
    with jax.default_matmul_precision("highest"):
        for renormalise in (False, True):
            _, picks = route(moe["gate"]["wg"], x, top_k, renormalise)
            took = np.bincount(np.asarray(picks)[np.asarray(live)].ravel(), minlength=num_experts)
            if routing == "one_expert_takes_every_token":
                assert took[3] == 24
            elif routing == "an_expert_takes_none":
                assert took[5] == 0
            got = jax.jit(sparse_moe_ffn, static_argnums=(2, 3))(moe, x, top_k, renormalise, live)
            want = dense_oracle(moe, x, top_k, renormalise, live)
            assert np.abs(np.asarray(got - want)).max() < 1e-5 * np.abs(np.asarray(want)).max()
            assert (np.asarray(got)[~np.asarray(live)] == 0).all()


@pytest.mark.parametrize("slots,top_k,rows", [(256, 8, 2048), (32, 8, 256), (16, 8, 128),
                                              (1, 8, 16), (4, 2, 16), (100, 2, 256)])
def test_expert_rows_are_whole_row_tiles(slots, top_k, rows):
    assert expert_rows(slots, top_k) == rows
    assert expert_rows(slots, top_k, 64, 64) == rows  # every routed expert held: every pick a row


@pytest.mark.parametrize("slots,top_k,held,routed,rows", [
    (64, 12, 16, 768, 128), (1024, 12, 16, 768, 384), (2048, 10, 128, 512, 6400),
    (512, 6, 40, 160, 1024), (1024, 8, 16, 256, 640), (1, 8, 16, 256, 16), (8, 10, 128, 512, 80),
    (24, 4, 15, 16, 96)],
    ids=["scmoe-decode", "scmoe-chunk", "gdn-chunk", "mla-chunk", "dsa-chunk", "one-slot",
         "gdn-decode-every-pick-is-fewer", "never-more-than-every-pick"])
def test_a_shares_rows_are_its_part_of_the_picks_with_headroom(slots, top_k, held, routed, rows):
    """The window a share's held picks are compacted into: picks x held / routed
    x HEADROOM in whole row tiles, never more than every pick, at the share
    cells' shapes."""
    assert expert_rows(slots, top_k, held, routed) == rows <= expert_rows(slots, top_k)


def test_interpreted_gmm_kernel_equals_the_xla_path(monkeypatch):
    from deepspeed_tpu.ops import _pallas
    moe, x = drawn_moe(8)
    live = jnp.asarray(np.random.default_rng(1).random(24) < 0.7)
    want = sparse_moe_ffn(moe, x, 4, False, live)
    monkeypatch.setattr(_pallas, "INTERPRET", True)
    got = sparse_moe_ffn(moe, x, 4, False, live)  # 96 picks: one tile of 96 rows
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    few = sparse_moe_ffn(moe, x[:3], 4, False, live[:3])  # 12 picks in a tile of 16
    assert np.abs(np.asarray(few - want[:3])).max() < 1e-5


def test_router_is_float32_whatever_the_activations_are():
    moe, x = drawn_moe(8)
    weights, picks = route(moe["gate"]["wg"].astype(jnp.bfloat16), x.astype(jnp.bfloat16), 4, False)
    assert weights.dtype == jnp.float32 and picks.dtype == jnp.int32
    assert (np.asarray(weights).sum(-1) < 1.0).all()  # not renormalised
    renormalised, _ = route(moe["gate"]["wg"], x, 4, True)
    assert np.allclose(np.asarray(renormalised).sum(-1), 1.0, atol=1e-6)


# --------------------------------------- group-limited routing, a chip's share
def plain_route(wg, x, top_k, renormalise):
    """``route`` as it stood before it learnt of groups (PR 27), to the letter."""
    logits = jnp.dot(x, wg.astype(x.dtype), preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, top_k)
    if renormalise:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_idx.astype(jnp.int32)


@pytest.mark.parametrize("num_experts,top_k,renormalise", [(64, 8, False), (8, 2, True)],
                         ids=["olmoe", "mixtral"])
def test_one_group_and_factor_one_route_bit_for_bit_as_before(num_experts, top_k, renormalise):
    moe, x = drawn_moe(num_experts)
    for dtype in (jnp.float32, jnp.bfloat16):
        got = jax.jit(lambda w, a: route(w, a, top_k, renormalise))(moe["gate"]["wg"], x.astype(dtype))
        want = jax.jit(lambda w, a: plain_route(w, a, top_k, renormalise))(moe["gate"]["wg"],
                                                                          x.astype(dtype))
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    # and the traced program is the same program: no trace of groups or of a factor
    text = lambda f: jax.jit(f).lower(moe["gate"]["wg"], x).as_text()
    assert text(lambda w, a: route(w, a, top_k, renormalise)).replace("route", "") == \
        text(lambda w, a: plain_route(w, a, top_k, renormalise)).replace("plain_route", "").replace(
            "route", "")


@pytest.mark.parametrize("n_group,topk_group,top_k", [(4, 2, 4), (8, 3, 6), (2, 1, 3)])
def test_group_limited_route_is_a_plain_loops(n_group, topk_group, top_k):
    experts = 8 * n_group
    moe, x = drawn_moe(experts, seed=3)
    weights, picks = route(moe["gate"]["wg"], x, top_k, False, n_group, topk_group, 16.0)
    probs = np.asarray(jax.nn.softmax(x @ moe["gate"]["wg"], axis=-1))
    differs = 0
    for s in range(x.shape[0]):
        best = np.argsort(-probs[s].reshape(n_group, -1).max(axis=1))[:topk_group]
        allowed = [e for e in range(experts) if e // (experts // n_group) in best]
        want = sorted(allowed, key=lambda e: -probs[s, e])[:top_k]
        assert list(np.asarray(picks[s])) == want
        np.testing.assert_allclose(np.asarray(weights[s]), 16.0 * probs[s, want], rtol=1e-6)
        differs += set(want) != set(np.argsort(-probs[s])[:top_k])
    assert differs  # the limit binds for some token, or the test shows nothing


def test_a_share_of_the_experts_routes_over_all_and_computes_its_own():
    """A router over 16 with expert leaves of 4: picks on experts 4..15 are dead
    rows (no weight read, zero added); what comes back is the dense oracle's sum
    restricted to experts 0..3, plus the shared expert where there is one."""
    moe, x = drawn_moe(16, seed=2)
    held = {name: w[:4] for name, w in moe["experts"].items()}
    live = jnp.asarray(np.random.default_rng(1).random(24) < 0.7)
    with jax.default_matmul_precision("highest"):
        got = sparse_moe_ffn({"gate": moe["gate"], "experts": held}, x, 4, False, live)
        masked = jax.tree_util.tree_map(lambda w: w.at[4:].set(0.0), moe["experts"])
        want = dense_oracle({"gate": moe["gate"], "experts": masked}, x, 4, False, live)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        _, picks = route(moe["gate"]["wg"], x, 4, False)
        assert (np.asarray(picks) >= 4).any() and (np.asarray(picks) < 4).any()
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        shared = {"w_gate": jax.random.normal(ks[0], (D, F)) * D ** -0.5,
                  "w_up": jax.random.normal(ks[1], (D, F)) * D ** -0.5,
                  "w_down": jax.random.normal(ks[2], (F, D)) * F ** -0.5}
        both = sparse_moe_ffn({"gate": moe["gate"], "experts": held, "shared": shared}, x, 4,
                              False, live)
        dense = (jax.nn.silu(x @ shared["w_gate"]) * (x @ shared["w_up"])) @ shared["w_down"]
        np.testing.assert_allclose(np.asarray(both)[np.asarray(live)],
                                   np.asarray(want + dense)[np.asarray(live)], atol=2e-5)
    # every expert held: the program is the one it was (no compare against the held count)
    text = jax.jit(lambda m, a: sparse_moe_ffn(m, a, 4, False, live)).lower(moe, x).as_text()
    assert text.count("stablehlo.compare") < jax.jit(lambda m, a: sparse_moe_ffn(
        m, a, 4, False, live)).lower({"gate": moe["gate"], "experts": held}, x).as_text().count(
            "stablehlo.compare")


# ------------------------------------------------------- identity experts (ISSUE 49)
def identity_oracle(moe, x, top_k, scaling, real, held, live, bias=None):
    """Softmax over ALL the router's outputs, the top-k of score (+ bias), the
    picked scores times the factor; experts under ``held`` through the dense
    oracle's loop, outputs from ``real`` on the identity, the rest nothing."""
    probs = jax.nn.softmax(x @ moe["gate"]["wg"], axis=-1)
    _, picks = jax.lax.top_k(probs if bias is None else probs + bias, top_k)
    rows = jnp.arange(x.shape[0])[:, None]
    combine = jnp.zeros_like(probs).at[rows, picks].set(probs[rows, picks] * scaling)
    ex = {name: w[:held] for name, w in moe["experts"].items()}
    every = jnp.einsum("etf,efd->etd", jax.nn.silu(jnp.einsum("td,edf->etf", x, ex["w_gate"]))
                       * jnp.einsum("td,edf->etf", x, ex["w_up"]), ex["w_down"])
    out = jnp.einsum("te,etd->td", combine[:, :held], every) \
        + combine[:, real:].sum(-1, keepdims=True) * x
    counts = [int(((picks >= real) & live[:, None]).sum()), int(((picks < held) & live[:, None]).sum())]
    return out * live[:, None], counts, picks


@pytest.mark.parametrize("held", [12, 4], ids=["every_real_expert_held", "a_share_of_the_real_experts"])
@pytest.mark.parametrize("biased", [False, True], ids=["no_bias", "selection_bias"])
def test_identity_experts_add_w_x_and_the_three_kinds_of_pick_are_tallied(held, biased):
    """A router over 12 real + 6 identity outputs: a pick at or past 12 keeps
    its weight and adds ``w x``; a pick under 12 that is not held adds nothing;
    the weights are the softmax over all 18, not renormalised, times the factor;
    the tallies count live slots' picks alone."""
    real, zero, top_k = 12, 6, 5
    moe, x = drawn_moe(real + zero, seed=3)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (real + zero, )) if biased else None
    gate = {"wg": moe["gate"]["wg"], **({"bias": bias} if biased else {})}
    experts = {name: w[:held] for name, w in moe["experts"].items()}
    live = jnp.asarray(np.random.default_rng(1).random(24) < 0.7)
    with jax.default_matmul_precision("highest"):
        got, tally = sparse_moe_ffn({"gate": gate, "experts": experts}, x, top_k, False, live,
                                    scaling=6.0, identity_experts=zero)
        want, counts, picks = identity_oracle(moe, x, top_k, 6.0, real, held, live, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.asarray(tally).tolist() == counts and counts[0] > 0 and counts[1] > 0
    picks = np.asarray(picks)
    assert ((picks >= held) & (picks < real)).any() == (held < real)  # the third kind occurs
    # identity picks read no weight: the grouped matmuls' groups hold the held picks alone
    without = sparse_moe_ffn({"gate": gate, "experts": experts}, x, top_k, False, live, scaling=6.0)
    np.testing.assert_allclose(
        np.asarray(got - without),
        np.asarray(identity_oracle(moe, x, top_k, 6.0, real, 0, live, bias)[0]), atol=2e-5)


def test_no_identity_experts_is_none_and_zero_of_them_is_a_tally_of_nothing():
    moe, x = drawn_moe(8, seed=5)
    plain = sparse_moe_ffn(moe, x, 2, True)
    out, tally = sparse_moe_ffn(moe, x, 2, True, identity_experts=0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    assert np.asarray(tally).tolist() == [0, 24 * 2]  # every pick is on a held expert
    text = jax.jit(lambda m, a: sparse_moe_ffn(m, a, 2, True)).lower(moe, x).as_text()
    assert "moe_identity" not in text


# ---------------------------------------- DeepSeek-V3's grouped sigmoid choice (ISSUE 58)
def parents_route(wg, x, top_k, renormalise, n_group=1, topk_group=1, scaling=1.0,
                  scoring="softmax", bias=None, norm_eps=0.0):
    """``route`` as it stood before the grouped sigmoid choice: DeepSeek-V2's groups by their
    best softmax score, GLM-5's sigmoid with a bias over one group."""
    logits = jnp.dot(x, wg.astype(x.dtype), preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(logits)
    if n_group > 1:
        by_group = probs.reshape(probs.shape[0], n_group, -1)
        _, best = jax.lax.top_k(jnp.max(by_group, axis=-1), topk_group)
        kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
        probs = jnp.where(kept[:, :, None], by_group, 0.0).reshape(probs.shape)
    if bias is None:
        top_p, top_idx = jax.lax.top_k(probs, top_k)
    else:
        _, top_idx = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
        top_p = jnp.take_along_axis(probs, top_idx, axis=-1)
    if renormalise:
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        top_p = top_p / (total + norm_eps if norm_eps else total)
    if scaling != 1.0:
        top_p = top_p * scaling
    return top_p, top_idx.astype(jnp.int32)


@pytest.mark.parametrize("family,args,kwargs", [
    ("deepseek_v2", (6, False, 8, 3, 16.0), {}),
    ("glm_5", (8, True, 1, 1, 2.5), {"scoring": "sigmoid", "biased": True, "norm_eps": 1e-20}),
    ("lfm2", (4, True), {"scoring": "sigmoid", "biased": True})])
def test_the_choices_that_were_there_route_bit_for_bit_as_before(family, args, kwargs):
    moe, x = drawn_moe(64, seed=5)
    kwargs = dict(kwargs)
    if kwargs.pop("biased", False):
        kwargs["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64, ))
    for dtype in (jnp.float32, jnp.bfloat16):
        got = jax.jit(lambda w, a: route(w, a, *args, **kwargs))(moe["gate"]["wg"], x.astype(dtype))
        want = jax.jit(lambda w, a: parents_route(w, a, *args, **kwargs))(moe["gate"]["wg"],
                                                                          x.astype(dtype))
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    text = lambda f: jax.jit(f).lower(moe["gate"]["wg"], x).as_text()
    assert text(lambda w, a: route(w, a, *args, **kwargs)).replace("route", "") == \
        text(lambda w, a: parents_route(w, a, *args, **kwargs)).replace("parents_route", "").replace(
            "route", "")


@pytest.mark.parametrize("n_group,topk_group,top_k,width", [(8, 4, 8, 64), (4, 2, 4, 8), (2, 1, 3, 16)])
def test_grouped_sigmoid_route_is_a_plain_loops(n_group, topk_group, top_k, width):
    """DeepSeek-V3's ``noaux_tc`` by brute force, a token at a time: the groups by the sum of
    their two best BIASED scores, the picks by the biased score among the kept groups' experts
    alone, the weights the picked scores without the bias over their sum, times the factor.
    The bias changes which groups are kept for some token, and the limit binds for some."""
    experts = width * n_group
    moe, x = drawn_moe(experts, seed=3)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (experts, ))
    weights, picks = route(moe["gate"]["wg"], x, top_k, True, n_group, topk_group, 2.5,
                           scoring="sigmoid", bias=bias)
    scores = np.asarray(jax.nn.sigmoid(x @ moe["gate"]["wg"]), np.float64)
    choice = scores + np.asarray(bias, np.float64)
    groups_moved = limit_binds = 0
    for s in range(x.shape[0]):
        two_best = lambda v: np.sort(v.reshape(n_group, width), axis=1)[:, -2:].sum(axis=1)
        best = np.argsort(-two_best(choice[s]))[:topk_group]
        allowed = [e for e in range(experts) if e // width in best]
        want = sorted(allowed, key=lambda e: -choice[s, e])[:top_k]
        assert list(np.asarray(picks[s])) == want
        np.testing.assert_allclose(np.asarray(weights[s]), 2.5 * scores[s, want] / scores[s, want].sum(),
                                   rtol=1e-5)
        groups_moved += set(best) != set(np.argsort(-two_best(scores[s]))[:topk_group])
        limit_binds += set(want) != set(np.argsort(-choice[s])[:top_k])
    assert groups_moved and limit_binds  # or the test shows nothing
    assert picks.dtype == jnp.int32 and weights.dtype == jnp.float32


def test_an_expert_of_a_group_left_out_is_not_picked_whatever_its_bias():
    """The mask is -inf and not zero: with every kept expert's biased score under zero an
    expert of a group that was left out (choice 0 under a zero fill) would be picked."""
    moe, x = drawn_moe(16, seed=4)
    bias = jnp.full((16, ), -2.0).at[:8].add(0.5)  # groups 0 and 1 of four are kept, all choices < 0
    _, picks = route(moe["gate"]["wg"], x, 4, True, 4, 2, 1.0, scoring="sigmoid", bias=bias)
    assert (np.asarray(picks) < 8).all()


# ------------------------------------------- an ungated expert: relu(up)^2, two matrices (ISSUE 62)
def ungated_moe(routed, held, seed=0, slots=41):
    """A router with a selection bias over ``routed`` outputs, ``held`` ungated experts (no
    ``w_gate`` leaf; ``w_up`` ``[E, F, D]`` like ``w_down``) and a shared expert of the same form."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    up = lambda key, *lead: jax.random.normal(key, (*lead, F, D)) * D ** -0.5
    down = lambda key, *lead: jax.random.normal(key, (*lead, F, D)) * F ** -0.5
    return {"gate": {"wg": jax.random.normal(ks[0], (D, routed)) * D ** -0.5,
                     "bias": jax.random.normal(ks[1], (routed, )) * 0.1},
            "experts": {"w_up": up(ks[2], held), "w_down": down(ks[3], held)},
            "shared": {"w_up": up(ks[4]), "w_down": down(ks[5])}}, jax.random.normal(ks[6], (slots, D))


def ungated_loop(moe, x, top_k, live, shared=True):
    """Token by token, pick by pick: ``W_down relu(W_up x)^2`` for a pick on an expert held here,
    nothing for a pick held elsewhere, the shared expert once a token."""
    moe, x = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), (moe, np.asarray(x)))
    weights, picks = route(jnp.asarray(moe["gate"]["wg"], jnp.float32), jnp.asarray(x, jnp.float32), top_k,
                           True, scaling=2.5, scoring="sigmoid", bias=jnp.asarray(moe["gate"]["bias"]),
                           norm_eps=1e-20)
    ffn = lambda w, row: np.square(np.maximum(w["w_up"] @ row, 0.0)) @ w["w_down"]
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        if not live[t]:
            continue
        for w, e in zip(np.asarray(weights[t]), np.asarray(picks[t])):
            if e < moe["experts"]["w_up"].shape[0]:
                out[t] += w * ffn({k: v[e] for k, v in moe["experts"].items()}, x[t])
        if shared:
            out[t] += ffn(moe["shared"], x[t])
    return out


@pytest.mark.parametrize("interpreted", [False, True], ids=["xla", "interpreted-kernels"])
@pytest.mark.parametrize("routed,held,shared", [(8, 8, True), (16, 4, True), (16, 8, False)],
                         ids=["all-held", "a-share", "a-share-no-shared-expert"])
def test_an_ungated_expert_is_the_brute_force_loop(routed, held, shared, interpreted, monkeypatch):
    """No ``w_gate`` leaf: two grouped matmuls and ``relu(.)^2`` between them, for every
    expert held (no loop traced), for a share's compacted window, and for the shared expert;
    the tally counts the live slots' picks on held experts."""
    from deepspeed_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, "INTERPRET", interpreted)
    moe, x = ungated_moe(routed, held, seed=routed + held)
    if not shared:
        del moe["shared"]
    live = np.arange(x.shape[0]) % 5 != 3
    run = jax.jit(lambda m, a, alive: sparse_moe_ffn(m, a, 3, True, alive, scaling=2.5, scoring="sigmoid",
                                                     norm_eps=1e-20, tally=("held", )))
    with jax.default_matmul_precision("highest"):
        got, tally = run(moe, x, jnp.asarray(live))
    want = ungated_loop(moe, x, 3, live, shared)
    np.testing.assert_allclose(np.asarray(got)[live], want[live], atol=3e-5 * np.abs(want).max())
    # a dead slot's routed part is zero; the shared expert runs over every slot, read or not
    assert np.abs(want[live]).max() > 0.1 and (shared or not np.asarray(got)[~live].any())
    _, picks = route(moe["gate"]["wg"], x, 3, True, scoring="sigmoid", bias=moe["gate"]["bias"])
    assert np.asarray(tally).tolist() == [int(((np.asarray(picks) < held) & live[:, None]).sum())]
    if not interpreted:  # (an interpreted kernel is a loop itself)
        text = run.lower(moe, x, jnp.asarray(live)).as_text()
        assert ("while" in text) == (held < routed)  # all held: no window, no loop


def test_a_gated_tree_is_gated_and_an_ungated_tree_is_not_whatever_else_is_passed():
    """The form is the tree's: with a ``w_gate`` leaf beside the same ``w_up`` and ``w_down`` the
    layer is SwiGLU over ``[D, F]`` matrices (three products), and differs."""
    moe, x = ungated_moe(8, 8, seed=3)
    gated = {**moe, "experts": {"w_gate": jnp.swapaxes(moe["experts"]["w_up"], 1, 2),
                                "w_up": jnp.swapaxes(moe["experts"]["w_up"], 1, 2),
                                "w_down": moe["experts"]["w_down"]}}
    del gated["shared"], moe["shared"]
    a, b = (sparse_moe_ffn(m, x, 3, True, scoring="sigmoid") for m in (moe, gated))
    up = np.einsum("td,efd->tef", np.asarray(x), np.asarray(moe["experts"]["w_up"]))
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 0.05 and (up < 0).any()


def test_the_tally_of_experts_named_counts_each_held_expert_once_whatever_names_it():
    moe, x = ungated_moe(16, 8, seed=2)
    live = jnp.asarray(np.arange(x.shape[0]) % 3 != 0)
    _, tally = sparse_moe_ffn(moe, x, 3, True, live, scoring="sigmoid", tally=("held", "experts_hit"))
    _, picks = route(moe["gate"]["wg"], x, 3, True, scoring="sigmoid", bias=moe["gate"]["bias"])
    named = {int(e) for row, alive in zip(np.asarray(picks), np.asarray(live)) if alive for e in row if e < 8}
    assert np.asarray(tally).tolist()[1] == len(named) and 0 < len(named) <= 8
    # the counts come in the order named, and with identity experts and no names the pair it was
    assert np.asarray(sparse_moe_ffn(moe, x, 3, True, live, scoring="sigmoid",
                                     tally=("experts_hit", "held"))[1]).tolist() == np.asarray(tally).tolist()[::-1]
    assert np.asarray(sparse_moe_ffn(moe, x, 3, True, live, scoring="sigmoid", identity_experts=0)[1]).shape == (2, )
