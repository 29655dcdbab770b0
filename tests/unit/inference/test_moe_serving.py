"""Serving-time mixture of experts: the sparse dispatch against a dense oracle
of the test's own, OLMoE through the engine (compaction, counters), and the
HF door (``engine_factory``)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import llama, mixtral, olmoe
from deepspeed_tpu.moe.serving import expert_rows, route, sparse_moe_ffn

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


# ------------------------------------------------------------------ the engine
_KW = dict(num_blocks=64, block_size=8, max_blocks_per_seq=8, token_budget=16,
           max_seqs_per_step=4)
PROMPTS = [list(range(1, 30)), [9, 10, 11], [5, 6, 7, 8, 9, 10]]


def _tiny(module):
    if module is olmoe:
        cfg = olmoe.OlmoeConfig.tiny(vocab=128, hidden=64, layers=2, heads=4, kv_heads=4,
                                     experts=8, top_k=4, seq=128)
    else:
        cfg = mixtral.MixtralConfig.tiny(vocab=128, hidden=64, layers=2, heads=4, kv_heads=2,
                                         experts=4, seq=128)
    return cfg, module.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("module", [mixtral, olmoe], ids=["mixtral", "olmoe"])
def test_moe_families_serve_compacted_with_no_option_and_count_their_rows(module):
    cfg, params = _tiny(module)
    eng = InferenceEngineV2(module, cfg, params, config={"dtype": "float32"}, **_KW)
    assert eng._live_token_bound == 16  # handed to every family: the one paged driver compacts
    got = eng.generate(PROMPTS, max_new_tokens=6)
    padded = InferenceEngineV2(module, cfg, params, config={
        "dtype": "float32", "serving_fastpath": {"enabled": False}}, **_KW)
    assert got == padded.generate(PROMPTS, max_new_tokens=6)
    c = eng.counters
    picks = cfg.top_k * cfg.num_layers
    assert c.compact_passes > 0 and padded.counters.compact_passes == 0
    assert c.moe_routed_rows == c.live_tokens * picks > 0
    # whole row tiles: a pass over 4 slots x top-2 is 8 routed rows in a tile of 16
    assert c.token_slots * picks <= c.moe_expert_rows <= 2 * c.token_slots * picks
    assert module.moe_expert_rows(cfg, 16) == expert_rows(16, cfg.top_k) * cfg.num_layers
    assert set(eng.counters.delta_since(eng.counters.snapshot())) >= {"moe_routed_rows",
                                                                     "moe_expert_rows"}


def test_a_dense_model_routes_no_rows():
    cfg = llama.LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4, kv_heads=2, seq=128)
    eng = InferenceEngineV2(llama, cfg, llama.init_params(cfg, jax.random.PRNGKey(0)),
                            config={"dtype": "float32"}, **_KW)
    eng.generate(PROMPTS, max_new_tokens=4)
    assert eng.counters.token_slots > 0
    assert eng.counters.moe_routed_rows == eng.counters.moe_expert_rows == 0


def test_mixtral_paged_forward_has_no_layer_loop_and_the_dense_ffn_is_gone():
    import inspect
    source = inspect.getsource(mixtral.forward_paged)
    assert "lax.scan" not in source and "transformer.paged_forward(" in source
    assert not hasattr(mixtral, "dense_moe_ffn")
    assert olmoe.forward_paged is mixtral.forward_paged


def test_olmoe_training_is_refused():
    cfg, params = _tiny(olmoe)
    with pytest.raises(ValueError, match="k=1 or k=2"):
        mixtral.forward(cfg, params, jnp.zeros((1, 8), jnp.int32))


# --------------------------------------------------------------- the HF door
def _hf_olmoe(cfg):
    """A synthetic ``OlmoeForCausalLM``: HF names, torch layout [out, in]."""
    rng = np.random.default_rng(0)
    d, f = cfg.hidden_size, cfg.intermediate_size
    sd = {"model.embed_tokens.weight": rng.normal(size=(cfg.vocab_size, d)),
          "model.norm.weight": rng.normal(size=(d,)),
          "lm_head.weight": rng.normal(size=(cfg.vocab_size, d))}
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            sd[pre + f"self_attn.{name}.weight"] = rng.normal(size=(d, d))
        for name in ("q_norm", "k_norm"):
            sd[pre + f"self_attn.{name}.weight"] = rng.normal(size=(d,))
        sd[pre + "input_layernorm.weight"] = rng.normal(size=(d,))
        sd[pre + "post_attention_layernorm.weight"] = rng.normal(size=(d,))
        sd[pre + "mlp.gate.weight"] = rng.normal(size=(cfg.num_experts, d))
        for e in range(cfg.num_experts):
            sd[pre + f"mlp.experts.{e}.gate_proj.weight"] = rng.normal(size=(f, d))
            sd[pre + f"mlp.experts.{e}.up_proj.weight"] = rng.normal(size=(f, d))
            sd[pre + f"mlp.experts.{e}.down_proj.weight"] = rng.normal(size=(d, f))
    sd = {k: v.astype(np.float32) for k, v in sd.items()}
    hf_config = types.SimpleNamespace(
        model_type="olmoe", vocab_size=cfg.vocab_size, hidden_size=d, intermediate_size=f,
        num_hidden_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.top_k, max_position_embeddings=cfg.max_seq_len,
        rope_theta=10000.0, rms_norm_eps=1e-5, norm_topk_prob=False, clip_qkv=None)
    return types.SimpleNamespace(config=hf_config, state_dict=lambda: sd), sd


def test_olmoe_state_dict_loads_into_the_layout_the_reference_draws():
    from chipbench.references import olmoe as ref
    cfg = olmoe.OlmoeConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=4,
                                 experts=4, top_k=2, seq=64)
    hf_model, sd = _hf_olmoe(cfg)
    params = olmoe.from_hf_state_dict(cfg, sd)
    sizes = {"hidden_size": 32, "intermediate_size": 16, "num_attention_heads": 4,
             "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 64, "num_experts": 4}
    drawn = jax.eval_shape(lambda k: ref.init_params(sizes, k, jnp.float32), jax.random.PRNGKey(0))
    shapes = lambda tree: jax.tree_util.tree_map(lambda a: a.shape, tree)
    assert shapes(params) == shapes(drawn) == shapes(olmoe.init_params(cfg, jax.random.PRNGKey(0)))
    layers = params["layers"]
    assert np.array_equal(layers["moe"]["experts"]["w_down"][1, 3],
                          sd["model.layers.1.mlp.experts.3.down_proj.weight"].T)
    assert np.array_equal(layers["moe"]["gate"]["wg"][0], sd["model.layers.0.mlp.gate.weight"].T)
    assert np.array_equal(layers["attn"]["k_norm"][1], sd["model.layers.1.self_attn.k_norm.weight"])

    # the registry resolves model_type olmoe, and the engine it builds serves
    from deepspeed_tpu.inference.v2.engine_factory import build_hf_engine
    eng = build_hf_engine(hf_model, config={"dtype": "float32"}, num_blocks=16, block_size=8,
                          max_blocks_per_seq=4)
    assert eng.model is olmoe and eng.model_config == cfg
    assert len(eng.generate([[1, 2, 3]], max_new_tokens=2)[0]) == 5
    hf_model.config.clip_qkv = 8.0
    with pytest.raises(ValueError, match="clip_qkv"):
        olmoe.config_from_hf(hf_model.config)


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


def test_deepseek_v2_through_the_engine_counts_every_pick_and_compacts():
    from deepspeed_tpu.models import deepseek_v2
    cfg = deepseek_v2.DeepseekV2Config.tiny(local_experts=4)
    params = deepseek_v2.init_params(cfg, jax.random.PRNGKey(0))
    assert params["layers"]["moe"]["gate"]["wg"].shape == (2, 128, 16)
    assert params["layers"]["moe"]["experts"]["w_gate"].shape == (2, 4, 128, 64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).tolist() for n in (40, 7, 19)]
    outs = {}
    for name, conf in (("fast", {"dtype": "float32"}),
                       ("padded", {"dtype": "float32", "serving_fastpath": {"enabled": False}})):
        eng = InferenceEngineV2(deepseek_v2, cfg, params, config=conf, block_size=8,
                                num_blocks=40, max_blocks_per_seq=8, token_budget=16)
        outs[name] = [r.tokens for r in eng.generate(prompts, max_new_tokens=5, strict=False)]
        if name == "fast":
            c = eng.counters.snapshot()
            assert c["compact_passes"] > 0
            # all picks, held or not: live tokens x top-4 x the two expert layers
            assert c["moe_routed_rows"] == c["live_tokens"] * 4 * 2
            assert c["moe_expert_rows"] >= c["moe_routed_rows"]
            assert [leaf.shape for leaf in jax.tree_util.tree_leaves(eng.kv)] == [(3, 40, 1, 8, 128)]
            eng.check_kv_invariant()
    assert outs["fast"] == outs["padded"]


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


# What six families' step programs lowered to at the parent of ISSUE 49 (sha256 of
# ``jit(forward_paged).lower(...).as_text()``, first 16 digits): a hand-on inside a
# period, identity experts and a tally leaf are traced for the family that has them
# and for no other.  Whoever changes ``paged_forward`` or ``sparse_moe_ffn`` on
# purpose re-pins these from the new tree and says in PERF.md that every cell's
# programs, and with them ``setup_s``, are compiled anew.
PROGRAMS_BEFORE = {"olmoe_decode": "be129bc1388a5808", "olmoe_compacted": "1157e790add8daad",
                   "deepseek_v2_share_compacted": "f9f66894abb41f67",
                   "glm_moe_dsa_share_padded": "68d732d5a1164316",
                   "lfm2_period_compacted": "6dd10a4bfc4f41c2", "llama_decode": "c6d24d39c1fabc19"}


@pytest.mark.parametrize("case", sorted(PROGRAMS_BEFORE))
def test_a_family_without_a_hand_on_or_identity_experts_lowers_to_the_program_it_was(case):
    import hashlib
    from deepspeed_tpu.models import deepseek_v2, glm_moe_dsa, lfm2
    module, cfg, cache_kw, n, t, b, bound = {
        "olmoe_decode": (olmoe, olmoe.OlmoeConfig.tiny(), {}, 4, 1, 4, 32),
        "olmoe_compacted": (olmoe, olmoe.OlmoeConfig.tiny(), {}, 4, 16, 4, 32),
        "deepseek_v2_share_compacted": (deepseek_v2, deepseek_v2.DeepseekV2Config.tiny(
            local_experts=4), {}, 4, 16, 4, 32),
        "glm_moe_dsa_share_padded": (glm_moe_dsa, glm_moe_dsa.GlmMoeDsaConfig.tiny(
            local_experts=1), {}, 2, 16, 4, None),
        "lfm2_period_compacted": (lfm2, lfm2.Lfm2Config.tiny(), {"state_slots": 5}, 4, 16, 5, 32),
        "llama_decode": (llama, llama.LlamaConfig.tiny(), {}, 4, 1, 4, 32)}[case]
    params = jax.eval_shape(lambda: module.init_params(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: module.init_paged_cache(cfg, 16, 8, dtype=jnp.float32, **cache_kw))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = jax.jit(lambda p, kv, tok, nt, sp, tab: module.forward_paged(
        cfg, p, tok, nt, sp, tab, kv, block_size=8, live_token_bound=bound, last_rows=True)).lower(
            params, kv, ints(n, t), ints(n), ints(n), ints(n, b)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PROGRAMS_BEFORE[case]
