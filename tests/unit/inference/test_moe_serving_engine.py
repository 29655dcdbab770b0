"""Serving-time mixture of experts through the engine (compaction, counters),
the HF door (``engine_factory``) and the step programs the families lower to;
split from ``test_moe_serving.py``, which holds the dispatch itself."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import llama, mixtral, olmoe
from deepspeed_tpu.moe.serving import expert_rows
from tests.unit.inference.scenario import launches_of


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


def test_deepseek_v2_through_the_engine_counts_every_pick_and_compacts(monkeypatch):
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
        launched = launches_of(eng, monkeypatch)
        outs[name] = [r.tokens for r in eng.generate(prompts, max_new_tokens=5, strict=False)]
        if name == "fast":
            c = eng.counters.snapshot()
            assert c["compact_passes"] > 0
            # all picks, held or not: live tokens x top-4 x the two expert layers
            assert c["moe_routed_rows"] == c["live_tokens"] * 4 * 2
            # the rows are the share's window of held picks (4 of 16 experts, with headroom,
            # in whole row tiles) in the two expert layers, a pass: a quarter of a large
            # pass's picks, every pick of one as small as this engine's
            assert deepseek_v2.moe_expert_rows(cfg, 512) == expert_rows(512, 4, 4, 16) * 2 == 640 * 2
            assert deepseek_v2.moe_expert_rows(cfg, 16) == expert_rows(16, 4, 4, 16) * 2 == 64 * 2
            assert c["moe_expert_rows"] == sum(
                deepseek_v2.moe_expert_rows(cfg, slots) * passes for slots, passes in launched) > 0
            assert [leaf.shape for leaf in jax.tree_util.tree_leaves(eng.kv)] == [(3, 40, 1, 8, 128)]
            eng.check_kv_invariant()
    assert outs["fast"] == outs["padded"]


# What six families' step programs lowered to at the parent of ISSUE 49 (sha256 of
# ``jit(forward_paged).lower(...).as_text()``, first 16 digits): a hand-on inside a
# period, identity experts and a tally leaf are traced for the family that has them
# and for no other.  The two shares were re-pinned in ISSUE 51, which compacts a
# share's held picks (the four others, whose leaves hold every routed expert or
# none, lowered to what they were); LFM2's in ISSUE 59, which hands a mixer its filter and no
# shifted copies (the five others, whose families have no shift, lowered to what they were).
# Whoever changes ``paged_forward`` or ``sparse_moe_ffn`` on
# purpose re-pins these from the new tree and says in PERF.md that every cell's
# programs, and with them ``setup_s``, are compiled anew.
PROGRAMS_BEFORE = {"olmoe_decode": "be129bc1388a5808", "olmoe_compacted": "1157e790add8daad",
                   "deepseek_v2_share_compacted": "d59248c8f6384b9a",
                   "glm_moe_dsa_share_padded": "70fa7bbde87e1038",
                   "lfm2_period_compacted": "b0c7b817dc68f0ed", "llama_decode": "c6d24d39c1fabc19"}


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
    text = lowered_step(module, cfg, cache_kw, n, t, b, bound)[0]
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PROGRAMS_BEFORE[case]


def lowered_step(module, cfg, cache_kw, n, t, b, bound):
    """``(text, cache)``: a family's step program over ``[n, t]`` as it lowers, from shapes alone."""
    params = jax.eval_shape(lambda: module.init_params(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: module.init_paged_cache(cfg, 16, 8, dtype=jnp.float32, **cache_kw))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return jax.jit(lambda p, kv, tok, nt, sp, tab: module.forward_paged(
        cfg, p, tok, nt, sp, tab, kv, block_size=8, live_token_bound=bound, last_rows=True)).lower(
            params, kv, ints(n, t), ints(n), ints(n), ints(n, b)).as_text(), kv


@pytest.mark.parametrize("family, config, sizes", [
    ("bailing_hybrid", "BailingHybridConfig", {}),
    ("qwen3_next", "Qwen3NextConfig", {"linear_dim": 16}),  # 128 columns: not the stream's 64
    ("granite_moe_hybrid", "GraniteMoeHybridConfig", {})], ids=["bailing_hybrid", "qwen3_next", "granite_moe_hybrid"])
def test_a_compacted_pass_makes_no_shifted_copy_of_a_mixers_columns(family, config, sizes):
    """ISSUE 59: the short filter reads the flat chunk itself; no gather from the rows' kept
    values and no pad a tap over the pass's ``[S, D]`` columns is left in the program."""
    import importlib
    import re
    from deepspeed_tpu.models.transformer import STATE, flat_slots
    module = importlib.import_module(f"deepspeed_tpu.models.{family}")
    cfg = getattr(module, config).tiny(**sizes)
    text, kv = lowered_step(module, cfg, {"state_slots": 5}, 4, 16, 5, 32)
    s, (k, d) = flat_slots(4, 16, 32), kv[STATE]["conv"].shape[-2:]
    assert s == 32 and d != cfg.hidden_size  # compacted, and the filter's columns told from the stream's
    results = [line.rsplit("->", 1)[1] for line in text.splitlines()
               if re.search(r"stablehlo\.(gather|pad)\b", line)]
    assert results and not [r for r in results if re.search(rf"tensor<(1x)?{s}x{d}x", r)], results
    # the filter's own pad is there: the chunk with ``k`` rows in front, sliced a tap
    assert any(f"tensor<{s + k}x{d}x" in r for r in results)
