"""The plain LongCat-Flash reference: against the installed ``transformers``
``LongcatFlashForCausalLM`` with copied weights (uncut: every real expert
held), the 32 chips' shares plus the identity part against the uncut layer,
its pick counts, and the cell's rehearsal with its fp8 control.  The program
against this reference, chunk by chunk, and each wrong reading of the
architecture: ``tests/unit/inference/test_longcat_flash.py``."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common
from chipbench.references import longcat_flash as ref

HELD, ZERO, TOPK = 2, 32, 6
SIZES = {"hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 2,
         "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "qk_nope_head_dim": 24, "mla_scale_q_lora": True,
         "mla_scale_kv_lora": True, "routed_scaling_factor": 6, "n_routed_experts": HELD,
         "zero_expert_num": ZERO, "moe_topk": TOPK, "rms_norm_eps": 1e-5, "rope_theta": 10000000,
         "vocab_size": 256, "max_position_embeddings": 1024}
REAL = ref.EP_CHIPS * HELD  # the router's real experts: 64, and 32 identity outputs behind them
CONFIG, CELL = "longcat-flash-omni-serve-ep32-4l", "serve.scmoe-decode-wide"


def lin(key, *shape):
    return jax.random.normal(key, shape) * shape[-2] ** -0.5


def uncut_params(seed):
    """The reference's draw with EVERY real expert held (the router stays 64 +
    32 wide), gains off one and a bias that moves picks."""
    params = jax.jit(lambda k: ref.init_params(SIZES, k, jnp.float32))(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 32))
    d, f, depth = SIZES["hidden_size"], SIZES["expert_ffn_hidden_size"], SIZES["num_layers"]
    params["layers"]["moe"]["experts"] = {
        "w_gate": lin(next(keys), depth, REAL, d, f), "w_up": lin(next(keys), depth, REAL, d, f),
        "w_down": lin(next(keys), depth, REAL, f, d)}
    params["layers"]["moe"]["gate"]["bias"] = 0.01 * jax.random.normal(
        next(keys), (depth, REAL + ZERO))
    for sub in ("sub0", "sub1"):
        for name in ("attn_norm", "mlp_norm"):
            gain = params["layers"][sub][name]
            params["layers"][sub][name] = gain + 0.3 * jax.random.normal(next(keys), gain.shape)
        for name in ("q_norm", "kv_norm"):
            gain = params["layers"][sub]["attn"][name]
            params["layers"][sub]["attn"][name] = gain + 0.3 * jax.random.normal(next(keys),
                                                                                 gain.shape)
    return params


def test_reference_imports_nothing_of_the_programs_models_and_sets_highest_precision():
    source = inspect.getsource(ref)
    assert "deepspeed_tpu" not in source.split('"""', 2)[2]
    assert 'jax.default_matmul_precision("highest")' in source
    assert ref.EP_CHIPS == 32 and ref.router_width(SIZES) == REAL + ZERO
    assert ref.lora_scales({"hidden_size": 6144, "q_lora_rank": 1536, "kv_lora_rank": 512}) == (
        2.0, 12 ** 0.5)
    assert ref.lora_scales({"hidden_size": 6144, "q_lora_rank": 1536, "kv_lora_rank": 512,
                            "mla_scale_q_lora": False, "mla_scale_kv_lora": False}) == (1.0, 1.0)


def test_the_reference_equals_transformers_longcat_flash_with_copied_weights():
    """The installed ``modeling_longcat_flash.py`` is the source the issue's
    equations were read from: two sublayers and one shortcut expert layer, the
    two LoRA scales, the 96-wide router with its bias buffer, identity experts."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "LongcatFlashForCausalLM"):
        pytest.skip("the installed transformers has no longcat_flash")
    params = uncut_params(3)
    config = transformers.LongcatFlashConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_hidden_layers=4, num_attention_heads=4,
        max_position_embeddings=1024, rms_norm_eps=1e-5, rope_theta=1e7, ffn_hidden_size=128,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
        head_dim=8, moe_topk=TOPK, n_routed_experts=REAL, zero_expert_num=ZERO,
        expert_ffn_hidden_size=32, routed_scaling_factor=6.0, attn_implementation="eager")
    model = transformers.LongcatFlashForCausalLM(config).eval().to(torch.float32)
    t = lambda a: torch.from_numpy(np.array(a, np.float32))  # a writable copy
    layers = params["layers"]
    state = {"model.embed_tokens.weight": t(params["embed"]), "model.norm.weight":
             t(params["final_norm"]), "lm_head.weight": t(params["lm_head"].T)}
    for l in range(2):
        at = f"model.layers.{l}."
        for i, sub in enumerate(("sub0", "sub1")):
            w = jax.tree_util.tree_map(lambda a: a[l], layers[sub])
            for theirs, ours in (("q_a_proj", "wq_a"), ("q_b_proj", "wq_b"),
                                 ("kv_a_proj_with_mqa", "wkv_a"), ("kv_b_proj", "wkv_b"),
                                 ("o_proj", "wo")):
                state[f"{at}self_attn.{i}.{theirs}.weight"] = t(w["attn"][ours].T)
            state[f"{at}self_attn.{i}.q_a_layernorm.weight"] = t(w["attn"]["q_norm"])
            state[f"{at}self_attn.{i}.kv_a_layernorm.weight"] = t(w["attn"]["kv_norm"])
            state[f"{at}input_layernorm.{i}.weight"] = t(w["attn_norm"])
            state[f"{at}post_attention_layernorm.{i}.weight"] = t(w["mlp_norm"])
            for theirs, ours in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                                 ("down_proj", "w_down")):
                state[f"{at}mlps.{i}.{theirs}.weight"] = t(w["mlp"][ours].T)
                if i == 0:
                    for e in range(REAL):
                        state[f"{at}mlp.experts.{e}.{theirs}.weight"] = t(
                            layers["moe"]["experts"][ours][l, e].T)
        state[f"{at}mlp.router.classifier.weight"] = t(layers["moe"]["gate"]["wg"][l].T)
        state[f"{at}mlp.router.e_score_correction_bias"] = t(layers["moe"]["gate"]["bias"][l])
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and not [k for k in missing if "rotary" not in k], (missing, unexpected)
    ids = np.random.default_rng(0).integers(0, 256, 40)
    with torch.no_grad():
        theirs = model(torch.from_numpy(ids)[None]).logits[0].numpy()
    ours = np.asarray(ref.logits_rows(SIZES, params, ids, list(range(40))))
    assert np.abs(ours - theirs).max() / np.abs(theirs).max() < 2e-5


def test_the_32_shares_and_the_identity_part_once_add_up_to_the_uncut_layer():
    """The guide's share test: the held parts of the 32 shares (each its own 2
    of the 64 real experts, the router over all 96 outputs) plus the identity
    part counted once are the uncut layer's ``s``; through the reference's own
    ``layer_parts`` and through the program's ``sparse_moe_ffn``, which holds
    experts 0..1 of whatever router it is handed."""
    from deepspeed_tpu.moe.serving import sparse_moe_ffn
    params = uncut_params(5)
    moe = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    u = jax.random.normal(jax.random.PRNGKey(6), (24, SIZES["hidden_size"]))
    share = lambda c: jax.tree_util.tree_map(lambda w: w[c * HELD:(c + 1) * HELD], moe["experts"])
    with jax.default_matmul_precision("highest"):
        held, identity, counts = ref.layer_parts(SIZES, moe, u)
        parts = [ref.layer_parts(SIZES, {**moe, "experts": share(c)}, u, chip=c)
                 for c in range(ref.EP_CHIPS)]
        by_reference = sum(p[0] for p in parts) + identity

        def program(c):
            """Chip c sees its own experts first: the real columns rolled by whole shares."""
            roll = lambda a: jnp.concatenate([jnp.roll(a[..., :REAL], -c * HELD, axis=-1),
                                              a[..., REAL:]], axis=-1)
            gate = {"wg": roll(moe["gate"]["wg"]), "bias": roll(moe["gate"]["bias"])}
            return sparse_moe_ffn({"gate": gate, "experts": share(c)}, u, TOPK, False,
                                  scaling=6, identity_experts=ZERO)
        programs = [program(c) for c in range(ref.EP_CHIPS)]
        only_identity = programs[0][0] - parts[0][0]
        by_program = sum(out - only_identity for out, _ in programs) + only_identity
    whole = np.asarray(held + identity)
    assert np.abs(np.asarray(by_reference) - whole).max() < 2e-5 * np.abs(whole).max()
    assert np.abs(np.asarray(by_program) - whole).max() < 2e-5 * np.abs(whole).max()
    np.testing.assert_allclose(np.asarray(only_identity), np.asarray(identity), atol=2e-5)
    # every pick is of exactly one kind: identity (the same on every chip), or one chip's
    every = 24 * TOPK
    assert all(int(p[2][0]) == int(counts[0]) for p in parts)
    assert int(counts[0]) + sum(int(p[2][1]) for p in parts) == every == int(counts.sum())
    assert [np.asarray(t).tolist() for _, t in programs] == [np.asarray(p[2]).tolist()
                                                             for p in parts]
    assert float(jnp.abs(identity).max()) > 0.05 * np.abs(whole).max()  # no part is negligible here


def test_router_is_softmax_over_all_outputs_biased_in_the_choice_alone_and_never_renormalised():
    params = uncut_params(7)
    gate = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["moe"]["gate"])
    u = jax.random.normal(jax.random.PRNGKey(8), (50, SIZES["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        combine, picks = ref.router(SIZES, u, gate)
        probs = np.asarray(jax.nn.softmax(u @ gate["wg"], axis=-1))
    combine, picks, bias = np.asarray(combine), np.asarray(picks), np.asarray(gate["bias"])
    moved = 0
    for s in range(50):
        want = np.argsort(-(probs[s] + bias))[:TOPK]
        assert set(picks[s]) == set(want)
        np.testing.assert_allclose(combine[s, want], 6 * probs[s, want], rtol=1e-6)
        assert np.count_nonzero(combine[s]) == TOPK and combine[s].sum() < 6  # not renormalised
        moved += set(want) != set(np.argsort(-probs[s])[:TOPK])
    assert moved and (picks >= REAL).any() and (picks < REAL).any()


def test_padding_after_the_last_row_changes_nothing():
    params = uncut_params(9)
    ids = np.random.default_rng(1).integers(0, 256, 30).tolist()
    a = np.asarray(ref.logits_rows(SIZES, params, ids, [10, 29]))
    b = np.asarray(ref.logits_rows(SIZES, params, ids + [0] * 34, [10, 29]))
    np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.reads_benchmark
def test_the_configuration_keeps_every_published_width_and_states_the_share():
    spec = common.load_json("configs", CONFIG + ".json")
    published = common.load_json("published", spec["published"] + ".json")["config"]
    assert sorted(spec["reduced"]) == ["n_routed_experts", "num_layers", "vocab_size"]
    assert {k for k in published if spec[k] != published[k]} == set(spec["reduced"])
    assert (spec["num_layers"], spec["n_routed_experts"], spec["vocab_size"]) == (4, 16, 16384)
    assert spec["n_routed_experts"] * ref.EP_CHIPS == published["n_routed_experts"] == 512
    assert 8 * spec["vocab_size"] == published["vocab_size"]
    assert (spec["moe_topk"], spec["zero_expert_num"]) == (12, 256) and spec["entry"] == \
        "serve_sublayers"
    sizes = common.published_sizes(spec, False)
    module, cfg = common.program_model(spec, sizes)
    assert ref.router_width(sizes) == 768
    shapes = jax.eval_shape(lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.bfloat16))
    assert shapes["layers"]["moe"]["gate"]["wg"].shape == (4, 6144, 768)  # the router as published
    assert shapes["layers"]["moe"]["experts"]["w_gate"].shape == (4, 16, 6144, 2048)
    assert shapes["layers"]["sub1"]["mlp"]["w_down"].shape == (4, 12288, 6144)
    assert shapes["layers"]["sub0"]["attn"]["wo"].shape == (4, 8192, 6144)
    # the file's arithmetic: 4 x 1,242.85M + 201.33M (the norms' gains and the routers' biases in)
    assert common.count_params(shapes) == 5_172_749_312
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0))))
    pool = jax.eval_shape(lambda: module.init_paged_cache(cfg, 1024, 128))
    assert pool["latent"].shape == (8, 1024, 1, 128, 640)  # two rows a layer: 10,240 B a token


@pytest.mark.reads_benchmark
def test_the_pool_holds_what_each_wave_asks_for():
    """``test_harness.py``'s three assertions, which select by the entry's name
    ``serve`` and so pass this cell by."""
    from chipbench.generators.waves import Traffic
    spec = common.load_json("configs", CONFIG + ".json")
    mix = common.load_json("traffic", "scmoe-decode-wide.json")
    engine = spec["engine"]
    wave = Traffic(mix["params"], 1, spec["vocab_size"])
    assert len(wave.lengths) == 64 and (min(wave.lengths), max(wave.lengths)) == (131, 509)
    assert sum(wave.lengths) == 20480 and wave.max_new_tokens == 256
    blocks = [-(-(n + wave.max_new_tokens) // engine["block_size"]) for n in wave.lengths]
    assert max(blocks) == 6 <= engine["max_blocks_per_seq"]  # the longest request is 765 tokens
    assert sum(blocks) <= 384 < engine["num_blocks"]  # admitted whole; one block takes the padded writes
    assert engine["max_seqs_per_step"] == 64


def test_the_entry_adds_the_count_of_attention_sublayers_and_runs_serve(monkeypatch):
    import types
    from chipbench.entries import serve, serve_sublayers
    seen = {}
    monkeypatch.setattr(serve, "run", lambda ctx: seen.update(ctx.sizes) or "a serve run")
    ctx = types.SimpleNamespace(sizes={"num_layers": 4, "hidden_size": 6144})
    assert serve_sublayers.run(ctx) == "a serve run"
    assert seen == {"num_layers": 4, "hidden_size": 6144, "num_hidden_layers": 8}


def test_engine_agrees_and_the_fp8_control_does_not(rehearse):
    """The new cell's rehearsal: inside its limits as built, outside them with
    the weights rounded through fp8; all three kinds of pick occur."""
    sound = rehearse("--workload", CELL, "--seed", "11", "--seconds", "0", "--trace", "1")
    control = rehearse("--workload", CELL, "--seed", "11", "--seconds", "0", "--control", "1")
    spec = common.load_json("configs", CONFIG + ".json")
    limit = common.correct_limits(spec, rehearse=True)["logit_rel_rms_limit"]
    assert sound.line["would_be_correct"] is True
    assert control.line["would_be_correct"] is False
    assert sound.number("logit_rel_rms") < limit < control.number("logit_rel_rms")
    assert control.number("logit_rel_rms") > 3 * sound.number("logit_rel_rms")
    picks = {k: sound.number(k) for k in ("moe_identity_picks", "moe_held_picks", "held_elsewhere")}
    assert all(v > 0 for v in picks.values()), picks
    assert 20 < sound.line["metrics"]["zexp.identity_share"]["value"] < 50
    assert 0 < sound.line["metrics"]["scmoe.held_row_fill"]["value"] < 10
