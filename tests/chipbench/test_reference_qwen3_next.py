"""The plain Qwen3-Next reference against its written source.

The whole model against ``transformers``' ``Qwen3NextForCausalLM`` (torch on the
CPU, the same weights, a small size with both layer kinds, 8 experts top-4 and
the gated shared expert) in float32; the token-by-token delta rule against HF's
``torch_chunk_gated_delta_rule``; and the share: the four chips' routed parts
plus the gated shared expert counted once are the uncut layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import qwen3_next as ref

SIZES = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 16, "hidden_act": "silu",
         "hidden_size": 64, "intermediate_size": 128, "linear_conv_kernel_dim": 4,
         "linear_key_head_dim": 8, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
         "linear_value_head_dim": 8, "max_position_embeddings": 512, "mlp_only_layers": [],
         "model_type": "qwen3_next", "moe_intermediate_size": 32, "norm_topk_prob": True,
         "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 4,
         "num_hidden_layers": 8, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
         "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 10000000,
         "shared_expert_intermediate_size": 32, "tie_word_embeddings": False,
         "use_sliding_window": False, "vocab_size": 256}
S = 75  # tokens of the one sequence: more than one of HF's chunks of 64
TOL = 2e-5


@pytest.fixture
def uncut(monkeypatch):
    """The reference as the whole model: one chip holds every expert."""
    monkeypatch.setattr(ref, "EP_CHIPS", 1)


def drawn(sizes, seed=3):
    params = ref.init_params(sizes, jax.random.PRNGKey(seed), jnp.float32)
    # gains off their neutral value, or a gain of the wrong kind (1 + w against w) or laid out
    # wrongly would change nothing; decays of every kind
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def off_neutral(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        if any(n in ("op_norm", "ffn_norm", "q_norm", "k_norm", "final_norm", "norm", "dt_bias")
               for n in names):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(off_neutral, params)


def to_hf_columns(sizes):
    """Where HF's grouped ``in_proj_qkvz`` / ``in_proj_ba`` rows lie among the
    reference's ``[q | k | v | z]`` / ``[b | a]`` columns (the one departure in
    layout): key head j holds ``[q_j | k_j | v_2j v_2j+1 | z_2j z_2j+1]``."""
    hk, hv, dk, dv, key_dim, value_dim = ref.gdn_widths(sizes)
    per = hv // hk
    qkvz, ba = [], []
    for j in range(hk):
        qkvz += list(range(j * dk, (j + 1) * dk))
        qkvz += list(range(key_dim + j * dk, key_dim + (j + 1) * dk))
        qkvz += list(range(2 * key_dim + j * per * dv, 2 * key_dim + (j + 1) * per * dv))
        qkvz += list(range(2 * key_dim + value_dim + j * per * dv,
                           2 * key_dim + value_dim + (j + 1) * per * dv))
        ba += list(range(j * per, (j + 1) * per)) + list(range(hv + j * per, hv + (j + 1) * per))
    return np.asarray(qkvz), np.asarray(ba)


def hf_model(sizes, params):
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.qwen3_next.modeling_qwen3_next")
    from transformers.models.qwen3_next.configuration_qwen3_next import Qwen3NextConfig
    keys = {k: v for k, v in sizes.items() if k not in ("model_type", "full_attention_interval")}
    cfg = Qwen3NextConfig(**keys, layer_types=ref.layer_kinds(sizes), attention_bias=False)
    cfg._attn_implementation = "eager"
    model = hf.Qwen3NextForCausalLM(cfg).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    qkvz_cols, ba_cols = to_hf_columns(sizes)
    state = {"model.embed_tokens.weight": t(params["embed"]), "model.norm.weight": t(params["final_norm"]),
             "lm_head.weight": t(params["lm_head"].T)}
    layer = 0
    for (start, period, repeats), run in zip(ref.segments(sizes), params["segments"]):
        for i in range(repeats):
            for stack in run:
                w = jax.tree_util.tree_map(lambda a, i=i: np.asarray(a[i]), stack)
                p = f"model.layers.{layer}."
                state[p + "input_layernorm.weight"] = t(w["op_norm"])
                state[p + "post_attention_layernorm.weight"] = t(w["ffn_norm"])
                if "mixer" in w:
                    m = w["mixer"]
                    state.update({
                        p + "linear_attn.in_proj_qkvz.weight": t(m["w_qkvz"][:, qkvz_cols].T),
                        p + "linear_attn.in_proj_ba.weight": t(m["w_ba"][:, ba_cols].T),
                        # torch's filter is [channel, 1, tap]; tap j weighs the value 3 - j before
                        p + "linear_attn.conv1d.weight": t(m["filter"].T[:, None, :]),
                        p + "linear_attn.A_log": t(m["A_log"]), p + "linear_attn.dt_bias": t(m["dt_bias"]),
                        p + "linear_attn.norm.weight": t(m["norm"]),
                        p + "linear_attn.out_proj.weight": t(m["w_out"].T)})
                else:
                    a = w["attn"]
                    state.update({p + "self_attn.q_proj.weight": t(a["wq"].T),
                                  p + "self_attn.k_proj.weight": t(a["wk"].T),
                                  p + "self_attn.v_proj.weight": t(a["wv"].T),
                                  p + "self_attn.o_proj.weight": t(a["wo"].T),
                                  p + "self_attn.q_norm.weight": t(a["q_norm"]),
                                  p + "self_attn.k_norm.weight": t(a["k_norm"])})
                moe = w["moe"]
                state[p + "mlp.gate.weight"] = t(moe["gate"]["wg"].T)
                state[p + "mlp.shared_expert_gate.weight"] = t(moe["shared_gate"].T)
                for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
                    state[p + f"mlp.shared_expert.{theirs}.weight"] = t(moe["shared"][ours].T)
                    for e in range(sizes["num_experts"]):
                        state[p + f"mlp.experts.{e}.{theirs}.weight"] = t(
                            np.asarray(params["experts"][ours][layer, e]).T)
                layer += 1
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("rotary" in k or "inv_freq" in k for k in missing), (missing, unexpected)
    return torch, model


@pytest.mark.parametrize("seed", [3, 11])
def test_the_whole_model_is_transformers_qwen3_next(uncut, seed):
    """One whole period (three Gated DeltaNet layers, then the gated attention).
    HF runs in float64 and is the answer: in float32 the two programs' rounding
    (HF's chunked rule against the token-by-token one, eight norms and a softmax
    over the experts a layer) reads 2e-5 to 6e-5 of logits that reach 4.6 (an
    untied head at 1/sqrt(D): LFM2's tied head gives logits of 0.16), as far from
    the float64 answer as from each other, so the tolerance is 2e-5 of the
    largest logit."""
    sizes = dict(SIZES, num_hidden_layers=4)
    params = drawn(sizes, seed)
    torch, model = hf_model(sizes, params)
    ids = np.random.default_rng(seed).integers(0, sizes["vocab_size"], S)
    with torch.no_grad():
        want = model.double()(torch.tensor(ids[None])).logits[0].numpy()
    got = np.asarray(ref.logits_rows(sizes, params, ids, list(range(S))))
    assert ref.layer_kinds(sizes) == ["linear_attention"] * 3 + ["full_attention"]
    assert ref.segments(sizes) == [(0, 1, 3), (3, 1, 1)] and ref.segments(SIZES) == [(0, 4, 2)]
    np.testing.assert_allclose(got, want, atol=TOL * np.abs(want).max(), rtol=0)


@pytest.mark.parametrize("carried", [False, True])
def test_the_token_by_token_rule_is_hfs_chunked_rule(carried):
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.qwen3_next.modeling_qwen3_next")
    rng = np.random.default_rng(1)
    s, h, dk, dv = 150, 3, 16, 8
    q, k = (rng.normal(size=(s, h, dk)).astype(np.float32) for _ in range(2))
    v = rng.normal(size=(s, h, dv)).astype(np.float32)
    g = -rng.uniform(1e-4, 3.0, size=(s, h)).astype(np.float32)  # decays from 0.05 to 0.9999
    beta = rng.uniform(0, 1, size=(s, h)).astype(np.float32)
    state = rng.normal(size=(h, dk, dv)).astype(np.float32) if carried else None
    norm = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    got, last = ref.delta_rule(jnp.asarray(norm(q) * dk ** -0.5), jnp.asarray(norm(k)),
                               jnp.asarray(v), jnp.exp(jnp.asarray(g)), jnp.asarray(beta),
                               None if state is None else jnp.asarray(state))
    t = lambda a: torch.tensor(a)[None]
    want, want_last = hf.torch_chunk_gated_delta_rule(
        t(q), t(k), t(v), g=t(g), beta=t(beta), output_final_state=True,
        initial_state=None if state is None else t(state), use_qk_l2norm_in_kernel=True)
    np.testing.assert_allclose(np.asarray(got), want[0].numpy(), atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(last), want_last[0].numpy(), atol=TOL, rtol=0)


def test_the_four_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The routed parts of chips 0..3, each over its own quarter of the
    experts, plus the gated shared expert counted once, are what the uncut
    reference gives for the whole layer (model-configs guide, section 4)."""
    whole = dict(SIZES, num_experts=16)
    monkeypatch.setattr(ref, "EP_CHIPS", 1)
    params = ref.init_params(whole, jax.random.PRNGKey(5), jnp.float32)
    moe = jax.tree_util.tree_map(lambda a: a[0], params["segments"][0][0]["moe"])
    experts = jax.tree_util.tree_map(lambda a: a[0], params["experts"])
    n = jax.random.normal(jax.random.PRNGKey(6), (33, whole["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.layer_parts(whole, {**moe, "experts": experts}, n)
        monkeypatch.setattr(ref, "EP_CHIPS", 4)
        held = dict(whole, num_experts=4)
        assert ref.router_width(held) == 16
        parts = []
        for chip in range(4):
            mine = jax.tree_util.tree_map(lambda a: a[4 * chip:4 * (chip + 1)], experts)
            part, same = ref.layer_parts(held, {**moe, "experts": mine}, n, chip=chip)
            np.testing.assert_array_equal(np.asarray(same), np.asarray(shared))
            parts.append(np.asarray(part))
    assert all(np.abs(p).max() > 0 for p in parts)  # every chip's experts are picked by someone
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), np.asarray(routed + shared),
                               atol=TOL, rtol=0)
