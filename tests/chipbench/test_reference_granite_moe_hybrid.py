"""The plain Granite 4.0-H reference against its written source.

The whole model against ``transformers``' ``GraniteMoeHybridForCausalLM`` (torch
on the CPU, whose Mamba-2 layer then runs its ``torch_forward``; the same
weights, a small size with both layer kinds, 8 experts top-4 and the shared
MLP) in float32; the token-by-token recurrence continued from a carried state;
and the share: the two chips' routed parts plus the shared MLP counted once are
the uncut layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import granite_moe_hybrid as ref

KINDS = ["mamba", "mamba", "attention", "mamba"]
SIZES = {"attention_bias": False, "attention_multiplier": 0.0625, "embedding_multiplier": 12,
         "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 32, "layer_types": KINDS,
         "logits_scaling": 16, "mamba_chunk_size": 32, "mamba_conv_bias": True, "mamba_d_conv": 4,
         "mamba_d_head": 16, "mamba_d_state": 16, "mamba_expand": 2, "mamba_n_groups": 1,
         "mamba_n_heads": 8, "mamba_proj_bias": False, "max_position_embeddings": 512,
         "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
         "num_attention_heads": 4, "num_experts_per_tok": 4, "num_hidden_layers": 4,
         "num_key_value_heads": 2, "num_local_experts": 8, "position_embedding_type": "nope",
         "residual_multiplier": 0.22, "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
         "shared_intermediate_size": 48, "tie_word_embeddings": True, "vocab_size": 256}
S = 75  # tokens of the one sequence: more than two of HF's chunks of 32
TOL = 2e-5


@pytest.fixture
def uncut(monkeypatch):
    """The reference as the whole model: one chip holds every expert."""
    monkeypatch.setattr(ref, "EP_CHIPS", 1)


def drawn(sizes, seed=3):
    params = ref.init_params(sizes, jax.random.PRNGKey(seed), jnp.float32)
    # gains, D and dt_bias off their neutral value, or one left out or laid out wrongly would
    # change nothing
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def off_neutral(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        if any(n in ("op_norm", "ffn_norm", "final_norm", "norm", "dt_bias", "D") for n in names):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(off_neutral, params)


def hf_model(sizes, params):
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.granitemoehybrid.modeling_granitemoehybrid")
    from transformers.models.granitemoehybrid.configuration_granitemoehybrid import (
        GraniteMoeHybridConfig)
    cfg = GraniteMoeHybridConfig(**{k: v for k, v in sizes.items() if k != "model_type"})
    cfg._attn_implementation = "eager"
    model = hf.GraniteMoeHybridForCausalLM(cfg).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    state = {"model.embed_tokens.weight": t(params["embed"]), "model.norm.weight": t(params["final_norm"]),
             "lm_head.weight": t(params["embed"])}
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
                        p + "mamba.in_proj.weight": t(m["w_in"].T),
                        # torch's filter is [channel, 1, tap]; tap j weighs the value 3 - j before
                        p + "mamba.conv1d.weight": t(m["filter"].T[:, None, :]),
                        p + "mamba.conv1d.bias": t(m["conv_bias"]),
                        p + "mamba.A_log": t(m["A_log"]), p + "mamba.dt_bias": t(m["dt_bias"]),
                        p + "mamba.D": t(m["D"]), p + "mamba.norm.weight": t(m["norm"]),
                        p + "mamba.out_proj.weight": t(m["w_out"].T)})
                else:
                    a = w["attn"]
                    state.update({p + f"self_attn.{theirs}_proj.weight": t(a[ours].T) for ours, theirs
                                  in (("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o"))})
                moe, ex = w["moe"], jax.tree_util.tree_map(lambda a: np.asarray(a[layer]),
                                                           params["experts"])
                state[p + "block_sparse_moe.router.layer.weight"] = t(moe["gate"]["wg"].T)
                # HF's input_linear is [g | u'] as one matrix, [expert, 2 F, D]
                state[p + "block_sparse_moe.input_linear.weight"] = t(np.concatenate(
                    [ex["w_gate"], ex["w_up"]], axis=-1).transpose(0, 2, 1))
                state[p + "block_sparse_moe.output_linear.weight"] = t(ex["w_down"].transpose(0, 2, 1))
                state[p + "shared_mlp.input_linear.weight"] = t(np.concatenate(
                    [moe["shared"]["w_gate"], moe["shared"]["w_up"]], axis=-1).T)
                state[p + "shared_mlp.output_linear.weight"] = t(moe["shared"]["w_down"].T)
                layer += 1
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("rotary" in k or "inv_freq" in k for k in missing), (missing, unexpected)
    return torch, model


@pytest.mark.parametrize("seed", [3, 11])
def test_the_whole_model_is_transformers_granite_moe_hybrid(uncut, seed):
    """Two Mamba-2 layers, the attention layer, a Mamba-2 layer, every expert
    held.  HF runs in float64 and is the answer (its chunked scan, chunks of 32,
    against the token-by-token recurrence): 2e-5 of the largest logit."""
    params = drawn(SIZES, seed)
    torch, model = hf_model(SIZES, params)
    ids = np.random.default_rng(seed).integers(0, SIZES["vocab_size"], S)
    with torch.no_grad():
        want = model.double()(torch.tensor(ids[None])).logits[0].numpy()
    got = np.asarray(ref.logits_rows(SIZES, params, ids, list(range(S))))
    assert ref.segments(SIZES) == [(0, 1, 2), (2, 1, 1), (3, 1, 1)]
    np.testing.assert_allclose(got, want, atol=TOL * np.abs(want).max(), rtol=0)


def test_the_published_period_is_three_runs():
    kinds = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]
    sizes = dict(SIZES, layer_types=kinds)
    assert ref.segments(dict(sizes, num_hidden_layers=10)) == [(0, 1, 5), (5, 1, 1), (6, 1, 4)]
    assert ref.segments(dict(sizes, num_hidden_layers=40)) == [(0, 10, 4)]


def test_a_recurrence_continued_from_its_state_is_the_whole_recurrence():
    rng = np.random.default_rng(1)
    s, h, p, ns = 90, 3, 8, 16
    x, b, c = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
               for shape in ((s, h, p), (s, ns), (s, ns)))
    dt = jnp.asarray(rng.uniform(0.01, 2.0, size=(s, h)).astype(np.float32))
    a, d = jnp.asarray(-rng.uniform(1e-3, 2.0, size=h).astype(np.float32)), jnp.ones(h)
    whole, last = ref.selective_scan(x, dt, a, b, c, d)
    head, kept = ref.selective_scan(x[:37], dt[:37], a, b[:37], c[:37], d)
    tail, end = ref.selective_scan(x[37:], dt[37:], a, b[37:], c[37:], d, kept)
    np.testing.assert_allclose(np.concatenate([head, tail]), np.asarray(whole), atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(end), np.asarray(last), atol=TOL, rtol=0)


def test_the_two_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The routed parts of chips 0 and 1, each over its own half of the
    experts, plus the shared MLP counted once, are what the uncut reference
    gives for the whole layer (model-configs guide, section 4)."""
    monkeypatch.setattr(ref, "EP_CHIPS", 1)
    params = ref.init_params(SIZES, jax.random.PRNGKey(5), jnp.float32)
    moe = jax.tree_util.tree_map(lambda a: a[0], params["segments"][0][0]["moe"])
    experts = jax.tree_util.tree_map(lambda a: a[0], params["experts"])
    n = jax.random.normal(jax.random.PRNGKey(6), (33, SIZES["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.layer_parts(SIZES, {**moe, "experts": experts}, n)
        monkeypatch.setattr(ref, "EP_CHIPS", 2)
        held = dict(SIZES, num_local_experts=4)
        assert ref.router_width(held) == 8
        parts = []
        for chip in range(2):
            mine = jax.tree_util.tree_map(lambda a: a[4 * chip:4 * (chip + 1)], experts)
            part, same = ref.layer_parts(held, {**moe, "experts": mine}, n, chip=chip)
            np.testing.assert_array_equal(np.asarray(same), np.asarray(shared))
            parts.append(np.asarray(part))
    assert all(np.abs(p).max() > 0 for p in parts)  # either chip's experts are picked by someone
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), np.asarray(routed + shared),
                               atol=TOL, rtol=0)
