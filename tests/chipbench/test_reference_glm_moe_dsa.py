"""The plain GLM-5 reference (``chipbench/references/glm_moe_dsa.py``) against
the program at a tiny size on the CPU in float32: one forward through the paged
pool against the reference's whole-sequence forward, the shares of the experts
against the uncut layer, and wrong readings of the architecture that each have
to fail.  (Chunks, decode steps, bursts, the prefix cache and the selected sets
themselves: ``tests/unit/inference/test_glm_moe_dsa.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import glm_moe_dsa as ref

HELD = 1
SIZES = {"first_k_dense_replace": 1, "hidden_size": 64, "index_head_dim": 16, "index_n_heads": 4,
         "index_topk": 16, "intermediate_size": 128, "kv_lora_rank": 32,
         "max_position_embeddings": 1024, "moe_intermediate_size": 32, "n_routed_experts": HELD,
         "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
         "num_experts_per_tok": 4, "num_hidden_layers": 3, "q_lora_rank": 48,
         "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5,
         "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
         "routed_scaling_factor": 2.5, "v_head_dim": 16, "vocab_size": 256}
LENGTH, BLOCK = 60, 8
# Both sides are float32 on the CPU and differ by the order of their sums: the
# program multiplies q into the latent where the reference expands k and v to
# heads, sorts rows for a grouped matmul where the reference runs every expert.
# 3e-6 of the largest logit was read.  A wrong reading has to pass 100 tolerances.
TOLERANCE = 2e-5


@pytest.fixture(scope="module")
def drawn():
    params = jax.jit(lambda k: ref.init_params(SIZES, k, jnp.float32))(jax.random.PRNGKey(4))
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))
    for stack in ("dense_layers", "layers"):  # gains, a LayerNorm's bias and a router's bias that
        for path in (("attn", "q_norm"), ("attn", "kv_norm"), ("indexer", "k_norm"),  # are not neutral
                     ("indexer", "k_norm_bias")):
            leaf = params[stack][path[0]][path[1]]
            params[stack][path[0]][path[1]] = leaf + 0.4 * jax.random.normal(next(keys), leaf.shape)
        heads = params[stack]["indexer"]["weights"]  # of either sign: the drawn ones are one positive number
        params[stack]["indexer"]["weights"] = jax.random.normal(next(keys), heads.shape) / 8
    bias = params["layers"]["moe"]["gate"]["bias"]
    params["layers"]["moe"]["gate"]["bias"] = 0.3 * jax.random.normal(next(keys), bias.shape)
    ids = np.random.default_rng(4).integers(0, 256, LENGTH).tolist()
    return params, ids


def reference_logits(params, ids, sizes=SIZES, **how):
    with jax.default_matmul_precision("highest"):
        x = ref.hidden_states(sizes, params, jnp.asarray(ids, jnp.int32), **how)
        return np.asarray(x @ params["lm_head"])


@pytest.fixture(scope="module")
def program_logits(drawn):
    from deepspeed_tpu.models import glm_moe_dsa
    params, ids = drawn
    cfg = glm_moe_dsa.GlmMoeDsaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_layers=3, first_k_dense=1, num_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16,
        index_topk=16, num_experts=ref.EP_CHIPS * HELD, num_local_experts=HELD, top_k=4,
        max_seq_len=1024, rope_parameters=SIZES["rope_parameters"])
    blocks = -(-LENGTH // BLOCK)
    cache = glm_moe_dsa.init_paged_cache(cfg, blocks + 1, BLOCK, dtype=jnp.float32)
    tokens = np.zeros((1, 64), np.int32)
    tokens[0, :LENGTH] = ids
    with jax.default_matmul_precision("highest"):
        logits, _ = glm_moe_dsa.forward_paged(
            cfg, params, jnp.asarray(tokens), jnp.asarray([LENGTH]), jnp.asarray([0]),
            jnp.asarray([list(range(blocks))]), cache, block_size=BLOCK)
    return np.asarray(logits[0, :LENGTH])


def test_the_program_is_the_reference(drawn, program_logits):
    want = reference_logits(*drawn)
    assert np.abs(program_logits - want).max() <= TOLERANCE * np.abs(want).max()


def without_relu(sizes, ix, k_i, n1, c_q, positions):
    j, di = sizes["index_n_heads"], sizes["index_head_dim"]
    q_i = ref.rotary_first((c_q @ ix["wq"]).reshape(-1, j, di), positions, ref.theta_of(sizes),
                           sizes["qk_rope_head_dim"])
    w = (n1 @ ix["weights"]) * (j ** -0.5 * di ** -0.5)
    return jnp.einsum("qj,qjd,sd->qs", w, q_i, k_i)


def rotary_last(x, positions, theta, rope):
    return jnp.concatenate([x[..., :-rope], ref.rotary(x[..., -rope:], positions, theta)], axis=-1)


def bias_that_weighs(sizes, n2, gate):
    scores = jax.nn.sigmoid(n2 @ gate["wg"]) + gate["bias"]
    top_s, top_idx = jax.lax.top_k(scores, sizes["num_experts_per_tok"])
    top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) * sizes["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(scores.shape[0])[:, None], top_idx].set(top_s)


WRONG = {
    "selection_off": dict(how={"select": False}),
    "relu_left_out": dict(patch=("index_scores", without_relu)),
    "head_weights_left_out": dict(patch=("index_scores", "unit_weights")),
    "rotary_part_taken_as_the_last_values": dict(patch=("rotary_first", rotary_last)),
    "bias_weighs_as_well_as_selects": dict(patch=("router", bias_that_weighs)),
    "no_renormalisation": dict(sizes={"norm_topk_prob": False}),
    "factor_left_out": dict(sizes={"routed_scaling_factor": 1.0}),
    "plain_layer_norm_for_rms": dict(patch=("layer_norm", lambda x, g, b, eps: ref.rms_norm(x, g, eps))),
}


@pytest.mark.parametrize("reading", sorted(WRONG))
def test_a_wrong_reading_of_the_architecture_fails(drawn, program_logits, monkeypatch, reading):
    """Each of these is a way to misread the published model that still runs:
    the program, compared with a reference that reads it so, is far off."""
    spec = WRONG[reading]
    if "patch" in spec:
        name, wrong = spec["patch"]
        if wrong == "unit_weights":  # w left out: every head weighs the same
            real = ref.index_scores

            def wrong(sizes, ix, k_i, n1, c_q, positions):
                ones = jnp.ones_like(ix["weights"]) * (n1.shape[-1] ** -0.5)
                return real(sizes, {**ix, "weights": ones}, k_i, jnp.ones_like(n1), c_q, positions)
        monkeypatch.setattr(ref, name, wrong)
    want = reference_logits(*drawn, sizes={**SIZES, **spec.get("sizes", {})}, **spec.get("how", {}))
    off = np.abs(program_logits - want).max() / np.abs(want).max()
    assert off > 100 * TOLERANCE, (reading, off)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(monkeypatch):
    """Sixteen experts as four shares of four: each chip's routed part (its own
    experts' among the picks of a router over all sixteen) and the shared
    expert ONCE are the layer with every expert held."""
    monkeypatch.setattr(ref, "EP_CHIPS", 4)
    sizes = {**SIZES, "n_routed_experts": 4}
    whole = ref.init_params({**sizes, "n_routed_experts": 16, "num_hidden_layers": 2},
                            jax.random.PRNGKey(6), jnp.float32)
    # (drawn under EP_CHIPS = 4 with 16 "held": a router 64 wide; cut to the 16 there are)
    moe = jax.tree_util.tree_map(lambda a: a[0], whole["layers"]["moe"])
    moe["gate"] = {"wg": moe["gate"]["wg"][:, :16],
                   "bias": 0.3 * jax.random.normal(jax.random.PRNGKey(7), (16, ))}
    n2 = jax.random.normal(jax.random.PRNGKey(8), (33, 64))
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(ref, "EP_CHIPS", 1)
        uncut_routed, shared = ref.layer_parts({**sizes, "n_routed_experts": 16}, moe, n2)
        monkeypatch.setattr(ref, "EP_CHIPS", 4)
        parts = [ref.layer_parts(sizes, {**moe, "experts": jax.tree_util.tree_map(
            lambda a: a[4 * chip:4 * chip + 4], moe["experts"])}, n2, chip=chip)
            for chip in range(4)]
    np.testing.assert_allclose(sum(routed for routed, _ in parts), uncut_routed, atol=2e-5)
    for _, again in parts:  # the same on every chip: counted once
        np.testing.assert_array_equal(np.asarray(again), np.asarray(shared))
    assert all(np.abs(np.asarray(routed)).max() > 1e-3 for routed, _ in parts)
    assert ref.router_width(sizes) == 16


def test_the_reference_imports_nothing_of_the_program():
    import inspect
    source = inspect.getsource(ref)
    assert "import deepspeed_tpu" not in source and "from deepspeed_tpu" not in source
    assert "approx_max_k" not in source and 'default_matmul_precision("highest")' in source
