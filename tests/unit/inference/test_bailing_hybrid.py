"""Ling-3.0 (ISSUE 58, ``bailing_hybrid``): Kimi Delta Attention layers whose
memory of a sequence is a float32 matrix a head (by reference) and a short shift
(by value), two leaves of a state tree in a slot beside a LATENT pool that the
one MLA layer of a period attends; a head-wise output gate on both mixers; a
leading dense layer; DeepSeek-V3's group-limited sigmoid router over a share of
the experts.

The program (``models/bailing_hybrid.py`` on ``transformer.paged_forward``,
through the engine's scheduler, manager, bursts) against the plain reference
(``chipbench/references/bailing_hybrid.py``: whole sequences, the recurrence
token by token, keys and values materialised from the latent, no state, no
cache) in float32 at two periods of (KDA, KDA, MLA).
The shared cases are ``family_contract.py``'s; this file builds two engine
configurations (``served``, ``oracle``).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import bailing_hybrid as ref
from deepspeed_tpu.models import bailing_hybrid as family
from deepspeed_tpu.models.transformer import STATE
from tests.unit.inference.family_contract import Family, Pool, StatefulContract, WrongReadings

HELD = 4  # of 16 experts in 4 groups: one chip's share of four, one group a chip
PUBLISHED = json.load(open(os.path.join(os.path.dirname(ref.__file__), "..", "published",
                                        "ling-3.0-flash.json")))["config"]
SIZES = {**PUBLISHED, "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
         "head_dim": 16, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
         "moe_shared_expert_intermediate_size": 32, "num_hidden_layers": 6, "layer_group_size": 3,
         "first_k_dense_replace": 1, "vocab_size": 256, "num_experts": HELD, "n_group": 4,
         "topk_group": 2, "num_experts_per_tok": 4, "rope_theta": 10000,
         "max_position_embeddings": 512, "expert_swiglu_limit_list": [0] * 6,
         "share_expert_swiglu_limit_list": [0] * 6}
CFG = family.BailingHybridConfig.tiny(experts=4 * HELD, local_experts=HELD)
NB, BS, SLOTS = 72, 4, 4
NORMS = {"op_norm", "ffn_norm", "final_norm", "norm", "kv_norm"}


def off_neutral(names, leaf, noise):  # a gain left out or misplaced must show
    return leaf + 0.3 * noise(leaf.shape) if NORMS & set(names) else leaf


def layout(h, own, cache):
    twelve = family.BailingHybridConfig(num_layers=12, num_local_experts=64, vocab_size=19648)
    assert family.layer_segments(twelve) == [(0, 1, 2), (2, 1, 3), (5, 1, 1), (6, 1, 5), (11, 1, 1)]
    assert family.layer_segments(family.BailingHybridConfig()) == [(0, 1, 2), (2, 6, 6), (38, 1, 3), (41, 1, 1)]
    assert twelve.num_experts == 512 and twelve.layer_types.count(family.MLA) == 2
    drawn = jax.jit(lambda key: family.init_params(CFG, key))(jax.random.PRNGKey(0))
    mixer = drawn["segments"][1][0]["mixer"]  # the draw is the reference's: no channel at the bound
    np.testing.assert_allclose(mixer["dt_bias"], h.params["segments"][1][0]["mixer"]["dt_bias"], rtol=1e-6)
    np.testing.assert_allclose(mixer["A_log"], h.params["segments"][1][0]["mixer"]["A_log"], atol=1e-6)
    assert own["experts"]["w_gate"].shape[:2] == (5, HELD)  # the held experts of every expert layer
    assert own["segments"][1][0]["moe"]["gate"]["wg"].shape[-1] == 4 * HELD  # the router's width
    # the two MLA layers alone in the latent pool; the KDA layers' two leaves apart, float32
    assert cache["latent"].shape == (2, NB, 1, BS, 128)
    assert cache[STATE]["conv"].shape == (4, SLOTS + 1, 3, 3 * 64)
    assert cache[STATE]["recurrent"].shape == (4, SLOTS + 1, 4, 16, 16)
    half = h.fresh_cache(jnp.bfloat16)
    assert (half["latent"].dtype, half[STATE]["conv"].dtype, half[STATE]["recurrent"].dtype) == \
        (jnp.bfloat16, jnp.bfloat16, jnp.float32)
    assert family.state_bytes_per_seq(twelve) == 10 * (2097152 + 73728) == 21708800
    assert family.paged_value_dim(twelve) == 512 and family.latent_width(twelve) == 640


def wave(h, seen):
    c, prompts = seen.counters, seen.prompts
    by_leaf = seen.engine.health()["state"]["state_bytes_by_leaf"]
    assert by_leaf == {"conv": 4 * 3 * 192 * 4, "recurrent": 4 * 4 * 16 * 16 * 4}
    # the scan's counters: the tokens of the rows of more than one token, in each of the four
    # KDA layers; a decode step or a burst walks no chunk
    assert c["scan_positions"] == c["scan_chunks"] * 64
    assert 0 < c["scan_live_positions"] <= c["scan_positions"] and c["scan_live_positions"] % 4 == 0
    assert sum(map(len, prompts)) - 2 * len(prompts) <= c["scan_live_positions"] // 4 \
        <= sum(map(len, prompts)) < c["live_tokens"]
    assert c["moe_routed_rows"] == c["live_tokens"] * 4 * 5  # top-4 in five expert layers


# ------------------------------------------------- readings that must not pass
KDA_GATE, ROUTER = ref.kda_gate, ref.router


def a_decay_a_head(sizes, u, w):  # Gated DeltaNet's: the channels' mean, one number a head
    g = KDA_GATE(sizes, u, w)
    return jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)


def unbounded_gate(sizes, u, w):  # FLA's gate without the lower bound
    f = (u @ w["w_f"] + w["dt_bias"]).reshape(-1, sizes["num_attention_heads"], sizes["head_dim"])
    return -jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus(f)


def rotate_half(x, positions, theta):  # pairs (i, i + d/2), not DeepSeek's (2i, 2i + 1)
    half = x.shape[-1] // 2
    angle = positions[:, None, None] * theta ** -(jnp.arange(half, dtype=jnp.float32) / half)
    return jnp.concatenate([x[..., :half] * jnp.cos(angle) - x[..., half:] * jnp.sin(angle),
                            x[..., half:] * jnp.cos(angle) + x[..., :half] * jnp.sin(angle)], axis=-1)


def groups_by_their_best(sizes, n, gate):  # DeepSeek-V2's group score: the maximum, not the two best
    scores = jax.nn.sigmoid(n @ gate["wg"].astype(jnp.float32))
    choice = scores + gate["bias"].astype(jnp.float32)
    by_group = choice.reshape(choice.shape[0], sizes["n_group"], -1)
    _, best = jax.lax.top_k(jnp.max(by_group, axis=-1), sizes["topk_group"])
    stays = jnp.zeros(by_group.shape[:2], bool).at[jnp.arange(choice.shape[0])[:, None], best].set(True)
    allowed = jnp.where(jnp.repeat(stays, by_group.shape[-1], axis=1), choice, -jnp.inf)
    _, idx = jax.lax.top_k(allowed, sizes["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * sizes["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(scores.shape[0])[:, None], idx].set(top)


def no_bias(sizes, n, gate):
    return ROUTER(sizes, n, {**gate, "bias": jnp.zeros_like(gate["bias"])})


def one_group(sizes, n, gate):  # GLM-5's: the top-k over all experts, no group left out
    return ROUTER({**sizes, "n_group": 1, "topk_group": 1}, n, gate)


WRONG = {
    "a decay a head, not a channel (Gated DeltaNet's)": dict(kda_gate=a_decay_a_head),
    "the gate without its lower bound": dict(kda_gate=unbounded_gate),
    "rotate-half rotary": dict(rotary_pairs=rotate_half),
    "a group scores its best, not its two best": dict(router=groups_by_their_best),
    "no selection bias": dict(router=no_bias),
    "one group": dict(router=one_group),
    "routed_scaling_factor left out": dict(sizes={"routed_scaling_factor": 1.0}),
    "the picks not renormalised": dict(sizes={"norm_topk_prob": False}),
    "A_log a channel's share: the slopes left out": dict(params=lambda p: jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if getattr(path[-1], "key", None) == "A_log" else a, p)),
    "the output gates left out": dict(params=lambda p: jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if getattr(path[-1], "key", None) == "w_gate"
        and getattr(path[-2], "key", None) in ("mixer", "attn") else a, p)),
}


FAMILY = Family(
    module=family, reference=ref, sizes=SIZES, config=CFG,
    tolerance=1e-4,
    tolerance_reason="""1e-4 of the largest logit.  Two float32 programs of six such layers (the
    chunked scan with its block inverse against the token-by-token recurrence,
    absorbed latent attention over a paged pool against materialised keys and a
    dense softmax, sorted dispatch against every expert) read 3e-6 apart at the
    row the wrong readings are held against; the weakest wrong reading below
    reads 2e-3, and bfloat16 in float32's place 3e-2.""",
    off_neutral=off_neutral, pool=Pool(NB, BS, 48, SLOTS), state_leaves=("conv", "recurrent"),
    segments=[(0, 1, 1), (1, 1, 1), (2, 1, 1), (3, 1, 2), (5, 1, 1)],
    # a chunk continues from the matrices and the shift its sequence's slot holds and from the
    # latents its blocks hold, across the scan's own chunks of 64 and the step's
    chunkings=((64, 64, 22), (1, 70, 79)), decode_steps=2,
    # two chunks, a decode row and a prompt of one token that begins: the one-token rows go to the
    # update, the others are laid onto a chunk's edge; all four slots are named
    mixed=((160, 70, 160), (80, 5, 80), (9, 8, 9), (1, 0, 1)), mixed_slots=(0, 3, 1, 2),
    mixed_interpreted=True,
    # six sequences through four slots; the last two prompts share 16 leading tokens (two whole
    # blocks): mapped blocks would restore the latents and start the KDA state at zero in
    # mid-prompt, so the hit is declined, counted, and both are served whole.  Compared: one cut
    # in three, a short one, the sharer
    waves=((5, 90, 140, 9, ((20, 16), (21, 54)), ((20, 16), (22, 3))), ), compared=(1, 3, 5),
    layout=layout, wave=wave, wrong_readings=WRONG)

class TestBailingHybrid(StatefulContract, WrongReadings):
    family = FAMILY

    @pytest.mark.parametrize("what,keys", [
        ("SwiGLU", {"expert_swiglu_limits": (0, 0, 4)}), ("SwiGLU", {"shared_swiglu_limits": (5, )}),
        ("use_kda_lora", {"use_kda_lora": True}), ("kda_safe_gate", {"kda_safe_gate": False}),
        ("kda_lower_bound", {"kda_lower_bound": -8.0}), ("q_lora_rank", {"q_lora_rank": 1536}),
        ("rope_scaling", {"rope_scaling": {"type": "yarn"}}), ("use_mla_nope", {"use_mla_nope": True}),
        ("value_norm", {"value_norm": True}), ("up_proj_norm", {"up_proj_norm": True}),
        ("use_nGPT", {"use_ngpt": True}), ("scale_router_input", {"scale_router_input": True}),
        ("granularity", {"gate_granularity": "element_wise"}), ("topk_method", {"topk_method": "greedy"})])
    def test_what_is_published_otherwise_and_not_built_is_refused(self, what, keys):
        with pytest.raises(NotImplementedError, match=what):
            family.BailingHybridConfig(**keys)
        hf = dict(PUBLISHED)
        hf["expert_swiglu_limit_list"] = PUBLISHED["expert_swiglu_limit_list"][:12]
        hf["share_expert_swiglu_limit_list"] = PUBLISHED["share_expert_swiglu_limit_list"][:12]
        hf["num_hidden_layers"] = 12
        cut = family.config_from_hf(type("Hf", (), hf))
        assert (cut.num_experts, cut.layer_types.count(family.KDA), cut.kda_lower_bound) == (512, 10, -5.0)
        with pytest.raises(NotImplementedError, match="SwiGLU"):  # the published 42 layers hold the clamp
            family.config_from_hf(type("Hf", (), dict(PUBLISHED)))

    def test_a_sequence_that_begins_in_a_slot_out_of_row_order_is_served_by_the_kernels_too(self, h):
        """A slot is never zeroed: the sequence that takes it over begins (``start_pos
        == 0``) over NaNs, in a slot that is neither its row nor in row order, and is
        served as over a fresh cache, in a chunk pass and in the decode steps that
        follow; the slots no row names hold what they held."""
        forward, second = h.interpreted(), h.ids_of(23, 70 + 2)
        blocks, slot = list(range(5, 30)), 3
        cache = h.fresh_cache()
        cache[STATE] = {leaf: rows.at[:, slot].set(jnp.nan).at[:, (0, 2)].set(3.0)
                        for leaf, rows in cache[STATE].items()}
        before, at = cache[STATE], 70
        (got, _), cache = h.step(cache, [(second[:at], 0, blocks, slot), ([], 0, [], SLOTS)], t=256,
                                 forward=forward)
        for _ in range(2):
            h.close(got, h.want(second, [at - 1])[0])
            (got, ), cache = h.step(cache, [(second[at:at + 1], at, blocks, slot)], t=1, forward=forward)
            at += 1
        h.close(got, h.want(second, [at - 1])[0])
        for leaf in ("conv", "recurrent"):
            np.testing.assert_array_equal(np.asarray(cache[STATE][leaf][:, (0, 2)]),
                                          np.asarray(before[leaf][:, (0, 2)]))
