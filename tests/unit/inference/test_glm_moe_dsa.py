"""GLM-5 (ISSUE 45, ``glm_moe_dsa``): latent attention that attends only the
cached tokens a learned indexer picks, an index-key leaf beside the latent one
in the paged pool, sigmoid bias-corrected routing over one chip's share of the
experts.

The program (``models/glm_moe_dsa.py`` on ``transformer.paged_forward``,
``ops/attention/dsa.py``, ``paged.py``; through the engine's scheduler, manager,
bursts and prefix cache) against the plain reference
(``chipbench/references/glm_moe_dsa.py``: whole sequences, expanded heads,
``lax.top_k``, no cache) in float32 at a size whose prompts are several times
``index_topk``, so that every compared position selects.
The shared cases are ``family_contract.py``'s; this file builds three engine
configurations (``served``, ``oracle``, and the contract's engine under
speculation, which this family serves).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import glm_moe_dsa as ref
from deepspeed_tpu.models import glm_moe_dsa
from deepspeed_tpu.moe.serving import sparse_moe_ffn
from deepspeed_tpu.ops.attention import paged
from tests.unit.inference.family_contract import Family, Pool, ServingContract

HELD, TOPK = 1, 16  # of 16 experts: one chip's share of sixteen; keys a token attends
SIZES = {"first_k_dense_replace": 1, "hidden_size": 64, "index_head_dim": 16, "index_n_heads": 4,
         "index_topk": TOPK, "intermediate_size": 128, "kv_lora_rank": 32,
         "max_position_embeddings": 1024, "moe_intermediate_size": 32, "n_routed_experts": HELD,
         "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
         "num_experts_per_tok": 4, "num_hidden_layers": 2, "q_lora_rank": 48,
         "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5,
         "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
         "routed_scaling_factor": 2.5, "v_head_dim": 16, "vocab_size": 256}
CFG = glm_moe_dsa.GlmMoeDsaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_layers=2,
    first_k_dense=1, num_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
    qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=TOPK,
    num_experts=ref.EP_CHIPS * HELD, num_local_experts=HELD, top_k=4, max_seq_len=1024,
    rope_parameters=SIZES["rope_parameters"])
NB, BS, MAXB = 72, 4, 48
NORMS = {"attn_norm", "mlp_norm", "q_norm", "kv_norm", "k_norm", "final_norm"}


def off_neutral(names, leaf, noise):  # a gain, a bias or a norm of the wrong kind or place must show
    if NORMS & set(names):
        return leaf + 0.3 * noise(leaf.shape)
    if "k_norm_bias" in names:
        return leaf + 0.5 * noise(leaf.shape)
    if "weights" in names:  # head weights of either sign (the drawn ones are one positive number)
        return noise(leaf.shape) * leaf.shape[-2] ** -0.5
    if "bias" in names:  # the router's: large enough to move picks
        return 0.3 * noise(leaf.shape)
    return leaf


def layout(h, own, cache):
    """Two pool leaves of unlike widths."""
    assert own["layers"]["moe"]["experts"]["w_gate"].shape[:2] == (1, HELD)
    assert own["layers"]["moe"]["gate"]["wg"].shape[-1] == 16 * HELD  # the router's width
    assert cache["latent"].shape == (2, NB, 1, BS, 128)  # 32 + 8 values in whole lanes
    assert cache[glm_moe_dsa.PAGED_SELECT_LEAF].shape == (2, NB, 1, BS, 16)
    full = glm_moe_dsa.GlmMoeDsaConfig()
    whole = jax.eval_shape(lambda: glm_moe_dsa.init_paged_cache(full, 8, 128))
    assert {k: v.shape[-1] for k, v in whole.items()} == {"latent": 640, "index_keys": 128}
    assert glm_moe_dsa.selected_keys(full) == (2048, 78) and full.rope_theta == 1e6
    assert glm_moe_dsa.paged_value_dim(full) == 512
    with pytest.raises(ValueError, match="rope_type"):
        glm_moe_dsa.GlmMoeDsaConfig(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"})


def wave(h, seen):
    c = seen.counters
    assert c["moe_routed_rows"] == c["live_tokens"] * 4  # k picks in the one expert layer
    # the selection's counters: every live token, in each of the two layers
    assert 0 < c["dsa_selected_keys"] < c["dsa_causal_keys"] <= c["dsa_scored_keys"]
    assert c["dsa_selected_keys"] <= c["dsa_attended_keys"] >= c["dsa_causal_keys"]
    assert c["dsa_selected_keys"] <= c["live_tokens"] * TOPK * 2


FAMILY = Family(
    module=glm_moe_dsa, reference=ref, sizes=SIZES, config=CFG,
    tolerance=3e-4,
    tolerance_reason="3e-4 of the largest logit, Qwen3-Next's: sound float32 runs read 2e-6; a key "
    "selected that the reference did not select, or a gain of the wrong kind, reads 1e-2 and more",
    off_neutral=off_neutral, pool=Pool(NB, BS, MAXB),
    # a later chunk scores the index keys an earlier chunk wrote to the pool and its own alike; a
    # token under ``index_topk`` attends all of its past, one over it the reference's ``S_t``.
    # The mixed step: a decode row riding in a chunk's bucket selects as it does alone (block 71
    # is the trash block)
    mixed=((160, 70, 160), (80, 5, 80), (40, 39, 40)),
    # two waves cut their prompts at different places: one decode-only, one cut in three, one in five
    waves=((5, 90, 140, 9), (7, 75, 120, 13)), new_tokens=5, oracle_new_tokens=3,
    # (under speculation a rejected draft is rolled back by blocks, and the index keys live in
    # those blocks: the verify path needs nothing of its own)
    layout=layout, wave=wave)


class TestGlmMoeDsa(ServingContract):
    family = FAMILY

    @pytest.mark.parametrize("tied", [False, True], ids=["drawn", "every_score_tied"])
    def test_the_selected_sets_are_the_references(self, h, monkeypatch, tied):
        """The program's selection of layer 0, read where the kernel is handed it,
        is the reference's ``S_t`` position for position: rows under ``index_topk``
        keep all of their past, rows over it the top 16; with the head weights zero
        every score is one (plus or minus) zero and the rule of the lower position
        alone decides: the first 16 positions."""
        params = h.params
        if tied:
            params = jax.tree_util.tree_map(lambda a: a, params)
            params["dense_layers"]["indexer"]["weights"] = jnp.zeros_like(
                params["dense_layers"]["indexer"]["weights"])
        seen = []
        real = paged.paged_attention

        def spy(q, *args, selection=None, **facts):
            jax.debug.callback(lambda s: seen.append(np.asarray(s)), selection)
            return real(q, *args, selection=selection, **facts)

        monkeypatch.setattr(paged, "paged_attention", spy)
        ids = h.ids_of(5, 60)
        logits, _ = glm_moe_dsa.forward_paged(
            CFG, params, jnp.asarray([ids + [0] * 4]), jnp.asarray([60]), jnp.asarray([0]),
            jnp.asarray([list(range(16)) + [NB - 1] * (MAXB - 16)]), h.fresh_cache(), block_size=BS)
        jax.block_until_ready(logits)
        jax.effects_barrier()
        assert len(seen) == 2 and seen[0].shape == (1, 64, MAXB * BS)
        w = jax.tree_util.tree_map(lambda a: a[0], params["dense_layers"])
        with jax.default_matmul_precision("highest"):
            x = params["embed"][jnp.asarray(ids)]
            n1 = ref.rms_norm(x, w["attn_norm"], 1e-5)
            c_q = ref.rms_norm(n1 @ w["attn"]["wq_a"], w["attn"]["q_norm"], 1e-5)
            wanted = np.asarray(ref.selection(SIZES, w["indexer"], n1, c_q, jnp.arange(60)))
        got = seen[0][0, :60, :60]
        np.testing.assert_array_equal(got, wanted)
        assert not seen[0][0, :, 60:].any() and not seen[0][0, 60:].any()  # nothing past the sequence
        assert (got.sum(-1) == np.minimum(np.arange(60) + 1, TOPK)).all()
        if tied:
            assert (got[TOPK:, :TOPK]).all()  # the lower positions

    def test_the_selections_counters_are_the_traffics_whatever_the_layout(self, h):
        fast, slow = h.twins.fast, h.twins.slow
        for name in ("dsa_causal_keys", "dsa_selected_keys"):
            # a burst's last passes may run past a sequence's end
            assert fast[name] >= slow[name] > 0

    def test_the_counters_are_the_sums_over_positions(self, h):
        eng = h.served
        before = eng.counters.snapshot()
        eng.generate([h.ids_of(60, 50)], max_new_tokens=3)
        positions = np.arange(50 + 3 - 1)  # every token that went through a forward pass
        c = eng.counters.delta_since(before)
        assert c["dsa_causal_keys"] == 2 * int((positions + 1).sum())
        assert c["dsa_selected_keys"] == 2 * int(np.minimum(positions + 1, TOPK).sum())

    def test_index_keys_in_a_shared_prefix_block_are_the_ones_a_later_prompt_scores(self, h):
        """Prompts of one wave share a header of whole blocks: the later ones take
        the first's blocks (both leaves of them: a block is a block) and score the
        index keys they find there."""
        head = h.ids_of(70, 64)
        prompts = [head + h.ids_of(71 + i, 20 + 7 * i) for i in range(2)]
        eng = h.served
        hits = eng.health()["prefix_cache"]["hits_total"]
        got = eng.generate(prompts, max_new_tokens=3)
        assert eng.health()["prefix_cache"]["hits_total"] - hits >= 64 // 8 - 1
        for p, g in zip(prompts, got):
            assert list(g) == h.greedy(p, 3)
        eng.check_kv_invariant()

    def test_a_copied_block_carries_both_leaves(self, h):
        eng = h.served
        eng.generate([h.ids_of(80, 40)], max_new_tokens=2)
        before = jax.tree_util.tree_map(np.asarray, eng.kv)
        eng._cow_copy_block(0, 50)
        for name, leaf in eng.kv.items():
            np.testing.assert_array_equal(np.asarray(leaf[:, 50]), before[name][:, 0])
            assert np.abs(before[name][:, 0]).max() > 0

    def test_the_expert_layer_is_this_chips_share_of_sixteen(self, h):
        """``sparse_moe_ffn`` against the reference's ``layer_parts`` for chip 0: a
        sigmoid router over 16 with a selection bias, 1 expert held, picks elsewhere
        add nothing, the picked scores renormalised and times 2.5."""
        params = h.params
        moe = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
        experts = params["layers"]["moe"]["experts"]
        x = jax.random.normal(jax.random.PRNGKey(9), (37, 64))
        with jax.default_matmul_precision("highest"):
            got = sparse_moe_ffn({"gate": moe["gate"], "shared": moe["shared"], "experts": experts}, x,
                                 4, True, layer=jnp.int32(0), scaling=2.5, scoring="sigmoid",
                                 norm_eps=1e-20)
            routed, shared = ref.layer_parts(SIZES, {**moe, "experts": experts}, x, layer=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(routed + shared), atol=2e-5, rtol=0)
