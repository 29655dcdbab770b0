"""LongCat-Flash (ISSUE 49, ``longcat_flash``): a layer of two latent attentions
and two dense FFNs with a shortcut expert layer whose router may pick identity
experts, served as one chip's share of thirty-two.

The program (``models/longcat_flash.py`` on ``transformer.paged_forward(hand_on=
True)``, ``moe/serving.py sparse_moe_ffn(identity_experts=)``; through the
engine's scheduler, manager, bursts and prefix cache) against the plain
reference (``chipbench/references/longcat_flash.py``: whole sequences, expanded
heads, a loop over experts, no cache) in float32, and each wrong reading of the
architecture, planted in the program or written as a wrong reference, against
the same tolerance.
The shared cases are ``family_contract.py``'s; this file builds three engine
configurations (``served``, ``oracle``, and the contract's engine under
speculation, which this family serves).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import longcat_flash as ref
from deepspeed_tpu.inference.v2.fastpath import ServeCounters
from deepspeed_tpu.models import longcat_flash
from deepspeed_tpu.models.transformer import TALLY
from deepspeed_tpu.moe import serving
from tests.unit.inference.family_contract import Family, Pool, ServingContract

HELD, ZERO, TOPK = 2, 32, 6  # held of 32 x 2 = 64 real experts; identity experts; picks a token
SIZES = {"hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 2,
         "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "qk_nope_head_dim": 24, "mla_scale_q_lora": True,
         "mla_scale_kv_lora": True, "routed_scaling_factor": 6, "n_routed_experts": HELD,
         "zero_expert_num": ZERO, "moe_topk": TOPK, "rms_norm_eps": 1e-5, "rope_theta": 10000000,
         "vocab_size": 256, "max_position_embeddings": 1024}
CFG = longcat_flash.LongcatFlashConfig(
    vocab_size=256, hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32, num_layers=2,
    num_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=ref.EP_CHIPS * HELD, num_local_experts=HELD,
    zero_expert_num=ZERO, moe_topk=TOPK, max_seq_len=1024)
NB, BS, MAXB = 72, 4, 48
REL_TOL = 3e-4
NORMS = {"attn_norm", "mlp_norm", "q_norm", "kv_norm", "final_norm"}


def off_neutral(names, leaf, noise):  # a gain or a bias of the wrong kind or place must show
    if NORMS & set(names):
        return leaf + 0.3 * noise(leaf.shape)
    if "bias" in names:  # the router's: large enough to move picks (scores are about 0.01)
        return 0.01 * noise(leaf.shape)
    if names[-1] == "w_down" and "experts" in names:  # a held pick that weighs as an identity one
        return leaf * (SIZES["routed_scaling_factor"] * TOPK)
    return leaf


def layout(h, own, cache):
    """One latent leaf of two rows a layer, and the tallies."""
    assert own["layers"]["moe"]["experts"]["w_gate"].shape[:2] == (2, HELD)
    assert own["layers"]["moe"]["gate"]["wg"].shape[-1] == 32 * HELD + ZERO  # the router's width
    assert cache["latent"].shape == (4, NB, 1, BS, 128)  # 32 + 8 values in whole lanes, 2 rows a layer
    assert cache[TALLY].shape == (3, ) and cache[TALLY].dtype == jnp.int32
    full = longcat_flash.LongcatFlashConfig()
    whole = jax.eval_shape(lambda: longcat_flash.init_paged_cache(full, 8, 128))
    assert whole["latent"].shape == (56, 8, 1, 128, 640)
    assert longcat_flash.moe_picks_per_token(full) == 12 * 28
    assert longcat_flash.moe_expert_rows(full, 64) == 640 * 28  # every real expert held: 512 of 768 outputs
    share = longcat_flash.LongcatFlashConfig(num_local_experts=16)  # one chip of 32: 16 of 768
    assert longcat_flash.moe_expert_rows(share, 64) == 128 * 28
    assert longcat_flash.moe_expert_rows(share, 1024) == 384 * 28
    assert longcat_flash.paged_value_dim(full) == 512
    assert longcat_flash.lora_scales(full) == (2.0, 12 ** 0.5)
    assert longcat_flash.pick_tallies(full) == ("moe_identity_picks", "moe_held_picks",
                                                "moe_overflow_windows")
    tiny = longcat_flash.LongcatFlashConfig.tiny(local_experts=2)
    assert jax.eval_shape(lambda: longcat_flash.init_params(tiny, jax.random.PRNGKey(0)))[
        "layers"]["moe"]["gate"]["wg"].shape == (2, 128, 16 + 8)


PICKS = {}  # a sequence -> the reference's counts of its picks, worked out once


def picks_of(h, ids):
    if ids not in PICKS:
        with jax.default_matmul_precision("highest"):
            PICKS[ids] = np.asarray(ref.hidden_states(SIZES, h.params, jnp.asarray(ids))[1])
    return PICKS[ids]


def reference_counts(h, sequences):
    """``(least, most)`` picks on identity and on held experts of the tokens that
    went through a forward pass: all of a served sequence but its last token,
    and the last too where the loop had launched the next step before it knew
    the sequence was done (``live_tokens`` counts that token as well)."""
    least, most = np.zeros(2, np.int64), np.zeros(2, np.int64)
    for ids in sequences:
        least += picks_of(h, tuple(ids[:-1]))
        most += picks_of(h, tuple(ids))
    return least, most


def tallied_within(counters, least, most, slack=2):
    """A near-tie at the router's cut may fall either way between the program
    and the reference: a pick or two of thousands."""
    got = np.asarray([counters["moe_identity_picks"], counters["moe_held_picks"]])
    return bool(((least - slack <= got) & (got <= most + slack)).all())


def wave(h, seen):
    """The tallies read once a wave are the reference's counts."""
    c, eng, budget = seen.counters, seen.engine, FAMILY.engine["token_budget"]
    assert c["moe_routed_rows"] == c["live_tokens"] * TOPK * 2  # every pick, in each layer's one expert layer
    assert tallied_within(c, *reference_counts(h, seen.got))
    assert 0 < c["moe_held_picks"] < c["moe_identity_picks"]
    assert c["moe_identity_picks"] < c["moe_routed_rows"] - c["moe_identity_picks"] - c["moe_held_picks"]
    # the rows are the window of held picks (2 of 96 outputs, with headroom) a layer a pass; a
    # pass that holds more runs its window again, tallied: nothing is dropped for want of rows
    window = longcat_flash.moe_expert_rows(CFG, budget) // 2
    assert window == serving.expert_rows(budget, TOPK, HELD, 32 * HELD + ZERO) == 128
    assert c["moe_held_picks"] <= c["moe_expert_rows"] + window * c["moe_overflow_windows"]
    assert c["moe_expert_rows"] < c["moe_routed_rows"] and c["moe_overflow_windows"] >= 0
    assert c["moe_expert_rows"] == sum(
        longcat_flash.moe_expert_rows(CFG, slots) * passes for slots, passes in seen.launched)
    assert set(c) == set(eng.counters.FIELDS + eng.counters.TALLIED_FIELDS)
    # one fetch a wave beyond the steps' and the bursts' own: a wave of the same lengths (other
    # draws: nothing of it is in the prefix tree) served with the tallies off fetches one time less
    tallied, eng.counters.tallied = eng.counters.tallied, None
    try:
        before = eng.counters.snapshot()
        eng.generate(h.prompts(list(map(len, seen.prompts)), seed=90), max_new_tokens=FAMILY.new_tokens)
        assert c["host_syncs"] == eng.counters.delta_since(before)["host_syncs"] + 1
    finally:  # and what that wave tallied on the device is no part of the next window
        eng.counters.tallied = tallied
        eng.counters.absorb_tallies(np.asarray(eng.kv[TALLY]))


FAMILY = Family(
    module=longcat_flash, reference=ref, sizes=SIZES, config=CFG,
    tolerance=REL_TOL,
    tolerance_reason="3e-4 of the largest logit: sound float32 runs read under 2e-5, and each wrong reading "
    "of the architecture below reads over a hundred times the tolerance",
    off_neutral=off_neutral, noise_keys=64, pool=Pool(NB, BS, MAXB),
    # a later chunk attends what an earlier chunk wrote to BOTH of a layer's pool rows.  The mixed
    # step: the shortcut is handed on slot by slot (block 71 is the trash block)
    mixed=((160, 70, 160), (80, 5, 80), (40, 39, 40)),
    # two waves cut their prompts at different places; the tokens are the reference's either way
    waves=((5, 90, 140, 9), (7, 75, 120, 13)), new_tokens=5, compared=(0, 1, 2, 3), oracle_new_tokens=3,
    layout=layout, wave=wave)

# ------------------------------------------------ wrong readings of the architecture
def shared_cache_row(monkeypatch):
    """Both sublayers of a layer write and read the layer's FIRST pool row."""
    from deepspeed_tpu.ops.attention import kv_write, paged
    pair = 2 * NB  # rows of the flat stack a layer's two sublayers own
    write, attend, attend_flat = kv_write.kv_write, paged.paged_attention, paged.paged_attention_flat
    monkeypatch.setattr(kv_write, "kv_write", lambda pools, rows, first, *a: write(
        pools, rows, first // pair * pair, *a))
    shared = lambda tables: tables % NB + tables // pair * pair
    monkeypatch.setattr(paged, "paged_attention", lambda q, k, v, tables, *a, **kw: attend(
        q, k, v, shared(tables), *a, **kw))
    monkeypatch.setattr(paged, "paged_attention_flat", lambda q, k, v, tables, *a, **kw: attend_flat(
        q, k, v, shared(tables), *a, **kw))


def wrong_program(wrong, monkeypatch):
    whole, route = serving.sparse_moe_ffn, serving.route
    if wrong == "no_lora_scale":
        monkeypatch.setattr(longcat_flash, "lora_scales", lambda config: (1.0, 1.0))
    elif wrong == "k_pe_scaled_too":
        rank, rope, qkv = CFG.kv_lora_rank, CFG.qk_rope_head_dim, longcat_flash.mla_qkv
        scale = jnp.ones((128, )).at[rank:rank + rope].set(longcat_flash.lora_scales(CFG)[1])

        def scaled(*args):
            q, latent, c_q = qkv(*args)
            return q, latent * scale, c_q
        monkeypatch.setattr(longcat_flash, "mla_qkv", scaled)
    elif wrong == "weights_renormalised":
        monkeypatch.setattr(serving, "sparse_moe_ffn", lambda moe, x, k, renormalise, *a, **kw: whole(
            moe, x, k, True, *a, **kw))
    elif wrong == "bias_in_the_weights":
        def biased(wg, x, top_k, renormalise, n_group, topk_group, scaling, *, bias, **kw):
            w, picks = route(wg, x, top_k, renormalise, n_group, topk_group, scaling, bias=bias, **kw)
            return w + bias.astype(jnp.float32)[picks] * scaling, picks
        monkeypatch.setattr(serving, "route", biased)
    elif wrong == "identity_pick_adds_zero":
        monkeypatch.setattr(serving, "sparse_moe_ffn", lambda *a, identity_experts, **kw: whole(
            *a, identity_experts=0, **kw))
    elif wrong == "a_pick_held_elsewhere_lands_on_a_held_expert":
        monkeypatch.setattr(serving, "route", lambda *a, **kw: (
            lambda w, picks: (w, jnp.where(picks < 32 * HELD, picks % HELD, picks)))(*route(*a, **kw)))
    else:
        assert wrong == "sublayers_share_a_cache_row"
        shared_cache_row(monkeypatch)


def wrong_reference(wrong, params, ids, rows):
    """The reference's layer with one line read wrongly."""
    eps, pos = SIZES["rms_norm_eps"], jnp.arange(len(ids))
    norm = lambda x, gain: ref.rms_norm(x, gain, eps)
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(ids)]
        for l in range(SIZES["num_layers"]):
            w0, w1, gate = (jax.tree_util.tree_map(lambda a: a[l], w) for w in (
                layers["sub0"], layers["sub1"], layers["moe"]["gate"]))
            a0 = h + ref.mla(SIZES, w0["attn"], norm(h, w0["attn_norm"]), pos)
            u = norm(a0, w0["mlp_norm"])
            b0 = a0 + ref.swiglu(u, w0["mlp"])
            source = norm(b0, w1["attn_norm"]) if wrong == "shortcut_from_N_b0" else u
            held, identity, _ = ref.layer_parts(
                SIZES, {"gate": gate, "experts": layers["moe"]["experts"]}, source, layer=l)
            s = held + identity
            if wrong == "shortcut_added_after_sublayer_0":
                b0, s = b0 + s, 0.0
            a1 = b0 + ref.mla(SIZES, w1["attn"], norm(b0, w1["attn_norm"]), pos)
            h = a1 + ref.swiglu(norm(a1, w1["mlp_norm"]), w1["mlp"]) + s
        return np.asarray(norm(h, params["final_norm"])[jnp.asarray(rows)] @ params["lm_head"])



class TestLongcatFlash(ServingContract):
    family = FAMILY

    @pytest.fixture(scope="class")
    def sound(self, h):
        """``(ids, rows)``: what the sound program gives for the contract's prompt in
        chunks of (64, 64, 22) and three decode steps, traced before any case plants a fault."""
        ids, got, _ = h.prefilled((64, 64, 22))
        return ids, got

    def test_the_tallies_after_a_prefill_are_the_references_counts_of_the_same_tokens_picks(self, h, chunks):
        ids, _, cache = h.prefilled(chunks)
        counts = picks_of(h, tuple(ids))
        assert np.abs(np.asarray(cache[TALLY][:2]) - counts).max() <= 2  # a near-tie at the cut
        assert counts[0] > counts[1] > 0 and counts.sum() < len(ids) * TOPK * 2  # all three kinds occur

    def test_a_pass_that_holds_more_picks_than_its_window_runs_it_again_and_tallies_the_trips(self, h):
        """A selection bias sends two of every token's six picks to the two held
        experts: a chunk of 150 tokens holds 300 picks where a window has 128 rows,
        so each layer runs its window three times; no pick is dropped (the logits are
        the reference's) and the third tally counts the two trips beyond the first."""
        params, ids = h.params, h.ids_of(5, 151)
        bias = params["layers"]["moe"]["gate"]["bias"].at[:, :HELD].add(10.0)
        moe = params["layers"]["moe"]
        sent_here = {**params, "layers": {**params["layers"],
                                          "moe": {**moe, "gate": {**moe["gate"], "bias": bias}}}}
        got, cache = h.chunks_then_decode(ids, (150, ), decode=1, params=sent_here)
        for (_, row), w in zip(got, h.want(ids, [at for at, _ in got], params=sent_here)):
            h.close(row, w)
        assert serving.expert_rows(256, TOPK, HELD, 32 * HELD + ZERO) == 128  # the chunk's bucket
        identity, held, beyond = np.asarray(cache[TALLY]).tolist()
        assert held == 151 * HELD * 2 and beyond == (serving.window_trips(150 * HELD, 128) - 1) * 2 == 4

    def test_a_compacted_pass_tallies_what_the_padded_pass_does(self, h):
        """The dead slots of the flat axis add nothing and are not tallied."""
        f, m = self.family, h.mixed
        mixed, after = h.step(m.cache, m.rows, f.mixed_slots_a_row, bound=f.mixed_bound)
        padded, after_padded = h.step(m.cache, m.rows, f.mixed_slots_a_row)
        np.testing.assert_array_equal(np.asarray(after[TALLY]), np.asarray(after_padded[TALLY]))
        for got, wanted in zip(mixed, padded):
            h.close(got, wanted)

    @pytest.mark.parametrize("wrong", [
        "no_lora_scale", "k_pe_scaled_too", "weights_renormalised", "bias_in_the_weights",
        "identity_pick_adds_zero", "a_pick_held_elsewhere_lands_on_a_held_expert",
        "sublayers_share_a_cache_row", "shortcut_from_N_b0", "shortcut_added_after_sublayer_0"])
    def test_each_wrong_reading_of_the_architecture_fails_the_tolerance(self, wrong, h, sound, monkeypatch):
        ids, served = sound
        rows = [at for at, _ in served]
        if wrong.startswith("shortcut"):  # the program stands; the reference reads the layer wrongly
            got, wanted = served, wrong_reference(wrong, h.params, ids, rows)
            right = wrong_reference(None, h.params, ids, rows)  # the copy itself is the reference
            assert max(h.error(row, w) for (_, row), w in zip(served, right)) < REL_TOL
        else:
            wrong_program(wrong, monkeypatch)
            got, _ = h.chunks_then_decode(ids, (64, 64, 22), FAMILY.decode_steps, forward=h.jitted())
            wanted = h.want(ids, rows)
        assert max(h.error(row, w) for (_, row), w in zip(got, wanted)) > 100 * REL_TOL

    def test_the_tallies_are_window_deltas_and_wrap_around(self, h):
        eng = h.served
        eng.generate([h.ids_of(20, 30)], max_new_tokens=3)
        first = eng.counters.snapshot()
        eng.generate([h.ids_of(21, 40)], max_new_tokens=3)
        delta = eng.counters.delta_since(first)
        assert tallied_within(delta, *reference_counts(h, [h.greedy(h.ids_of(21, 40), 3)]))
        c = ServeCounters(tallied=ServeCounters.TALLIED_FIELDS)
        c.absorb_tallies(np.asarray([2 ** 31 - 5, 7, 0], np.int32))
        c.absorb_tallies(np.asarray([-2 ** 31 + 10, 9, 1], np.int32))  # the device's int32 wrapped
        assert (c.moe_identity_picks, c.moe_held_picks, c.moe_overflow_windows) == (2 ** 31 - 5 + 15, 9, 1)
        assert "moe_identity_picks" not in ServeCounters().snapshot()

    def test_the_fast_paths_tallies_are_a_few_tokens_more_than_the_oracles(self, h):
        """A step launched before the last token was known done, and a burst's padded
        row (every row of a burst holds one token as the program sees it)."""
        fast, slow = h.twins.fast, h.twins.slow
        assert 0 < slow["moe_identity_picks"] <= fast["moe_identity_picks"]
        assert fast["moe_identity_picks"] < 1.1 * slow["moe_identity_picks"]

    def test_a_shared_prefix_block_holds_both_sublayers_rows_and_a_copy_moves_no_tally(self, h):
        head = h.ids_of(70, 64)
        prompts = [head + h.ids_of(71 + i, 20 + 7 * i) for i in range(2)]
        eng = h.served
        hits = eng.health()["prefix_cache"]["hits_total"]
        got = eng.generate(prompts, max_new_tokens=3)
        assert eng.health()["prefix_cache"]["hits_total"] - hits >= 64 // 8 - 1
        for p, g in zip(prompts, got):
            assert list(g) == h.greedy(p, 3)
        before = jax.tree_util.tree_map(np.asarray, eng.kv)
        eng._cow_copy_block(0, 50)
        np.testing.assert_array_equal(np.asarray(eng.kv["latent"][:, 50]), before["latent"][:, 0])
        assert np.abs(before["latent"][:, 0]).min(axis=(1, 2, 3)).shape == (4, )
        assert all(np.abs(before["latent"][row, 0]).max() > 0 for row in range(4))  # 2 rows a layer
        np.testing.assert_array_equal(np.asarray(eng.kv[TALLY]), before[TALLY])
        eng.check_kv_invariant()

    def test_the_expert_layer_is_this_chips_share_with_the_identity_part(self, h):
        """``sparse_moe_ffn`` against the reference's ``layer_parts`` for chip 0:
        softmax over 64 + 32 outputs with a selection bias, 2 experts held, picks
        elsewhere add nothing, identity picks add ``w u``, nothing renormalised,
        times 6; dead slots add nothing and are not tallied."""
        params = h.params
        moe = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
        experts = params["layers"]["moe"]["experts"]
        x = jax.random.normal(jax.random.PRNGKey(9), (37, 64))
        live = jnp.arange(37) < 30
        with jax.default_matmul_precision("highest"):
            got, tally = serving.sparse_moe_ffn({"gate": moe["gate"], "experts": experts}, x, TOPK,
                                                False, live, layer=jnp.int32(0), scaling=6,
                                                identity_experts=ZERO)
            held, identity, counts = ref.layer_parts(SIZES, {**moe, "experts": experts}, x[:30],
                                                     layer=0)
        np.testing.assert_allclose(np.asarray(got[:30]), np.asarray(held + identity), atol=2e-5, rtol=0)
        assert float(jnp.abs(identity).max()) > 100 * 2e-5 and float(jnp.abs(held).max()) > 100 * 2e-5
        assert not np.asarray(got[30:]).any()
        np.testing.assert_array_equal(np.asarray(tally), np.asarray(counts))


def test_config_from_hf_reads_the_published_keys():
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "LongcatFlashConfig"):
        pytest.skip("the installed transformers has no longcat_flash")
    cfg = longcat_flash.config_from_hf(transformers.LongcatFlashConfig())
    assert cfg == longcat_flash.LongcatFlashConfig()
    with pytest.raises(ValueError, match="rope_scaling"):
        longcat_flash.config_from_hf(transformers.LongcatFlashConfig(
            rope_scaling={"rope_type": "linear", "factor": 2.0}))
