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
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import longcat_flash as ref
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import longcat_flash, transformer
from deepspeed_tpu.models.transformer import TALLY
from deepspeed_tpu.moe import serving
from tests.unit.inference.scenario import launches_of

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
REL_TOL = 3e-4  # of logits, as a share of the largest (``close``): sound reads under 2e-5


@pytest.fixture(scope="module")
def params():
    drawn = jax.jit(lambda k: ref.init_params(SIZES, k, jnp.float32))(jax.random.PRNGKey(7))
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 64))

    def off_neutral(path, leaf):  # a gain or a bias of the wrong kind or place must show
        names = [getattr(p, "key", None) for p in path]
        if any(n in ("attn_norm", "mlp_norm", "q_norm", "kv_norm", "final_norm") for n in names):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        if "bias" in names:  # the router's: large enough to move picks (scores are about 0.01)
            return 0.01 * jax.random.normal(next(keys), leaf.shape)
        if names[-1] == "w_down" and "experts" in names:  # a held pick that weighs as an identity one
            return leaf * (SIZES["routed_scaling_factor"] * TOPK)
        return leaf
    return jax.tree_util.tree_map_with_path(off_neutral, drawn)


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n).tolist()


def want(params, ids, rows, sizes=SIZES):
    return np.asarray(ref.logits_rows(sizes, params, ids, rows))


def error(got, wanted):
    return float(np.abs(np.asarray(got) - wanted).max() / np.abs(wanted).max())


def close(got, wanted):
    assert error(got, wanted) < REL_TOL


def fresh_cache(cfg=CFG):
    return longcat_flash.init_paged_cache(cfg, NB, BS, dtype=jnp.float32)


def forward_of(cfg=CFG):
    """A jitted forward traced now: a planted fault is what the trace finds."""
    return jax.jit(functools.partial(longcat_flash.forward_paged, cfg),
                   static_argnames=("block_size", "live_token_bound"))


def step(forward, params, cache, rows, t, bound=None):
    """One forward over ``rows`` = [(tokens, start_pos, blocks)]; returns
    (logits at each row's last token, cache).  Rows are padded to a power of two."""
    n = 1 << (len(rows) - 1).bit_length()
    tokens, counts = np.zeros((n, t), np.int32), np.zeros(n, np.int32)
    starts, tables = np.zeros(n, np.int32), np.full((n, MAXB), NB - 1, np.int32)
    for i, (toks, start, blocks) in enumerate(rows):
        tokens[i, :len(toks)], counts[i], starts[i] = toks, len(toks), start
        tables[i, :len(blocks)] = blocks
    logits, cache = forward(params, jnp.asarray(tokens), jnp.asarray(counts), jnp.asarray(starts),
                            jnp.asarray(tables), cache, block_size=BS, live_token_bound=bound)
    return [np.asarray(logits[i, len(r[0]) - 1]) for i, r in enumerate(rows)], cache


def chunks_then_decode(forward, params, ids, chunks, decode=3, cache=None):
    """The logits that end each chunk and each decode step, and the cache."""
    blocks, cache, at, got = list(range(3, 3 + 40)), cache or fresh_cache(), 0, []
    for size in chunks:
        (row, ), cache = step(forward, params, cache, [(ids[at:at + size], at, blocks)],
                              t=1 << (size - 1).bit_length())
        at += size
        got.append((at - 1, row))
    for _ in range(decode):
        (row, ), cache = step(forward, params, cache, [(ids[at:at + 1], at, blocks)], t=1)
        at += 1
        got.append((at - 1, row))
    return got, cache


@pytest.fixture(scope="module")
def sound(params):
    """``(forward, ids, rows)``: the sound program, traced before any case plants
    a fault and compiled once a shape for every case that serves through it, and
    what it gives for ``ids`` in chunks of (64, 64, 22) and three decode steps."""
    forward, ids = forward_of(), ids_of(1, 150 + 3)
    return forward, ids, chunks_then_decode(forward, params, ids, (64, 64, 22))[0]


def test_the_layout_is_one_latent_leaf_of_two_rows_a_layer_and_the_tallies(params):
    own = longcat_flash.init_params(CFG, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(own)] == \
        [a.shape for a in jax.tree_util.tree_leaves(params)]
    assert own["layers"]["moe"]["experts"]["w_gate"].shape[:2] == (2, HELD)
    assert own["layers"]["moe"]["gate"]["wg"].shape[-1] == 32 * HELD + ZERO  # the router's width
    cache = fresh_cache()
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


@pytest.mark.parametrize("chunks", [(150, ), (64, 64, 22), (1, 70, 79), (5, 131, 1, 2, 11)],
                         ids=lambda c: "x".join(map(str, c)))
def test_prefill_in_chunks_then_decode_steps_equal_the_reference(params, sound, chunks):
    """A later chunk attends what an earlier chunk wrote to BOTH of a layer's
    pool rows; a decode step is a chunk of one; the tallies are the reference's
    counts of the same tokens' picks."""
    forward, ids, _ = sound
    got, cache = chunks_then_decode(forward, params, ids, chunks)
    wanted = want(params, ids + [0] * 7, [at for at, _ in got])
    for (at, row), w in zip(got, wanted):
        close(row, w)
    with jax.default_matmul_precision("highest"):
        _, counts = ref.hidden_states(SIZES, params, jnp.asarray(ids))
    assert np.abs(np.asarray(cache[TALLY][:2]) - np.asarray(counts)).max() <= 2  # a near-tie at the cut
    assert counts[0] > counts[1] > 0 and counts.sum() < len(ids) * TOPK * 2  # all three kinds occur


def test_a_pass_that_holds_more_picks_than_its_window_runs_it_again_and_tallies_the_trips(params, sound):
    """A selection bias sends two of every token's six picks to the two held
    experts: a chunk of 150 tokens holds 300 picks where a window has 128 rows,
    so each layer runs its window three times; no pick is dropped (the logits are
    the reference's) and the third tally counts the two trips beyond the first."""
    forward, ids = sound[0], ids_of(5, 151)
    bias = params["layers"]["moe"]["gate"]["bias"].at[:, :HELD].add(10.0)
    moe = params["layers"]["moe"]
    sent_here = {**params, "layers": {**params["layers"],
                                      "moe": {**moe, "gate": {**moe["gate"], "bias": bias}}}}
    got, cache = chunks_then_decode(forward, sent_here, ids, (150, ), decode=1)
    for (at, row), w in zip(got, want(sent_here, ids + [0] * 7, [at for at, _ in got])):
        close(row, w)
    assert serving.expert_rows(256, TOPK, HELD, 32 * HELD + ZERO) == 128  # the chunk's bucket
    identity, held, beyond = np.asarray(cache[TALLY]).tolist()
    assert held == 151 * HELD * 2 and beyond == (serving.window_trips(150 * HELD, 128) - 1) * 2 == 4


def test_a_compacted_mixed_step_gives_each_sequence_what_it_gets_alone(params, sound):
    """Two chunks and a decode row of three sequences on the flat [1, S] axis:
    the shortcut is handed on slot by slot, the dead slots add nothing and are
    not tallied."""
    forward = sound[0]
    seqs = [(ids_of(2, 160), list(range(0, 41))), (ids_of(3, 80), list(range(41, 61))),
            (ids_of(4, 40), list(range(61, 71)))]  # block 71 is the trash block
    heads = (70, 5, 39)
    cache = fresh_cache()
    for (ids, blocks), done in zip(seqs, heads):
        _, cache = step(forward, params, cache, [(ids[:done], 0, blocks)], t=128)
    rows = [(seqs[0][0][70:160], 70, seqs[0][1]), (seqs[1][0][5:80], 5, seqs[1][1]),
            (seqs[2][0][39:40], 39, seqs[2][1])]
    mixed, after = step(forward, params, cache, rows, t=128, bound=176)  # 512 slots > 176: compacted
    padded, after_padded = step(forward, params, cache, rows, t=128)
    np.testing.assert_array_equal(np.asarray(after[TALLY]), np.asarray(after_padded[TALLY]))
    for i, r in enumerate(rows):
        close(mixed[i], padded[i])
        close(mixed[i], want(params, seqs[i][0], [r[1] + len(r[0]) - 1])[0])


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


@pytest.mark.parametrize("wrong", [
    "no_lora_scale", "k_pe_scaled_too", "weights_renormalised", "bias_in_the_weights",
    "identity_pick_adds_zero", "a_pick_held_elsewhere_lands_on_a_held_expert",
    "sublayers_share_a_cache_row", "shortcut_from_N_b0", "shortcut_added_after_sublayer_0"])
def test_each_wrong_reading_of_the_architecture_fails_the_tolerance(wrong, params, sound,
                                                                   monkeypatch):
    _, ids, served = sound
    rows = [at for at, _ in served]
    if wrong.startswith("shortcut"):  # the program stands; the reference reads the layer wrongly
        got, wanted = served, wrong_reference(wrong, params, ids, rows)
        right = wrong_reference(None, params, ids, rows)  # the copy itself is the reference
        assert max(error(row, w) for (_, row), w in zip(served, right)) < REL_TOL
    else:
        wrong_program(wrong, monkeypatch)
        got, _ = chunks_then_decode(forward_of(), params, ids, (64, 64, 22))
        wanted = want(params, ids + [0] * 7, rows)
    assert max(error(row, w) for (_, row), w in zip(got, wanted)) > 100 * REL_TOL


# ----------------------------------------------------------- through the engine
def build_engine(params, fast=True, budget=32, **sections):
    conf = {"dtype": "float32", **sections}
    if not fast:
        conf["serving_fastpath"] = {"enabled": False}
    return InferenceEngineV2(longcat_flash, CFG, params, config=conf, num_blocks=96, block_size=8,
                             max_blocks_per_seq=24, token_budget=budget, max_seqs_per_step=4)


@pytest.fixture(scope="module")
def engine(params):
    """``engine(fast=True, budget=32)``: one engine a configuration, built when
    first asked for.  A drained engine replays a wave step for step, so a case
    serves through it and reads tokens, and counters as deltas; a case that
    changes the engine it is handed, or its sections, takes ``build_engine``."""
    made = {}

    def get(fast=True, budget=32):
        if (fast, budget) not in made:
            made[fast, budget] = build_engine(params, fast, budget)
        return made[fast, budget]
    return get


GREEDY = {}  # (prompt, new) -> the reference's continuation: ``params`` is the module's one draw


def greedy(params, prompt, new):
    if (tuple(prompt), new) not in GREEDY:
        ids = list(prompt)
        for _ in range(new):
            ids.append(int(np.argmax(want(params, ids + [0] * (-len(ids) % 16), [len(ids) - 1])[0])))
        GREEDY[tuple(prompt), new] = ids
    return list(GREEDY[tuple(prompt), new])


def reference_counts(params, sequences):
    """``(least, most)`` picks on identity and on held experts of the tokens that
    went through a forward pass: all of a served sequence but its last token,
    and the last too where the loop had launched the next step before it knew
    the sequence was done (``live_tokens`` counts that token as well)."""
    least, most = np.zeros(2, np.int64), np.zeros(2, np.int64)
    for ids in sequences:
        least += picks_of(params, tuple(ids[:-1]))
        most += picks_of(params, tuple(ids))
    return least, most


PICKS = {}  # a sequence -> the reference's counts of its picks, worked out once


def picks_of(params, ids):
    if ids not in PICKS:
        with jax.default_matmul_precision("highest"):
            PICKS[ids] = np.asarray(ref.hidden_states(SIZES, params, jnp.asarray(ids))[1])
    return PICKS[ids]


def tallied_within(counters, least, most, slack=2):
    """A near-tie at the router's cut may fall either way between the program
    and the reference: a pick or two of thousands."""
    got = np.asarray([counters["moe_identity_picks"], counters["moe_held_picks"]])
    return bool(((least - slack <= got) & (got <= most + slack)).all())


@pytest.mark.parametrize("budget", [32, 48])
def test_generate_through_chunks_and_the_fused_burst_is_the_references_greedy(params, engine, budget,
                                                                              monkeypatch):
    """Two ``token_budget``s cut a prompt at different places; the tokens are the
    reference's either way, through compacted passes and fused bursts, and the
    tallies read once a wave are the reference's counts."""
    prompts = [ids_of(10 + i, n) for i, n in enumerate((5, 90, 140, 9))]
    eng = engine(budget=budget)
    before, launched = eng.counters.snapshot(), launches_of(eng, monkeypatch)
    got = eng.generate(prompts, max_new_tokens=5)
    c = eng.counters.delta_since(before)
    assert c["burst_tokens"] > 0 and c["compact_passes"] > 0
    for p, g in zip(prompts, got):
        assert list(g) == greedy(params, p, 5)
    assert c["moe_routed_rows"] == c["live_tokens"] * TOPK * 2  # every pick, in each layer's one expert layer
    assert tallied_within(c, *reference_counts(params, [list(g) for g in got]))
    assert 0 < c["moe_held_picks"] < c["moe_identity_picks"]
    assert c["moe_identity_picks"] < c["moe_routed_rows"] - c["moe_identity_picks"] - c["moe_held_picks"]
    # the rows are the window of held picks (2 of 96 outputs, with headroom) a layer a pass; a
    # pass that holds more runs its window again, tallied: nothing is dropped for want of rows
    window = longcat_flash.moe_expert_rows(CFG, budget) // 2
    assert window == serving.expert_rows(budget, TOPK, HELD, 32 * HELD + ZERO) == 128
    assert c["moe_held_picks"] <= c["moe_expert_rows"] + window * c["moe_overflow_windows"]
    assert c["moe_expert_rows"] < c["moe_routed_rows"] and c["moe_overflow_windows"] >= 0
    assert c["moe_expert_rows"] == sum(
        longcat_flash.moe_expert_rows(CFG, slots) * passes for slots, passes in launched)
    assert set(c) == set(eng.counters.FIELDS + eng.counters.TALLIED_FIELDS)
    # one fetch a wave beyond the steps' and the bursts' own
    other = build_engine(params, budget=budget)
    other.counters.tallied = None
    other.generate(prompts, max_new_tokens=5)
    assert c["host_syncs"] == other.counters.host_syncs + 1
    eng.check_kv_invariant()


def test_the_tallies_are_window_deltas_and_wrap_around(params, engine):
    eng = engine()
    eng.generate([ids_of(20, 30)], max_new_tokens=3)
    first = eng.counters.snapshot()
    eng.generate([ids_of(21, 40)], max_new_tokens=3)
    delta = eng.counters.delta_since(first)
    assert tallied_within(delta, *reference_counts(params, [greedy(params, ids_of(21, 40), 3)]))
    from deepspeed_tpu.inference.v2.fastpath import ServeCounters
    c = ServeCounters(tallied=ServeCounters.TALLIED_FIELDS)
    c.absorb_tallies(np.asarray([2 ** 31 - 5, 7, 0], np.int32))
    c.absorb_tallies(np.asarray([-2 ** 31 + 10, 9, 1], np.int32))  # the device's int32 wrapped
    assert (c.moe_identity_picks, c.moe_held_picks, c.moe_overflow_windows) == (2 ** 31 - 5 + 15, 9, 1)
    assert "moe_identity_picks" not in ServeCounters().snapshot()


def test_the_fast_path_and_the_padded_oracle_serve_the_same_tokens(engine):
    prompts = [ids_of(50 + i, n) for i, n in enumerate((33, 7, 81))]
    fast, slow = engine(), engine(fast=False)
    before = fast.counters.snapshot(), slow.counters.snapshot()
    assert [list(g) for g in fast.generate(prompts, max_new_tokens=3)] == \
        [list(g) for g in slow.generate(prompts, max_new_tokens=3)]
    fast, slow = fast.counters.delta_since(before[0]), slow.counters.delta_since(before[1])
    assert slow["compact_passes"] == 0 < fast["compact_passes"]
    # the fast path's are a few tokens more: a step launched before the last token was known
    # done, and a burst's padded row (every row of a burst holds one token as the program sees it)
    assert 0 < slow["moe_identity_picks"] <= fast["moe_identity_picks"]
    assert fast["moe_identity_picks"] < 1.1 * slow["moe_identity_picks"]


def test_a_shared_prefix_block_holds_both_sublayers_rows_and_a_copy_moves_no_tally(params, engine):
    head = ids_of(70, 64)
    prompts = [head + ids_of(71 + i, 20 + 7 * i) for i in range(2)]
    eng = engine()
    hits = eng.health()["prefix_cache"]["hits_total"]
    got = eng.generate(prompts, max_new_tokens=3)
    assert eng.health()["prefix_cache"]["hits_total"] - hits >= 64 // 8 - 1
    for p, g in zip(prompts, got):
        assert list(g) == greedy(params, p, 3)
    before = jax.tree_util.tree_map(np.asarray, eng.kv)
    eng._cow_copy_block(0, 50)
    np.testing.assert_array_equal(np.asarray(eng.kv["latent"][:, 50]), before["latent"][:, 0])
    assert np.abs(before["latent"][:, 0]).min(axis=(1, 2, 3)).shape == (4, )
    assert all(np.abs(before["latent"][row, 0]).max() > 0 for row in range(4))  # 2 rows a layer
    np.testing.assert_array_equal(np.asarray(eng.kv[TALLY]), before[TALLY])
    eng.check_kv_invariant()


def test_speculative_decoding_serves_the_same_tokens_and_tensor_parallelism_is_refused(params, engine):
    prompt = ids_of(40, 60)
    plain = engine().generate([prompt], max_new_tokens=6)[0]
    spec = build_engine(params, serving_spec_decode={"enabled": True, "k": 3})
    assert list(spec.generate([prompt], max_new_tokens=6)[0]) == list(plain)
    assert spec.counters.spec_rounds > 0
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        longcat_flash.forward_paged(CFG, params, None, None, None, None, fresh_cache(),
                                    block_size=BS, tp_axis="tensor")


# ------------------------------------------------------------------ the experts
def test_the_expert_layer_is_this_chips_share_with_the_identity_part(params):
    """``sparse_moe_ffn`` against the reference's ``layer_parts`` for chip 0:
    softmax over 64 + 32 outputs with a selection bias, 2 experts held, picks
    elsewhere add nothing, identity picks add ``w u``, nothing renormalised,
    times 6; dead slots add nothing and are not tallied."""
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
