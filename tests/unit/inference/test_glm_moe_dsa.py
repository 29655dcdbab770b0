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
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import glm_moe_dsa as ref
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import glm_moe_dsa
from deepspeed_tpu.moe.serving import sparse_moe_ffn
from deepspeed_tpu.ops.attention import paged

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
REL_TOL = 3e-4  # of logits, as a share of the largest (``close``)


@pytest.fixture(scope="module")
def params():
    drawn = jax.jit(lambda k: ref.init_params(SIZES, k, jnp.float32))(jax.random.PRNGKey(7))
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 128))

    def off_neutral(path, leaf):  # a gain, a bias or a norm of the wrong kind or place must show
        names = [getattr(p, "key", None) for p in path]
        if any(n in ("attn_norm", "mlp_norm", "q_norm", "kv_norm", "k_norm", "final_norm")
               for n in names):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        if "k_norm_bias" in names:
            return leaf + 0.5 * jax.random.normal(next(keys), leaf.shape)
        if "weights" in names:  # head weights of either sign (the drawn ones are one positive number)
            return jax.random.normal(next(keys), leaf.shape) * leaf.shape[-2] ** -0.5
        if "bias" in names:  # the router's: large enough to move picks
            return 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(off_neutral, drawn)


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n).tolist()


def want(params, ids, rows, sizes=SIZES):
    return np.asarray(ref.logits_rows(sizes, params, ids, rows))


def close(got, wanted):
    np.testing.assert_allclose(got, wanted, atol=REL_TOL * np.abs(wanted).max(), rtol=0)


def fresh_cache():
    return glm_moe_dsa.init_paged_cache(CFG, NB, BS, dtype=jnp.float32)


FORWARD = jax.jit(functools.partial(glm_moe_dsa.forward_paged, CFG),
                  static_argnames=("block_size", "live_token_bound"))


def step(params, cache, rows, t, bound=None):
    """One forward over ``rows`` = [(tokens, start_pos, blocks)]; returns
    (logits at each row's last token, cache).  Rows are padded to a power of two."""
    n = 1 << (len(rows) - 1).bit_length()
    tokens, counts = np.zeros((n, t), np.int32), np.zeros(n, np.int32)
    starts, tables = np.zeros(n, np.int32), np.full((n, MAXB), NB - 1, np.int32)
    for i, (toks, start, blocks) in enumerate(rows):
        tokens[i, :len(toks)], counts[i], starts[i] = toks, len(toks), start
        tables[i, :len(blocks)] = blocks
    logits, cache = FORWARD(params, jnp.asarray(tokens), jnp.asarray(counts), jnp.asarray(starts),
                            jnp.asarray(tables), cache, block_size=BS, live_token_bound=bound)
    return [np.asarray(logits[i, len(r[0]) - 1]) for i, r in enumerate(rows)], cache


def test_the_layout_is_two_pool_leaves_of_unlike_widths(params):
    own = glm_moe_dsa.init_params(CFG, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(own)] == \
        [a.shape for a in jax.tree_util.tree_leaves(params)]
    assert own["layers"]["moe"]["experts"]["w_gate"].shape[:2] == (1, HELD)
    assert own["layers"]["moe"]["gate"]["wg"].shape[-1] == 16 * HELD  # the router's width
    cache = fresh_cache()
    assert cache["latent"].shape == (2, NB, 1, BS, 128)  # 32 + 8 values in whole lanes
    assert cache[glm_moe_dsa.PAGED_SELECT_LEAF].shape == (2, NB, 1, BS, 16)
    full = glm_moe_dsa.GlmMoeDsaConfig()
    whole = jax.eval_shape(lambda: glm_moe_dsa.init_paged_cache(full, 8, 128))
    assert {k: v.shape[-1] for k, v in whole.items()} == {"latent": 640, "index_keys": 128}
    assert glm_moe_dsa.selected_keys(full) == (2048, 78) and full.rope_theta == 1e6
    assert glm_moe_dsa.paged_value_dim(full) == 512
    with pytest.raises(ValueError, match="rope_type"):
        glm_moe_dsa.GlmMoeDsaConfig(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"})


@pytest.mark.parametrize("chunks", [(150, ), (64, 64, 22), (1, 70, 79), (5, 131, 1, 2, 11)],
                         ids=lambda c: "x".join(map(str, c)))
def test_prefill_in_chunks_then_decode_steps_equal_the_reference(params, chunks):
    """A later chunk scores the index keys an earlier chunk wrote to the pool
    and its own alike; a token under ``index_topk`` attends all of its past, one
    over it the reference's ``S_t``; a decode step is a chunk of one."""
    ids = ids_of(1, 150 + 3)
    blocks, cache, at = list(range(3, 3 + 40)), fresh_cache(), 0
    for size in chunks:
        (got, ), cache = step(params, cache, [(ids[at:at + size], at, blocks)],
                              t=1 << (size - 1).bit_length())
        at += size
        close(got, want(params, ids, [at - 1])[0])
    for _ in range(3):  # decode by single steps
        (got, ), cache = step(params, cache, [(ids[at:at + 1], at, blocks)], t=1)
        at += 1
        close(got, want(params, ids, [at - 1])[0])


def test_a_compacted_mixed_step_gives_each_sequence_what_it_gets_alone(params):
    """Two chunks and a decode row of three sequences on the flat [1, S] axis:
    each token's scores run over its own sequence's blocks, and a decode row
    riding in a chunk's bucket selects as it does alone."""
    seqs = [(ids_of(2, 160), list(range(0, 41))), (ids_of(3, 80), list(range(41, 61))),
            (ids_of(4, 40), list(range(61, 71)))]  # block 71 is the trash block
    heads = (70, 5, 39)  # tokens already in the cache: two chunks continue, one row decodes
    cache = fresh_cache()
    for (ids, blocks), done in zip(seqs, heads):
        _, cache = step(params, cache, [(ids[:done], 0, blocks)], t=128)
    rows = [(seqs[0][0][70:160], 70, seqs[0][1]), (seqs[1][0][5:80], 5, seqs[1][1]),
            (seqs[2][0][39:40], 39, seqs[2][1])]
    mixed, _ = step(params, cache, rows, t=128, bound=176)  # [4, 128] = 512 slots > 176: compacted
    for i, r in enumerate(rows):
        (alone, ), _ = step(params, cache, [r], t=128)
        close(mixed[i], alone)
        close(mixed[i], want(params, seqs[i][0], [r[1] + len(r[0]) - 1])[0])


@pytest.mark.parametrize("tied", [False, True], ids=["drawn", "every_score_tied"])
def test_the_selected_sets_are_the_references(params, monkeypatch, tied):
    """The program's selection of layer 0, read where the kernel is handed it,
    is the reference's ``S_t`` position for position: rows under ``index_topk``
    keep all of their past, rows over it the top 16; with the head weights zero
    every score is one (plus or minus) zero and the rule of the lower position
    alone decides: the first 16 positions."""
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
    ids = ids_of(5, 60)
    logits, _ = glm_moe_dsa.forward_paged(
        CFG, params, jnp.asarray([ids + [0] * 4]), jnp.asarray([60]), jnp.asarray([0]),
        jnp.asarray([list(range(16)) + [NB - 1] * (MAXB - 16)]), fresh_cache(), block_size=BS)
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


# ----------------------------------------------------------- through the engine
def build_engine(params, fast=True, budget=32, **sections):
    conf = {"dtype": "float32", **sections}
    if not fast:
        conf["serving_fastpath"] = {"enabled": False}
    return InferenceEngineV2(glm_moe_dsa, CFG, params, config=conf, num_blocks=96, block_size=8,
                             max_blocks_per_seq=24, token_budget=budget, max_seqs_per_step=4)


@pytest.fixture(scope="module")
def engine(params):
    """``engine(fast=True, budget=32)``: one engine a configuration, built when
    first asked for.  A drained engine replays a wave step for step, so a case
    serves through it and reads tokens, and counters as deltas; a case that
    reaches into the manager, or brings sections, takes ``build_engine``."""
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


@pytest.mark.parametrize("budget", [32, 48])
def test_generate_through_chunks_and_the_fused_burst_is_the_references_greedy(params, engine, budget):
    """Two ``token_budget``s cut a prompt at different places; the tokens are
    the reference's either way, through compacted passes and fused bursts."""
    prompts = [ids_of(10 + i, n) for i, n in enumerate((5, 90, 140, 9))]
    eng = engine(budget=budget)
    before = eng.counters.snapshot()
    got = eng.generate(prompts, max_new_tokens=5)
    c = eng.counters.delta_since(before)
    assert c["burst_tokens"] > 0 and c["compact_passes"] > 0
    for p, g in list(zip(prompts, got))[:3]:  # one decode-only, one cut in three, one in five
        assert list(g) == greedy(params, p, 5)
    assert c["moe_routed_rows"] == c["live_tokens"] * 4  # k picks in the one expert layer
    # the selection's counters: every live token, in each of the two layers
    assert 0 < c["dsa_selected_keys"] < c["dsa_causal_keys"] <= c["dsa_scored_keys"]
    assert c["dsa_selected_keys"] <= c["dsa_attended_keys"] >= c["dsa_causal_keys"]
    assert c["dsa_selected_keys"] <= c["live_tokens"] * TOPK * 2
    eng.check_kv_invariant()


def test_the_fast_path_and_the_padded_oracle_serve_the_same_tokens(engine):
    prompts = [ids_of(50 + i, n) for i, n in enumerate((33, 7, 81))]
    fast, slow = engine(), engine(fast=False)
    before = fast.counters.snapshot(), slow.counters.snapshot()
    assert [list(g) for g in fast.generate(prompts, max_new_tokens=3)] == \
        [list(g) for g in slow.generate(prompts, max_new_tokens=3)]
    fast, slow = fast.counters.delta_since(before[0]), slow.counters.delta_since(before[1])
    assert slow["compact_passes"] == 0 < fast["compact_passes"]
    for name in ("dsa_causal_keys", "dsa_selected_keys"):
        # the traffic's, whatever the layout; a burst's last passes may run past a sequence's end
        assert fast[name] >= slow[name] > 0


def test_the_counters_are_the_sums_over_positions(engine):
    eng = engine()
    before = eng.counters.snapshot()
    eng.generate([ids_of(60, 50)], max_new_tokens=3)
    positions = np.arange(50 + 3 - 1)  # every token that went through a forward pass
    c = eng.counters.delta_since(before)
    assert c["dsa_causal_keys"] == 2 * int((positions + 1).sum())
    assert c["dsa_selected_keys"] == 2 * int(np.minimum(positions + 1, TOPK).sum())


def test_index_keys_in_a_shared_prefix_block_are_the_ones_a_later_prompt_scores(params, engine):
    """Prompts of one wave share a header of whole blocks: the later ones take
    the first's blocks (both leaves of them: a block is a block) and score the
    index keys they find there."""
    head = ids_of(70, 64)
    prompts = [head + ids_of(71 + i, 20 + 7 * i) for i in range(2)]
    eng = engine()
    hits = eng.health()["prefix_cache"]["hits_total"]
    got = eng.generate(prompts, max_new_tokens=3)
    assert eng.health()["prefix_cache"]["hits_total"] - hits >= 64 // 8 - 1
    for p, g in zip(prompts, got):
        assert list(g) == greedy(params, p, 3)
    eng.check_kv_invariant()


def test_a_copied_block_carries_both_leaves(engine):
    eng = engine()
    eng.generate([ids_of(80, 40)], max_new_tokens=2)
    before = jax.tree_util.tree_map(np.asarray, eng.kv)
    eng._cow_copy_block(0, 50)
    for name, leaf in eng.kv.items():
        np.testing.assert_array_equal(np.asarray(leaf[:, 50]), before[name][:, 0])
        assert np.abs(before[name][:, 0]).max() > 0


def test_a_preempted_sequence_resumes_to_the_undisturbed_tokens(params):
    prompt = ids_of(30, 100)
    undisturbed = greedy(params, prompt, 5)
    eng = build_engine(params)
    eng.put([7], [prompt])
    for _ in range(2):
        eng.step()
    seq = eng.manager.seqs[7]
    eng.manager.preempt(seq, keep_blocks=4)  # index keys live in the kept blocks too
    assert seq.seen_tokens == 32 and len(seq.blocks) == 4
    out = []
    while len(out) < 5:
        out.extend(eng.step().values())
    assert prompt + out == list(undisturbed)


def test_speculative_decoding_serves_the_same_tokens_and_tensor_parallelism_is_refused(params, engine):
    """A rejected draft is rolled back by blocks, and the index keys live in
    those blocks: the verify path needs nothing of its own."""
    prompt = ids_of(40, 60)
    plain = engine().generate([prompt], max_new_tokens=6)[0]
    spec = build_engine(params, serving_spec_decode={"enabled": True, "k": 3})
    assert list(spec.generate([prompt], max_new_tokens=6)[0]) == list(plain)
    assert spec.counters.spec_rounds > 0
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        glm_moe_dsa.forward_paged(CFG, params, None, None, None, None, fresh_cache(), block_size=BS,
                                  tp_axis="tensor")


# ------------------------------------------------------------------ the experts
def test_the_expert_layer_is_this_chips_share_of_sixteen(params):
    """``sparse_moe_ffn`` against the reference's ``layer_parts`` for chip 0: a
    sigmoid router over 16 with a selection bias, 1 expert held, picks elsewhere
    add nothing, the picked scores renormalised and times 2.5."""
    moe = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    experts = params["layers"]["moe"]["experts"]
    x = jax.random.normal(jax.random.PRNGKey(9), (37, 64))
    with jax.default_matmul_precision("highest"):
        got = sparse_moe_ffn({"gate": moe["gate"], "shared": moe["shared"], "experts": experts}, x,
                             4, True, layer=jnp.int32(0), scaling=2.5, scoring="sigmoid",
                             norm_eps=1e-20)
        routed, shared = ref.layer_parts(SIZES, {**moe, "experts": experts}, x, layer=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(routed + shared), atol=2e-5, rtol=0)
