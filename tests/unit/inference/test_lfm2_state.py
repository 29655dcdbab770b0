"""LFM2 (ISSUE 33): gated short-convolution layers whose memory of a sequence
is a FIXED state in a slot beside the paged KV pool, attention in one layer of
four over a pool of packed heads, and a sigmoid, bias-corrected router.

The program (``models/lfm2.py`` on ``transformer.paged_forward``, through the
engine's scheduler, manager, bursts) against the plain reference
(``chipbench/references/lfm2.py``: whole sequences, no state, no cache) in
float32 at a size with both layer kinds, a dense layer and two whole periods.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import lfm2 as ref
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.ragged_manager import PrefixCache, RaggedStateManager
from deepspeed_tpu.models import lfm2
from deepspeed_tpu.models.transformer import STATE, sequence_filter
from deepspeed_tpu.moe.serving import route

TYPES = ["conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv"]
SIZES = {"hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
         "num_hidden_layers": 9, "num_dense_layers": 1, "layer_types": TYPES,
         "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 8,
         "num_experts_per_tok": 4, "norm_topk_prob": True, "use_expert_bias": True,
         "routed_scaling_factor": 1, "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
         "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "vocab_size": 256,
         "max_position_embeddings": 512}
CFG = lfm2.Lfm2Config(
    vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_layers=9,
    num_dense_layers=1, layer_types=TYPES, num_heads=4, num_kv_heads=2, num_experts=8, top_k=4,
    rope_parameters=SIZES["rope_parameters"], max_seq_len=512)
NB, BS, MAXB, SLOTS = 24, 4, 6, 4
TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return ref.init_params(SIZES, jax.random.PRNGKey(7), jnp.float32)


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n).tolist()


def want(params, ids, rows):
    return np.asarray(ref.logits_rows(SIZES, params, ids, rows))


def fresh_cache():
    return lfm2.init_paged_cache(CFG, NB, BS, dtype=jnp.float32, state_slots=SLOTS)


FORWARD = jax.jit(functools.partial(lfm2.forward_paged, CFG),
                  static_argnames=("block_size", "live_token_bound"))


def step(params, cache, rows, t, bound=None):
    """One forward over ``rows`` = [(tokens, start_pos, blocks, slot)]; returns
    (logits at each row's last token, cache).  Rows are padded to a power of two."""
    n = 1 << (len(rows) - 1).bit_length()
    tokens, counts = np.zeros((n, t), np.int32), np.zeros(n, np.int32)
    starts, tables = np.zeros(n, np.int32), np.full((n, MAXB + 1), NB - 1, np.int32)
    tables[:, -1] = SLOTS  # the trash slot
    for i, (toks, start, blocks, slot) in enumerate(rows):
        tokens[i, :len(toks)], counts[i], starts[i] = toks, len(toks), start
        tables[i, :len(blocks)], tables[i, -1] = blocks, slot
    logits, cache = FORWARD(params, jnp.asarray(tokens), jnp.asarray(counts), jnp.asarray(starts),
                            jnp.asarray(tables), cache, block_size=BS, live_token_bound=bound)
    return [np.asarray(logits[i, len(r[0]) - 1]) for i, r in enumerate(rows)], cache


def test_the_layout_is_the_layers_as_they_are_scanned(params):
    assert lfm2.layer_segments(CFG) == ref.segments(SIZES) == [(0, 1, 1), (1, 4, 2)]
    assert lfm2.layer_segments(lfm2.Lfm2Config()) == [(0, 1, 2), (2, 4, 9), (38, 1, 1), (39, 1, 1)]
    own = lfm2.init_params(CFG, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(own)] == \
        [a.shape for a in jax.tree_util.tree_leaves(params)]
    cache = fresh_cache()
    # attention layers alone in the pool, two KV heads of 16 a row; the conv layers' slots apart
    assert cache["k"].shape == cache["v"].shape == (2, NB, 1, BS, 32)
    assert cache[STATE].shape == (7, SLOTS + 1, 2, 64)
    assert lfm2.state_bytes_per_seq(CFG, 4) == 7 * 2 * 64 * 4
    full = lfm2.Lfm2Config()
    assert lfm2.kv_pack(full) == 2 and lfm2.state_bytes_per_seq(full) == 30 * 2 * 2048 * 2
    nine = lfm2.Lfm2Config(num_layers=10)  # the benchmark's cut: 2 dense + two periods
    assert lfm2.state_bytes_per_seq(nine) == 57344 + 8192 and nine.layer_types.count("conv") == 8


@pytest.mark.parametrize("chunks", [(11, ), (1, 10), (2, 9), (3, 8), (4, 1, 2, 3, 1)],
                         ids=lambda c: "x".join(map(str, c)))
def test_prefill_in_chunks_then_decode_steps_equal_the_reference(params, chunks):
    """A chunk's first tokens read the sequence's slot, not nothing; its end
    writes the slot back; a chunk of one token shifts it."""
    ids = ids_of(1, 11 + 3)
    blocks, slot, cache, at = [3, 9, 5, 11], 2, fresh_cache(), 0
    for size in chunks:
        (got, ), cache = step(params, cache, [(ids[at:at + size], at, blocks, slot)],
                              t=1 << (size - 1).bit_length())
        at += size
        np.testing.assert_allclose(got, want(params, ids, [at - 1])[0], atol=TOL, rtol=0)
    for _ in range(3):  # decode by single steps
        (got, ), cache = step(params, cache, [(ids[at:at + 1], at, blocks, slot)], t=1)
        at += 1
        np.testing.assert_allclose(got, want(params, ids, [at - 1])[0], atol=TOL, rtol=0)


def test_a_compacted_mixed_step_gives_each_sequence_what_it_gets_alone(params):
    """One chunk beside decode rows of other sequences on the flat [1, S] axis:
    nothing crosses a sequence boundary, in the shift or in the slots."""
    seqs = [(ids_of(2, 13), [1, 2, 3, 4], 0), (ids_of(3, 6), [5, 6], 3), (ids_of(4, 9), [7, 8, 10], 1)]
    heads = (5, 5, 8)  # tokens already in the cache: the chunk continues, the others decode
    cache = fresh_cache()
    for (ids, blocks, slot), done in zip(seqs, heads):
        _, cache = step(params, cache, [(ids[:done], 0, blocks, slot)], t=8)
    rows = [(seqs[0][0][5:11], 5, seqs[0][1], 0), (seqs[1][0][5:6], 5, seqs[1][1], 3),
            (seqs[2][0][8:9], 8, seqs[2][1], 1)]
    mixed, after = step(params, cache, rows, t=8, bound=8)  # [4, 8] = 32 slots > 8: compacted
    for i, r in enumerate(rows):
        (alone, ), single = step(params, cache, [r], t=8)
        np.testing.assert_allclose(mixed[i], alone, atol=TOL, rtol=0)
        ids = seqs[i][0]
        np.testing.assert_allclose(mixed[i], want(params, ids, [r[1] + len(r[0]) - 1])[0],
                                   atol=TOL, rtol=0)
        np.testing.assert_allclose(np.asarray(after[STATE][:, r[3]]),
                                   np.asarray(single[STATE][:, r[3]]), atol=TOL, rtol=0)
    # the slot no row named is untouched
    np.testing.assert_array_equal(np.asarray(after[STATE][:, 2]), np.asarray(cache[STATE][:, 2]))


@pytest.mark.parametrize("with_bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("layout", ["padded", "compacted"])
def test_the_shift_is_local_to_a_sequence_in_both_layouts(layout, k, with_bias):
    """``paged_forward``'s ``filtered`` against a filter over each sequence alone: a
    row of no token, of fewer tokens than ``k`` (at 2 and 3 taps), of exactly ``k``,
    of more, and a dead tail; ``out`` and ``last`` to the bit in float32."""
    rng = np.random.default_rng(k)
    counts, d, t = np.array([k, 0, 1, 5, 2, 0]), 4, 8
    n = len(counts)
    kept = rng.normal(size=(n, k, d)).astype(np.float32)
    chunk = rng.normal(size=(n, t, d)).astype(np.float32)
    w = rng.normal(size=(k + 1, d)).astype(np.float32)
    bias = rng.normal(size=(d, )).astype(np.float32) if with_bias else None
    if layout == "padded":
        z, places = chunk, (None, None)
        at = lambda a, r, c: np.asarray(a)[r, c]
    else:
        row = np.repeat(np.arange(n), counts)
        col = np.concatenate([np.arange(c) for c in counts])
        pad = 16 - len(row)
        # a dead slot is what ``flat_chunk_indices`` makes it: token [0, 0], row and column nought
        z = np.concatenate([chunk[row, col], np.tile(chunk[0, 0], (pad, 1))])[None]
        places = tuple(jnp.asarray(np.concatenate([a, np.zeros(pad, int)]))[None] for a in (row, col))
        starts = np.cumsum(counts) - counts
        at = lambda a, r, c: np.asarray(a)[0, starts[r] + c]
    out, last = sequence_filter(jnp.asarray(z), jnp.asarray(kept), jnp.asarray(w),
                                None if bias is None else jnp.asarray(bias), jnp.asarray(counts), *places)
    assert out.dtype == jnp.float32 and out.shape == z.shape and last.shape == kept.shape
    for r, c in enumerate(counts):
        whole = np.concatenate([kept[r], chunk[r, :c]])  # what the sequence has seen, in order
        for j in range(c):
            want = w[-1] * whole[k + j]  # the token itself, then the earlier ones oldest first
            for i in range(k):
                want = want + w[i] * whole[j + i]
            np.testing.assert_array_equal(at(out, r, j), want if bias is None else want + bias)
        np.testing.assert_array_equal(np.asarray(last)[r], whole[-k:])


# ----------------------------------------------------------- through the engine
def engine(params, budget=16, seqs=4, **sections):
    return InferenceEngineV2(lfm2, CFG, params, config={"dtype": "float32", **sections},
                             num_blocks=64, block_size=8, max_blocks_per_seq=16,
                             token_budget=budget, max_seqs_per_step=seqs)


@pytest.fixture(scope="module")
def served(params):
    """The default engine, built once for the cases that only serve a wave
    through it (drained, it replays a wave step for step) and read tokens, and
    the manager's and the prefix tree's totals as deltas."""
    return engine(params)


def greedy(params, prompt, new):
    ids = list(prompt)
    for _ in range(new):
        ids.append(int(np.argmax(want(params, ids + [0] * (-len(ids) % 16), [len(ids) - 1])[0])))
    return ids


def test_generate_through_chunks_and_the_fused_burst_is_the_references_greedy(params, served):
    prompts = [ids_of(10 + i, n) for i, n in enumerate((5, 23, 40, 9, 17, 3))]
    eng, before = served, (served.counters.snapshot(), served.health()["state"])
    got = eng.generate(prompts, max_new_tokens=6)
    c = eng.counters.delta_since(before[0])
    assert c["burst_tokens"] > 0 and c["compact_passes"] > 0
    for p, g in list(zip(prompts, got))[:3]:  # one decode-only, one chunked, one cut in three
        assert list(g) == greedy(params, p, 6)
    state = eng.health()["state"]
    # six sequences through four slots: every hand-out starts a sequence from zero
    assert state == {"enabled": True, "state_slots": 4, "state_slots_in_use": 0,
                     "state_bytes_per_seq": lfm2.state_bytes_per_seq(CFG),
                     "state_slots_zeroed": before[1]["state_slots_zeroed"] + 6,
                     "prefix_declined_stateful": before[1]["prefix_declined_stateful"]}
    assert eng.manager.trash_slot == 4 and eng.kv[STATE].shape[1] == 5


def test_a_slot_reused_after_retire_starts_from_zero(params):
    first, second = ids_of(20, 19), ids_of(21, 12)
    eng = engine(params, seqs=1)  # one slot: the second sequence takes the first's
    eng.generate([first], max_new_tokens=6)
    assert np.abs(np.asarray(eng.kv[STATE][:, 0])).max() > 0  # the first's state is still there
    assert list(eng.generate([second], max_new_tokens=6)[0]) == greedy(params, second, 6)
    assert eng.manager.state_slots_zeroed == 2 and eng.manager.state_slots_in_use == 0


def test_more_sequences_than_slots_wait_for_one(params):
    m = RaggedStateManager(16, 4, 4, state_slots=2)
    a, b, c = (m.add_sequence(uid, [1, 2, 3]) for uid in (1, 2, 3))
    assert (a.state_slot, b.state_slot, c.state_slot) == (0, 1, None)
    assert not m.ensure_state_slot(c) and m.state_slots_in_use == 2
    assert list(m.block_table_row(a, width=3)) == [15, 15, 15, 0]
    assert list(m.block_table_row(c, width=3)) == list(m.dead_table_row(3)) == [15, 15, 15, 2]
    m.retire(1)
    assert m.ensure_state_slot(c) and c.state_slot == 0 and m.state_slots_zeroed == 3
    m.evict(b, "deadline_expired")
    m.fail(3, "boom")
    assert m.state_slots_in_use == 0
    plain = RaggedStateManager(16, 4, 4)  # a model without a state: nothing of it
    seq = plain.add_sequence(1, [1, 2, 3])
    assert seq.state_slot is None and plain.ensure_state_slot(seq)
    assert list(plain.block_table_row(seq, width=3)) == [15, 15, 15]


def test_a_preempted_sequence_resumes_to_the_undisturbed_tokens(params):
    prompt = ids_of(30, 29)
    undisturbed = greedy(params, prompt, 5)
    eng = engine(params, budget=8)
    eng.put([7], [prompt])
    for _ in range(2):
        eng.step()
    seq = eng.manager.seqs[7]
    assert seq.seen_tokens == 16 and seq.state_slot == 0
    eng.manager.preempt(seq, keep_blocks=1)  # a state keeps no block boundary: nothing is kept
    assert (seq.seen_tokens, seq.blocks, seq.state_slot) == (0, [], None)
    out = []
    while len(out) < 5:
        out.extend(eng.step().values())
    assert prompt + out == list(undisturbed)
    assert eng.manager.state_slots_zeroed == 2


def test_a_prefix_hit_is_declined_and_counted(params, served):
    """Mapped blocks would restore the KV and start the conv state at zero in
    mid-prompt: the tree never serves a model with a state."""
    shared = ids_of(40, 24)
    prompts = [shared + ids_of(41, 5), shared + ids_of(42, 7)]
    eng, cache = served, served.manager.prefix_cache
    declined = cache.declined_stateful_total
    got = eng.generate(prompts, max_new_tokens=4)
    assert [list(g) for g in got] == [greedy(params, p, 4) for p in prompts]
    assert cache.hit_blocks_total == 0 and cache.tokens_saved_total == 0
    assert cache.declined_stateful_total == declined + 1 \
        == eng.health()["state"]["prefix_declined_stateful"]
    m = RaggedStateManager(16, 4, 4, prefix_cache=PrefixCache(4), state_slots=2)
    a = m.add_sequence(1, list(range(9)))
    m.ensure_blocks(a, 9)
    a.seen_tokens = 9
    assert m.register_prefix_blocks(a) == 2
    b = m.add_sequence(2, list(range(9)))
    assert m.map_prefix(b) == 0 == m.map_prefix(b) and b.blocks == [] and b.seen_tokens == 0
    assert m.prefix_cache.declined_stateful_total == 1 and m.next_prefix_hash(b) is None


def test_speculative_decoding_is_refused_with_a_message(params):
    with pytest.raises(ValueError, match="per-sequence state"):
        engine(params, serving_spec_decode={"enabled": True})
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        lfm2.forward_paged(CFG, params, None, None, None, None, fresh_cache(), block_size=BS,
                           tp_axis="tensor")


# ------------------------------------------------------------------- the router
def test_a_selection_bias_changes_the_experts_and_not_the_weights_formula():
    rng = np.random.default_rng(3)
    x, wg = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32), \
        jnp.asarray(rng.normal(size=(32, 8)) / 32 ** 0.5, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8, )) * 0.5, jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ wg))
    plain_w, plain_e = route(wg, x, 4, True, scoring="sigmoid", norm_eps=1e-6)
    w, e = route(wg, x, 4, True, scoring="sigmoid", bias=bias, norm_eps=1e-6, scaling=2.0)
    assert not np.array_equal(np.sort(np.asarray(e)), np.sort(np.asarray(plain_e)))
    for s in range(16):
        picks = np.argsort(-(scores[s] + np.asarray(bias)), kind="stable")[:4]
        assert sorted(picks) == sorted(np.asarray(e)[s])
        chosen = scores[s][np.asarray(e)[s]]  # the scores themselves: the bias never weighs
        np.testing.assert_allclose(np.asarray(w)[s], 2.0 * chosen / (chosen.sum() + 1e-6), rtol=1e-6)
        top = np.sort(scores[s])[-4:]
        np.testing.assert_allclose(np.sort(np.asarray(plain_w)[s]), top / (top.sum() + 1e-6),
                                   rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        route(wg, x, 4, True, scoring="tanh")


@pytest.mark.parametrize("renormalise", [False, True])
def test_softmax_and_no_bias_route_bit_for_bit_as_before(renormalise):
    def before(wg, x, top_k, renormalise):  # ``route`` before it learnt of scoring (PR 32)
        logits = jnp.dot(x, wg.astype(x.dtype), preferred_element_type=jnp.float32)
        top_p, top_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        if renormalise:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        return top_p, top_idx.astype(jnp.int32)

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.bfloat16)
    wg = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    for got, old in zip(route(wg, x, 4, renormalise), before(wg, x, 4, renormalise)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(old))
    now, then = (jax.jit(f, static_argnums=(2, 3)).lower(wg, x, 4, renormalise).as_text()
                 for f in (route, before))
    assert now.replace("route", "f") == then.replace("before", "f")  # the same program
