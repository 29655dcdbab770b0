"""LFM2 (ISSUE 33): gated short-convolution layers whose memory of a sequence
is a FIXED state in a slot beside the paged KV pool, attention in one layer of
four over a pool of packed heads, and a sigmoid, bias-corrected router.

The program (``models/lfm2.py`` on ``transformer.paged_forward``, through the
engine's scheduler, manager, bursts) against the plain reference
(``chipbench/references/lfm2.py``: whole sequences, no state, no cache) in
float32 at a size with both layer kinds, a dense layer and two whole periods.
The shared cases are ``family_contract.py``'s; this file builds two engine
configurations (``served``, ``oracle``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import lfm2 as ref
from deepspeed_tpu.inference.v2.ragged_manager import PrefixCache, RaggedStateManager
from deepspeed_tpu.models import lfm2
from deepspeed_tpu.models.transformer import STATE, sequence_filter
from deepspeed_tpu.moe.serving import route
from tests.unit.inference.family_contract import Family, Pool, StatefulContract

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
NB, BS, SLOTS = 24, 4, 4


def layout(h, own, cache):
    assert lfm2.layer_segments(lfm2.Lfm2Config()) == [(0, 1, 2), (2, 4, 9), (38, 1, 1), (39, 1, 1)]
    # attention layers alone in the pool, two KV heads of 16 a row; the conv layers' slots apart
    assert cache["k"].shape == cache["v"].shape == (2, NB, 1, BS, 32)
    assert cache[STATE].shape == (7, SLOTS + 1, 2, 64)
    assert lfm2.state_bytes_per_seq(CFG, 4) == 7 * 2 * 64 * 4
    full = lfm2.Lfm2Config()
    assert lfm2.kv_pack(full) == 2 and lfm2.state_bytes_per_seq(full) == 30 * 2 * 2048 * 2
    nine = lfm2.Lfm2Config(num_layers=10)  # the benchmark's cut: 2 dense + two periods
    assert lfm2.state_bytes_per_seq(nine) == 57344 + 8192 and nine.layer_types.count("conv") == 8


FAMILY = Family(
    module=lfm2, reference=ref, sizes=SIZES, config=CFG,
    tolerance=2e-5, relative=False,
    tolerance_reason="absolute, over logits of 0.16 (a tied head of scale 0.02): 1.25e-4 of the largest; "
    "two float32 programs of nine such layers read under it, a shift that crosses a sequence far over",
    pool=Pool(blocks=NB, block_size=BS, table=6, slots=SLOTS), state_leaves=(STATE, ),
    engine=dict(num_blocks=64, block_size=8, max_blocks_per_seq=16, token_budget=16, max_seqs_per_step=4),
    segments=[(0, 1, 1), (1, 4, 2)],
    # a chunk's first tokens read the sequence's slot, not nothing; a chunk of one token shifts it
    chunkings=((11, ), (1, 10), (2, 9), (3, 8), (4, 1, 2, 3, 1)), chunk_slots=None,
    # one chunk beside decode rows of other sequences: [4, 8] = 32 slots > 8, compacted
    mixed=((13, 5, 11), (6, 5, 6), (9, 8, 9)), mixed_slots_a_row=8, mixed_bound=8,
    waves=((5, 23, 40, 9, 17, 3), ),  # one decode-only, one chunked, one cut in three
    preempt_prompt=45, reference_slots=64, layout=layout)


class TestLfm2(StatefulContract):
    family = FAMILY

    def test_a_prefix_hit_is_declined_and_counted(self, h):
        """Mapped blocks would restore the KV and start the conv state at zero in
        mid-prompt: the tree never serves a model with a state."""
        shared = h.ids_of(40, 24)
        prompts = [shared + h.ids_of(41, 5), shared + h.ids_of(42, 7)]
        eng, cache = h.served, h.served.manager.prefix_cache
        declined = cache.declined_stateful_total
        got = eng.generate(prompts, max_new_tokens=4)
        assert [list(g) for g in got] == [h.greedy(p, 4) for p in prompts]
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


def test_more_sequences_than_slots_wait_for_one():
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
