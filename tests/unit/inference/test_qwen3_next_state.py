"""Qwen3-Next (ISSUE 43): Gated DeltaNet layers whose memory of a sequence is a
float32 matrix a head and a short shift, two leaves of a state tree in a slot
beside the paged KV pool; gated attention over heads two lane tiles wide in the
fourth layer; a gated shared expert beside the routed ones.

The program (``models/qwen3_next.py`` on ``transformer.paged_forward``, through
the engine's scheduler, manager, bursts) against the plain reference
(``chipbench/references/qwen3_next.py``: whole sequences, the delta rule token
by token, no state, no cache) in float32 at a size with two whole periods.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import qwen3_next as ref
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import qwen3_next
from deepspeed_tpu.models.transformer import STATE
from deepspeed_tpu.moe.serving import sparse_moe_ffn
from deepspeed_tpu.ops.linear_attention import CHUNK

HELD = 4  # of 16 experts: one chip's share of four
SIZES = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 16, "hidden_act": "silu",
         "hidden_size": 64, "intermediate_size": 128, "linear_conv_kernel_dim": 4,
         "linear_key_head_dim": 8, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
         "linear_value_head_dim": 8, "max_position_embeddings": 512, "mlp_only_layers": [],
         "model_type": "qwen3_next", "moe_intermediate_size": 32, "norm_topk_prob": True,
         "num_attention_heads": 4, "num_experts": HELD, "num_experts_per_tok": 4,
         "num_hidden_layers": 8, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
         "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 10000000,
         "shared_expert_intermediate_size": 32, "tie_word_embeddings": False,
         "use_sliding_window": False, "vocab_size": 256}
CFG = qwen3_next.Qwen3NextConfig.tiny(experts=ref.EP_CHIPS * HELD, local_experts=HELD)
NB, BS, MAXB, SLOTS = 72, 4, 48, 4
TOL = 2e-5      # of the expert layer alone
REL_TOL = 3e-4  # of logits, as a share of the largest (``close``)


@pytest.fixture(scope="module")
def params():
    drawn = ref.init_params(SIZES, jax.random.PRNGKey(7), jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 128))

    def off_neutral(path, leaf):  # a gain of the wrong kind or place must show
        names = [getattr(p, "key", None) for p in path]
        if any(n in ("op_norm", "ffn_norm", "q_norm", "k_norm", "final_norm", "norm") for n in names):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(off_neutral, drawn)


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n).tolist()


def want(params, ids, rows):
    return np.asarray(ref.logits_rows(SIZES, params, ids, rows))


def close(got, wanted):
    """3e-4 of the largest logit.  LFM2's 2e-5 is 1.25e-4 of its own (a tied
    0.02-scale head gives logits of 0.16; this head is untied at 1/sqrt(D) and
    its logits reach 3-4), and this model at this draw turns a relative noise of
    1e-7 in the weights into 1.1e-5 of the logits (measured on the reference
    alone), so two float32 programs of eight such layers (the chunked scan
    against the token-by-token rule, sorted dispatch against every expert) read
    1e-5 to 1.4e-4 apart, as HF's own float32 and float64 runs do
    (``tests/chipbench/test_reference_qwen3_next.py``).  A state that is not
    carried, a gate left out or a gain of the wrong kind reads 1e-2 and more."""
    np.testing.assert_allclose(got, wanted, atol=REL_TOL * np.abs(wanted).max(), rtol=0)


def fresh_cache():
    return qwen3_next.init_paged_cache(CFG, NB, BS, dtype=jnp.float32, state_slots=SLOTS)


FORWARD = jax.jit(functools.partial(qwen3_next.forward_paged, CFG),
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
    assert qwen3_next.layer_segments(CFG) == ref.segments(SIZES) == [(0, 4, 2)]
    assert qwen3_next.layer_segments(qwen3_next.Qwen3NextConfig()) == [(0, 4, 12)]
    own = qwen3_next.init_params(CFG, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(own)] == \
        [a.shape for a in jax.tree_util.tree_leaves(params)]
    assert own["experts"]["w_gate"].shape[:2] == (8, HELD)  # the held experts of every layer
    assert own["segments"][0][0]["moe"]["gate"]["wg"].shape[-1] == 4 * HELD  # the router's width
    cache = fresh_cache()
    # attention layers alone in the pool; the DeltaNet layers' two leaves apart, the matrix float32
    assert cache["k"].shape == cache["v"].shape == (2, NB, 2, BS, 16)
    assert cache[STATE]["conv"].shape == (6, SLOTS + 1, 3, 2 * 16 + 32)
    assert cache[STATE]["recurrent"].shape == (6, SLOTS + 1, 4, 8, 8)
    half = qwen3_next.init_paged_cache(CFG, NB, BS, dtype=jnp.bfloat16, state_slots=SLOTS)[STATE]
    assert (half["conv"].dtype, half["recurrent"].dtype) == (jnp.bfloat16, jnp.float32)
    full = qwen3_next.Qwen3NextConfig(num_layers=12)  # the benchmark's cut: three periods
    assert qwen3_next.state_bytes_per_seq(full) == 9 * (2097152 + 49152) == 19316736
    with pytest.raises(NotImplementedError, match="mlp_only_layers"):
        qwen3_next.Qwen3NextConfig(mlp_only_layers=[0])


@pytest.mark.parametrize("chunks", [(150, ), (64, 64, 22), (1, 70, 79), (5, 131, 1, 2, 11)],
                         ids=lambda c: "x".join(map(str, c)))
def test_prefill_in_chunks_then_decode_steps_equal_the_reference(params, chunks):
    """A chunk continues from the matrix and the shift its sequence's slot
    holds, across the scan's own chunks of 64 and the step's; its end writes
    both back; a step of one token is the one-token update."""
    ids = ids_of(1, 150 + 3)
    blocks, slot, cache, at = list(range(3, 3 + 40)), 2, fresh_cache(), 0
    for size in chunks:
        (got, ), cache = step(params, cache, [(ids[at:at + size], at, blocks, slot)],
                              t=1 << (size - 1).bit_length())
        at += size
        close(got, want(params, ids, [at - 1])[0])
    for _ in range(3):  # decode by single steps
        (got, ), cache = step(params, cache, [(ids[at:at + 1], at, blocks, slot)], t=1)
        at += 1
        close(got, want(params, ids, [at - 1])[0])


def test_a_compacted_mixed_step_gives_each_sequence_what_it_gets_alone(params):
    """Two chunks and a decode row of three sequences on the flat [1, S] axis:
    each is laid onto a chunk's edge, scanned from its own slot's matrix, and
    nothing crosses a sequence boundary in the scan, the shift or the slots."""
    seqs = [(ids_of(2, 160), list(range(0, 41)), 0), (ids_of(3, 80), list(range(41, 62)), 3),
            (ids_of(4, 9), [62, 63, 64], 1)]
    heads = (70, 5, 8)  # tokens already in the cache: two chunks continue, one row decodes
    cache = fresh_cache()
    for (ids, blocks, slot), done in zip(seqs, heads):
        _, cache = step(params, cache, [(ids[:done], 0, blocks, slot)], t=128)
    rows = [(seqs[0][0][70:160], 70, seqs[0][1], 0), (seqs[1][0][5:80], 5, seqs[1][1], 3),
            (seqs[2][0][8:9], 8, seqs[2][1], 1)]
    mixed, after = step(params, cache, rows, t=128, bound=176)  # [4, 128] = 512 slots > 176: compacted
    for i, r in enumerate(rows):
        (alone, ), single = step(params, cache, [r], t=128)
        close(mixed[i], alone)
        close(mixed[i], want(params, seqs[i][0], [r[1] + len(r[0]) - 1])[0])
        for leaf in ("conv", "recurrent"):
            close(np.asarray(after[STATE][leaf][:, r[3]]), np.asarray(single[STATE][leaf][:, r[3]]))
    for leaf in ("conv", "recurrent"):  # the slot no row named is untouched
        np.testing.assert_array_equal(np.asarray(after[STATE][leaf][:, 2]),
                                      np.asarray(cache[STATE][leaf][:, 2]))


# ----------------------------------------------------------- through the engine
def engine(params, fast=True, budget=32, seqs=4, **sections):
    conf = {"dtype": "float32", **sections}
    if not fast:
        conf["serving_fastpath"] = {"enabled": False}
    return InferenceEngineV2(qwen3_next, CFG, params, config=conf, num_blocks=96, block_size=8,
                             max_blocks_per_seq=24, token_budget=budget, max_seqs_per_step=seqs)


@pytest.fixture(scope="module")
def served(params):
    """The default engine, built once for the cases that only serve a wave
    through it (drained, it replays a wave step for step) and read tokens, and
    its counters and the manager's totals as deltas."""
    return engine(params)


def greedy(params, prompt, new):
    ids = list(prompt)
    for _ in range(new):
        ids.append(int(np.argmax(want(params, ids + [0] * (-len(ids) % 16), [len(ids) - 1])[0])))
    return ids


def test_generate_through_chunks_and_the_fused_burst_is_the_references_greedy(params, served):
    prompts = [ids_of(10 + i, n) for i, n in enumerate((5, 90, 140, 9, 70, 3))]
    eng, before = served, (served.counters.snapshot(), served.health()["state"])
    got = eng.generate(prompts, max_new_tokens=6)
    c = eng.counters.delta_since(before[0])
    assert c["burst_tokens"] > 0 and c["compact_passes"] > 0
    for p, g in list(zip(prompts, got))[:3]:  # one decode-only, one cut in three, one in five
        assert list(g) == greedy(params, p, 6)
    state = eng.health()["state"]
    by_leaf = state.pop("state_bytes_by_leaf")
    assert by_leaf == {"conv": 6 * 3 * 64 * 4, "recurrent": 6 * 4 * 8 * 8 * 4}
    assert eng.state_snapshot()["state"]["state_bytes_by_leaf"] == by_leaf
    # six sequences through four slots: every hand-out starts a sequence from zero
    assert state == {"enabled": True, "state_slots": 4, "state_slots_in_use": 0,
                     "state_bytes_per_seq": qwen3_next.state_bytes_per_seq(CFG),
                     "state_slots_zeroed": before[1]["state_slots_zeroed"] + 6,
                     "prefix_declined_stateful": 0}
    # the scan's counters: a pass that walks chunks counts its live tokens (a mixed pass's decode
    # rows among them) in each of the six DeltaNet layers; a decode step or a burst walks none
    assert c["scan_positions"] == c["scan_chunks"] * CHUNK
    assert 0 < c["scan_live_positions"] <= c["scan_positions"]
    assert c["scan_live_positions"] % 6 == 0
    assert sum(map(len, prompts)) <= c["scan_live_positions"] // 6 < c["live_tokens"]
    assert c["moe_routed_rows"] == c["live_tokens"] * 4 * 8


def test_the_fast_path_and_the_padded_oracle_serve_the_same_tokens(params, served):
    prompts = [ids_of(50 + i, n) for i, n in enumerate((33, 7, 81))]
    fast, slow = served, engine(params, fast=False)
    compacted = fast.counters.compact_passes
    assert [list(g) for g in fast.generate(prompts, max_new_tokens=5)] == \
        [list(g) for g in slow.generate(prompts, max_new_tokens=5)]
    assert slow.counters.compact_passes == 0 < fast.counters.compact_passes - compacted


def test_a_slot_reused_after_retire_starts_from_zero(params):
    first, second = ids_of(20, 75), ids_of(21, 40)
    eng = engine(params, seqs=1)  # one slot: the second sequence takes the first's
    eng.generate([first], max_new_tokens=6)
    for leaf in ("conv", "recurrent"):  # the first's state is still there
        assert np.abs(np.asarray(eng.kv[STATE][leaf][:, 0])).max() > 0
    assert list(eng.generate([second], max_new_tokens=6)[0]) == greedy(params, second, 6)
    assert eng.manager.state_slots_zeroed == 2 and eng.manager.state_slots_in_use == 0


def test_a_preempted_sequence_resumes_to_the_undisturbed_tokens(params):
    prompt = ids_of(30, 100)
    undisturbed = greedy(params, prompt, 5)
    eng = engine(params, budget=32)
    eng.put([7], [prompt])
    for _ in range(2):
        eng.step()
    seq = eng.manager.seqs[7]
    assert seq.seen_tokens == 64 and seq.state_slot == 0
    eng.manager.preempt(seq, keep_blocks=1)  # a state keeps no block boundary: nothing is kept
    assert (seq.seen_tokens, seq.blocks, seq.state_slot) == (0, [], None)
    out = []
    while len(out) < 5:
        out.extend(eng.step().values())
    assert prompt + out == list(undisturbed)
    assert eng.manager.state_slots_zeroed == 2


def test_speculative_decoding_and_tensor_parallelism_are_refused(params):
    with pytest.raises(ValueError, match="per-sequence state"):
        engine(params, serving_spec_decode={"enabled": True})
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        qwen3_next.forward_paged(CFG, params, None, None, None, None, fresh_cache(), block_size=BS,
                                 tp_axis="tensor")


# ------------------------------------------------------------------ the experts
def test_the_expert_layer_is_this_chips_share_and_the_shared_expert_is_gated(params):
    """``sparse_moe_ffn`` against the reference's ``layer_parts`` for chip 0:
    a router over 16, 4 experts held, picks elsewhere add nothing; the shared
    expert times one sigmoid gate a token."""
    moe = jax.tree_util.tree_map(lambda a: a[0], params["segments"][0][1]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(9), (37, 64))
    with jax.default_matmul_precision("highest"):
        got = sparse_moe_ffn({**moe, "experts": params["experts"]}, x, 4, True, layer=jnp.int32(1))
        routed, shared = ref.layer_parts(SIZES, {**moe, "experts": params["experts"]}, x, layer=1)
        ungated = ref.swiglu(x, moe["shared"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(routed + shared), atol=TOL, rtol=0)
    assert np.abs(np.asarray(shared - ungated)).max() > 0.05  # the gate is not one
