"""Granite 4.0-H (ISSUE 52): Mamba-2 layers whose memory of a sequence is a
float32 matrix a head and a short shift, two leaves of a state tree in a slot
beside the paged KV pool; one attention layer in ten with no positions at all;
four scalar multipliers; a tied head; top-k logits softmaxed among themselves
beside a shared MLP.

The program (``models/granite_moe_hybrid.py`` on ``transformer.paged_forward``,
through the engine's scheduler, manager, bursts) against the plain reference
(``chipbench/references/granite_moe_hybrid.py``: whole sequences, the
recurrence token by token, no state, no cache) in float32 at one published
period (mamba x 5, attention, mamba x 4).
The shared cases are ``family_contract.py``'s; this file builds three engine
configurations (``served``, ``oracle``, and one of eight slots for the wave whose
first pass holds more prompt pieces than a window has rows).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import granite_moe_hybrid as ref
from deepspeed_tpu.models import granite_moe_hybrid as family
from deepspeed_tpu.models.transformer import STATE
from deepspeed_tpu.moe.serving import sparse_moe_ffn
from deepspeed_tpu.ops.linear_attention.ssd import CHUNK, WINDOW, scan_chunks, walk_trips
from tests.unit.inference.family_contract import Family, Pool, StatefulContract, WrongReadings

HELD = 4  # of 8 experts: one chip's share of two
SIZES = {"attention_bias": False, "attention_multiplier": 0.0625, "embedding_multiplier": 12,
         "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 32,
         "layer_types": ["attention" if i % 10 == 5 else "mamba" for i in range(40)],
         "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
         "mamba_d_head": 16, "mamba_d_state": 16, "mamba_expand": 2, "mamba_n_groups": 1,
         "mamba_n_heads": 8, "mamba_proj_bias": False, "max_position_embeddings": 512,
         "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
         "num_attention_heads": 4, "num_experts_per_tok": 4, "num_hidden_layers": 10,
         "num_key_value_heads": 2, "num_local_experts": HELD, "position_embedding_type": "nope",
         "residual_multiplier": 0.22, "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
         "shared_intermediate_size": 32, "tie_word_embeddings": True, "vocab_size": 256}
CFG = family.GraniteMoeHybridConfig.tiny(experts=ref.EP_CHIPS * HELD, local_experts=HELD)
NB, BS, SLOTS = 72, 4, 4
TOL = 2e-5  # of the expert layer alone
NORMS = {"op_norm", "ffn_norm", "final_norm", "norm", "D"}


def off_neutral(names, leaf, noise):  # a gain or a D left out or misplaced must show
    return leaf + 0.3 * noise(leaf.shape) if NORMS & set(names) else leaf


def layout(h, own, cache):
    assert family.layer_segments(family.GraniteMoeHybridConfig()) == [(0, 10, 4)]
    assert own["experts"]["w_gate"].shape[:2] == (10, HELD)  # the held experts of every layer
    assert own["segments"][0][0]["moe"]["gate"]["wg"].shape[-1] == 2 * HELD  # the router's width
    # the one attention layer alone in the pool; the Mamba-2 layers' two leaves apart, float32
    assert cache["k"].shape == cache["v"].shape == (1, NB, 2, BS, 16)
    assert cache[STATE]["conv"].shape == (9, SLOTS + 1, 3, 128 + 2 * 16)
    assert cache[STATE]["ssm"].shape == (9, SLOTS + 1, 8, 16, 16)
    half = h.fresh_cache(jnp.bfloat16)[STATE]
    assert (half["conv"].dtype, half["ssm"].dtype) == (jnp.bfloat16, jnp.float32)
    cut = family.GraniteMoeHybridConfig(num_layers=10, num_local_experts=36, vocab_size=50176)
    assert family.state_bytes_per_seq(cut) == 9 * (4194304 + 50688) == 38204928
    assert family.ssm_widths(cut) == (8192, 8448, 16768)


def wave(h, seen):
    c, eng, prompts = seen.counters, seen.engine, seen.prompts
    assert eng.health()["state"]["state_bytes_by_leaf"] == {"conv": 9 * 3 * 160 * 4, "ssm": 9 * 8 * 16 * 16 * 4}
    # the scan's counters: a pass that walks chunks counts the tokens of its rows of more than one
    # (ISSUE 55: a decode row beside them, a prompt's last piece of one token, are the update
    # kernel's) in each of the nine Mamba-2 layers; a decode step or a burst walks none
    assert c["scan_positions"] == c["scan_chunks"] * CHUNK
    assert 0 < c["scan_live_positions"] <= c["scan_positions"]
    assert c["scan_live_positions"] % 9 == 0
    # every prompt token but the pieces of one token the budget's cuts left (the wave of decode
    # rows beside chunks counts them launch by launch)
    assert sum(map(len, prompts)) - len(prompts) <= c["scan_live_positions"] // 9 \
        <= sum(map(len, prompts)) < c["live_tokens"]
    assert c["moe_routed_rows"] == c["live_tokens"] * 4 * 10
    assert c["scan_overflow_windows"] == 0  # four slots: no pass holds more rows than a window
    assert set(c) == set(eng.counters.FIELDS) | {"scan_overflow_windows"}


# ------------------------------------------------- readings that must not pass
def rotated(sizes, u, w):  # rotate-half rotary over q and k, as a "rope" model would
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], 16
    w = ref.f32(w)
    angle = jnp.arange(u.shape[0])[:, None] * 10000.0 ** (-jnp.arange(dh // 2) / (dh // 2))
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    turn = lambda x: jnp.concatenate([x[..., :dh // 2] * cos - x[..., dh // 2:] * sin,
                                      x[..., dh // 2:] * cos + x[..., :dh // 2] * sin], axis=-1)
    out = ref.causal_attention(turn((u @ w["wq"]).reshape(-1, h, dh)),
                               turn((u @ w["wk"]).reshape(-1, kv, dh)),
                               (u @ w["wv"]).reshape(-1, kv, dh), sizes["attention_multiplier"])
    return out.reshape(-1, h * dh) @ w["wo"]


def full_softmax(sizes, n, wg):  # a softmax over every expert, the top-k of it not renormalised
    probs = jax.nn.softmax(n @ wg, axis=-1)
    top, idx = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    return jnp.zeros_like(probs).at[jnp.arange(probs.shape[0])[:, None], idx].set(top)


WRONG = {
    "one over the root of the head for attention_multiplier": dict(sizes={"attention_multiplier": 0.25}),
    "rotary applied": dict(attention=rotated),
    "a softmax over all experts, not renormalised": dict(router=full_softmax),
    "the gate outside the norm": dict(gated_norm=lambda y, z, gain, eps: ref.rms_norm(
        y, gain, eps) * jax.nn.silu(z)),
    "D left out": dict(params=lambda p: jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if getattr(path[-1], "key", None) == "D" else a, p)),
    "residual_multiplier left out": dict(sizes={"residual_multiplier": 1.0}),
    "embedding_multiplier left out": dict(sizes={"embedding_multiplier": 1.0}),
    "logits_scaling left out": dict(sizes={"logits_scaling": 1.0}),
}


FAMILY = Family(
    module=family, reference=ref, sizes=SIZES, config=CFG,
    tolerance=1e-4,
    tolerance_reason="""1e-4 of the largest logit.  Two float32 programs of ten such layers (the
    chunked scan against the token-by-token recurrence, sorted dispatch against
    every expert, a paged softmax against a dense one) read 2.9e-6 apart at the
    row the wrong readings are held against (a tied head of scale 0.02 / 12 over
    16: logits of 0.003); the weakest wrong reading below (rotary applied) reads
    4.2e-2, a softmax over all the experts, not renormalised, 8.4e-2, every other
    0.12 to 1.5, and bfloat16 in float32's place 0.12.""",
    off_neutral=off_neutral, pool=Pool(NB, BS, 48, SLOTS), state_leaves=("conv", "ssm"),
    segments=[(0, 1, 5), (5, 1, 1), (6, 1, 4)],
    # a chunk continues from the matrices and the shift its sequence's slot holds, across the
    # scan's own chunks of 64 and the step's
    chunkings=((150, ), (64, 64, 22), (1, 70, 79)), decode_steps=2, layout=layout, wave=wave,
    wrong_readings=WRONG)


class TestGraniteMoeHybrid(StatefulContract, WrongReadings):
    family = FAMILY

    @pytest.fixture(params=["numpy", "kernels"])
    def forward(self, request, h):
        """The jitted forward in both forms: ``jax.numpy`` (the harness's one) and the
        Pallas kernels interpreted (its own trace: the form is read as it is traced)."""
        return h.forward if request.param == "numpy" else h.interpreted()

    @pytest.mark.parametrize("what,keys", [
        ("position_embedding_type", {"position_embedding_type": "rope"}),
        ("attention_bias", {"attention_bias": True}), ("mamba_proj_bias", {"mamba_proj_bias": True}),
        ("mamba_n_groups", {"mamba_n_groups": 8}), ("untied", {"tie_embeddings": False}),
        ("layer_types", {"layer_types": ("mamba", "conv")})])
    def test_what_is_published_otherwise_and_not_built_is_refused(self, what, keys):
        with pytest.raises(NotImplementedError, match=what):
            family.GraniteMoeHybridConfig(**keys)

    def test_a_pass_that_walks_more_rows_than_a_window_is_the_padded_pass_and_the_reference(self, h, forward):
        """ISSUE 55: five prompt pieces (2, 30, 64, 65 and 9 tokens, each continuing
        its sequence), a decode row and a prompt of one token that begins, in one
        compacted pass of 176 slots: the two one-token rows go to the update kernel,
        the five others are walked ``WINDOW`` a trip (two trips), and each row reads
        what the padded pass gives it (the oracle: every row's chunks in place, no
        window) and what the reference gives; both leave the same state in the rows'
        slots."""
        heads, pieces = (10, 8, 6, 5, 7, 8, 0), (2, 30, 64, 65, 9, 1, 1)
        assert walk_trips(sum(p > 1 for p in pieces)) == 2 and sum(pieces) <= 176
        seqs, at = [], 0
        for i, (head, piece) in enumerate(zip(heads, pieces)):
            blocks = -(-(head + piece) // BS)
            seqs.append((h.ids_of(40 + i, head + piece), list(range(at, at + blocks)), 6 - i))
            at += blocks
        cache = h.fresh_cache(slots=8)
        _, cache = h.step(cache, [(ids[:head], 0, blocks, slot)
                                  for (ids, blocks, slot), head in zip(seqs, heads) if head], t=16,
                          forward=forward)
        rows = [(ids[head:], head, blocks, slot) for (ids, blocks, slot), head in zip(seqs, heads)]
        mixed, after = h.step(cache, rows, t=256, bound=176, forward=forward)  # [8, 256] > 176
        padded, oracle = h.step(cache, rows, t=256, forward=forward)
        for i, (ids, _, slot) in enumerate(seqs):
            h.close(mixed[i], padded[i])
            h.close(mixed[i], h.want(ids, [len(ids) - 1])[0])
            for leaf in ("conv", "ssm"):
                h.close(np.asarray(after[STATE][leaf][:, slot]), np.asarray(oracle[STATE][leaf][:, slot]))
        for leaf in ("conv", "ssm"):  # the slot no row named is untouched
            np.testing.assert_array_equal(np.asarray(after[STATE][leaf][:, 7]),
                                          np.asarray(cache[STATE][leaf][:, 7]))

    def test_rows_find_their_own_slots_in_whatever_order_the_slots_lie(self, h, forward):
        """ISSUE 53: the matrices go to the kernels by reference, a row's slot an
        index.  Two sequences whose slots (3, then 1) are neither their rows nor in
        row order, beside a dead row on the trash slot: a chunk each in one padded
        step, then decode steps of both; each reads the reference's logits, and the
        slots no row names hold what they held."""
        seqs = [(h.ids_of(20, 70 + 3), list(range(0, 20)), 3), (h.ids_of(21, 9 + 3), list(range(20, 24)), 1)]
        done = [70, 9]
        cache = h.fresh_cache()
        cache[STATE] = {leaf: rows.at[:, (0, 2)].set(3.0) for leaf, rows in cache[STATE].items()}
        before = cache[STATE]
        chunks = [(ids[:n], 0, blocks, slot) for (ids, blocks, slot), n in zip(seqs, done)]
        got, cache = h.step(cache, chunks + [([], 0, [], SLOTS)], t=256, forward=forward)
        for _ in range(3):
            for (ids, _, _), n, row in zip(seqs, done, got):
                h.close(row, h.want(ids, [n - 1])[0])
            got, cache = h.step(cache, [(ids[n:n + 1], n, blocks, slot)
                                        for (ids, blocks, slot), n in zip(seqs, done)], t=1,
                                forward=forward)
            done = [n + 1 for n in done]
        for leaf in ("conv", "ssm"):
            np.testing.assert_array_equal(np.asarray(cache[STATE][leaf][:, (0, 2)]),
                                          np.asarray(before[leaf][:, (0, 2)]))

    def test_a_sequence_that_begins_over_a_spoiled_slot_is_served_by_the_kernels_too(self, h):
        self.begins_over_what_a_slot_was_left_with(h, h.interpreted())

    # ----------------------------------------------------------- through the engine
    def test_a_wave_of_decode_rows_beside_chunks_counts_what_its_scans_were_given(self, h):
        """ISSUE 55, through the engine: six prompts admitted at once under a budget
        of 32 over eight slots, so the first pass holds six prompt pieces (two trips
        of the window) and the short prompts decode beside the long one's chunks.  The
        tokens are the reference's greedy continuation; the three scan counters are
        what each launch's rows say (the tokens of the rows of more than one token;
        ``ceil(S / CHUNK) + WINDOW`` chunks a trip a layer for a compacted pass, a
        row's chunks in place for a padded one) and the trips beyond a walk's first
        are counted with them, on the host, at no fetch; and a family whose
        one-token rows stay in its walk (Qwen3-Next's ``state_scan``) counts the same
        launches as the parent did: every live token, ``ceil(S / 64) + n`` chunks."""
        from deepspeed_tpu.inference.v2.fastpath import ServeCounters
        from deepspeed_tpu.models import qwen3_next
        # a third engine: eight slots, so that a pass holds more prompt pieces than a window has rows
        eng = h.engine(max_seqs_per_step=8, sections={"serving_fastpath": {"prewarm_buckets": 0}})
        other = ServeCounters(scan=qwen3_next.state_scan(qwen3_next.Qwen3NextConfig.tiny()))
        their_layers = other.scan[2]
        assert not other.reads_spans and eng.counters.reads_spans
        launches, count = [], eng.counters.count_slots

        def recorded(n, t, b, live, blocks, **kw):
            launches.append((n, t, kw.get("flat"), kw.get("passes", 1), live, kw.get("spans")))
            other.count_slots(n, t, b, live, blocks, **{**kw, "spans": None})
            count(n, t, b, live, blocks, **kw)
        eng.counters.count_slots = recorded
        prompts = [h.ids_of(60 + i, n) for i, n in enumerate((5, 6, 4, 7, 5, 40))]
        got = eng.generate(prompts, max_new_tokens=4)
        for p, g in list(zip(prompts, got))[::5]:
            assert list(g) == h.greedy(p, 4)
        chunks = positions = scanned = trips_beyond = theirs = their_live = 0
        beside = False  # a pass in which a one-token row rode beside a walked one
        for n, t, flat, passes, live, spans in launches:
            walked = [count for _, count in spans if count > 1]
            if t == 1:
                continue
            if flat is None:
                here = n * -(-t // CHUNK)
                theirs += here
            else:
                here = walk_trips(len(walked)) * (-(-flat // CHUNK) + WINDOW)
                assert here == scan_chunks(n, t, flat, len(walked))
                trips_beyond += max(walk_trips(len(walked)) - 1, 0)
                theirs += -(-flat // 64) + n
            chunks, scanned, their_live = chunks + here, scanned + sum(walked), their_live + live
            beside |= bool(walked) and len(walked) < len(spans)
        c = eng.counters.snapshot()
        assert beside and trips_beyond >= 1
        assert (c["scan_chunks"], c["scan_positions"], c["scan_live_positions"]) == \
            (9 * chunks, 9 * chunks * CHUNK, 9 * scanned)
        assert c["scan_overflow_windows"] == 9 * trips_beyond
        assert (other.scan_chunks, other.scan_live_positions) == (their_layers * theirs,
                                                                  their_layers * their_live)
        assert scanned < their_live  # the rows of one token are no part of Granite's walk
        assert "scan_overflow_windows" not in other.snapshot() and other.scan_overflow_windows == 0

    # ------------------------------------------------------------------ the experts
    def test_the_expert_layer_is_this_chips_share_beside_the_whole_shared_mlp(self, h):
        """``sparse_moe_ffn`` against the reference's ``layer_parts`` for chip 0: a
        router over 8, 4 experts held, picks elsewhere add nothing; a softmax over
        the top-k logits is the renormalised top-k of the full softmax."""
        params = h.params
        moe = jax.tree_util.tree_map(lambda a: a[0], params["segments"][0][0]["moe"])
        x = jax.random.normal(jax.random.PRNGKey(9), (37, 64))
        with jax.default_matmul_precision("highest"):
            got = sparse_moe_ffn({**moe, "experts": params["experts"]}, x, 4, True, layer=jnp.int32(1))
            routed, shared = ref.layer_parts(SIZES, {**moe, "experts": params["experts"]}, x, layer=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(routed + shared), atol=TOL, rtol=0)
        assert np.abs(np.asarray(routed)).max() > 0.01 and np.abs(np.asarray(shared)).max() > 0.1
