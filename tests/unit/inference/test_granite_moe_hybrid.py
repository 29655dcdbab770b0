"""Granite 4.0-H (ISSUE 52): Mamba-2 layers whose memory of a sequence is a
float32 matrix a head and a short shift, two leaves of a state tree in a slot
beside the paged KV pool; one attention layer in ten with no positions at all;
four scalar multipliers; a tied head; top-k logits softmaxed among themselves
beside a shared MLP.

The program (``models/granite_moe_hybrid.py`` on ``transformer.paged_forward``,
through the engine's scheduler, manager, bursts) against the plain reference
(``chipbench/references/granite_moe_hybrid.py``: whole sequences, the
recurrence token by token, no state, no cache) in float32 at one published
period (mamba x 5, attention, mamba x 4).  One tiny model, one set of weights,
one jitted forward and one engine a module; a case is data.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import granite_moe_hybrid as ref
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import granite_moe_hybrid as family
from deepspeed_tpu.models.transformer import STATE
from deepspeed_tpu.moe.serving import sparse_moe_ffn
from deepspeed_tpu.ops.linear_attention.ssd import CHUNK, WINDOW, scan_chunks, walk_trips

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
NB, BS, MAXB, SLOTS = 72, 4, 48, 4
TOL = 2e-5      # of the expert layer alone
REL_TOL = 1e-4  # of logits, as a share of the largest (``close``)


@pytest.fixture(scope="module")
def params():
    drawn = ref.init_params(SIZES, jax.random.PRNGKey(7), jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 128))

    def off_neutral(path, leaf):  # a gain or a D left out or misplaced must show
        names = [getattr(p, "key", None) for p in path]
        if any(n in ("op_norm", "ffn_norm", "final_norm", "norm", "D") for n in names):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(off_neutral, drawn)


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n).tolist()


def want(params, ids, rows):
    return np.asarray(ref.logits_rows(SIZES, params, ids, rows))


def close(got, wanted):
    """1e-4 of the largest logit.  Two float32 programs of ten such layers (the
    chunked scan against the token-by-token recurrence, sorted dispatch against
    every expert, a paged softmax against a dense one) read 2.9e-6 apart at the
    row the wrong readings are held against (a tied head of scale 0.02 / 12 over
    16: logits of 0.003); the weakest wrong reading below (rotary applied) reads
    4.2e-2, a softmax over all the experts, not renormalised, 8.4e-2, every other
    0.12 to 1.5, and bfloat16 in float32's place 0.12."""
    np.testing.assert_allclose(got, wanted, atol=REL_TOL * np.abs(wanted).max(), rtol=0)


def fresh_cache(dtype=jnp.float32, slots=SLOTS):
    return family.init_paged_cache(CFG, NB, BS, dtype=dtype, state_slots=slots)


FORWARD = jax.jit(functools.partial(family.forward_paged, CFG),
                  static_argnames=("block_size", "live_token_bound"))


@pytest.fixture(params=["numpy", "kernels"])
def forward(request, monkeypatch):
    """The jitted forward in both forms: ``jax.numpy`` (the module's one) and the
    Pallas kernels interpreted (its own trace: the form is read as it is traced)."""
    if request.param == "numpy":
        return FORWARD
    from deepspeed_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, "INTERPRET", True)
    return jax.jit(functools.partial(family.forward_paged, CFG),
                   static_argnames=("block_size", "live_token_bound"))


def step(params, cache, rows, t, bound=None, forward=FORWARD):
    """One forward over ``rows`` = [(tokens, start_pos, blocks, slot)]; returns
    (logits at each row's last token, cache).  Rows are padded to a power of two."""
    n = 1 << (len(rows) - 1).bit_length()
    tokens, counts = np.zeros((n, t), np.int32), np.zeros(n, np.int32)
    starts, tables = np.zeros(n, np.int32), np.full((n, MAXB + 1), NB - 1, np.int32)
    tables[:, -1] = cache[STATE]["ssm"].shape[1] - 1  # the trash slot
    for i, (toks, start, blocks, slot) in enumerate(rows):
        tokens[i, :len(toks)], counts[i], starts[i] = toks, len(toks), start
        tables[i, :len(blocks)], tables[i, -1] = blocks, slot
    logits, cache = forward(params, jnp.asarray(tokens), jnp.asarray(counts), jnp.asarray(starts),
                            jnp.asarray(tables), cache, block_size=BS, live_token_bound=bound)
    return [np.asarray(logits[i, len(r[0]) - 1], np.float32) for i, r in enumerate(rows)], cache


def test_the_layout_is_the_layers_as_they_are_scanned(params):
    runs = [(0, 1, 5), (5, 1, 1), (6, 1, 4)]
    assert family.layer_segments(CFG) == ref.segments(SIZES) == runs
    assert family.layer_segments(family.GraniteMoeHybridConfig()) == [(0, 10, 4)]
    own = family.init_params(CFG, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(own)] == \
        [a.shape for a in jax.tree_util.tree_leaves(params)]
    assert own["experts"]["w_gate"].shape[:2] == (10, HELD)  # the held experts of every layer
    assert own["segments"][0][0]["moe"]["gate"]["wg"].shape[-1] == 2 * HELD  # the router's width
    cache = fresh_cache()
    # the one attention layer alone in the pool; the Mamba-2 layers' two leaves apart, float32
    assert cache["k"].shape == cache["v"].shape == (1, NB, 2, BS, 16)
    assert cache[STATE]["conv"].shape == (9, SLOTS + 1, 3, 128 + 2 * 16)
    assert cache[STATE]["ssm"].shape == (9, SLOTS + 1, 8, 16, 16)
    half = fresh_cache(jnp.bfloat16)[STATE]
    assert (half["conv"].dtype, half["ssm"].dtype) == (jnp.bfloat16, jnp.float32)
    cut = family.GraniteMoeHybridConfig(num_layers=10, num_local_experts=36, vocab_size=50176)
    assert family.state_bytes_per_seq(cut) == 9 * (4194304 + 50688) == 38204928
    assert family.ssm_widths(cut) == (8192, 8448, 16768)


@pytest.mark.parametrize("what,keys", [
    ("position_embedding_type", {"position_embedding_type": "rope"}),
    ("attention_bias", {"attention_bias": True}), ("mamba_proj_bias", {"mamba_proj_bias": True}),
    ("mamba_n_groups", {"mamba_n_groups": 8}), ("untied", {"tie_embeddings": False}),
    ("layer_types", {"layer_types": ("mamba", "conv")})])
def test_what_is_published_otherwise_and_not_built_is_refused(what, keys):
    with pytest.raises(NotImplementedError, match=what):
        family.GraniteMoeHybridConfig(**keys)


@pytest.mark.parametrize("chunks", [(150, ), (64, 64, 22), (1, 70, 79)],
                         ids=lambda c: "x".join(map(str, c)))
def test_prefill_in_chunks_then_decode_steps_equal_the_reference(params, chunks):
    """A chunk continues from the matrices and the shift its sequence's slot
    holds, across the scan's own chunks of 64 and the step's; its end writes
    both back; a step of one token is the one-token update."""
    ids = ids_of(1, 150 + 2)
    blocks, slot, cache, at = list(range(3, 3 + 40)), 2, fresh_cache(), 0
    for size in chunks:
        (got, ), cache = step(params, cache, [(ids[at:at + size], at, blocks, slot)], t=256)
        at += size
        close(got, want(params, ids, [at - 1])[0])
    for _ in range(2):  # decode by single steps
        (got, ), cache = step(params, cache, [(ids[at:at + 1], at, blocks, slot)], t=1)
        at += 1
        close(got, want(params, ids, [at - 1])[0])


def test_a_compacted_mixed_step_gives_each_sequence_what_it_gets_alone(params):
    """Two chunks and a decode row of three sequences on the flat [1, S] axis:
    each is laid onto a chunk's edge, scanned from its own slot's matrices, and
    nothing crosses a sequence boundary in the scan, the shift or the slots."""
    seqs = [(ids_of(2, 160), list(range(0, 41)), 0), (ids_of(3, 80), list(range(41, 62)), 3),
            (ids_of(4, 9), [62, 63, 64], 1)]
    heads = (70, 5, 8)  # tokens already in the cache: two chunks continue, one row decodes
    cache = fresh_cache()
    for (ids, blocks, slot), done in zip(seqs, heads):
        _, cache = step(params, cache, [(ids[:done], 0, blocks, slot)], t=256)
    rows = [(seqs[0][0][70:160], 70, seqs[0][1], 0), (seqs[1][0][5:80], 5, seqs[1][1], 3),
            (seqs[2][0][8:9], 8, seqs[2][1], 1)]
    mixed, after = step(params, cache, rows, t=256, bound=176)  # [4, 256] slots > 176: compacted
    for i, r in enumerate(rows):
        (alone, ), single = step(params, cache, [r], t=256)
        close(mixed[i], alone)
        close(mixed[i], want(params, seqs[i][0], [r[1] + len(r[0]) - 1])[0])
        for leaf in ("conv", "ssm"):
            close(np.asarray(after[STATE][leaf][:, r[3]]), np.asarray(single[STATE][leaf][:, r[3]]))
    for leaf in ("conv", "ssm"):  # the slot no row named is untouched
        np.testing.assert_array_equal(np.asarray(after[STATE][leaf][:, 2]),
                                      np.asarray(cache[STATE][leaf][:, 2]))


def test_a_pass_that_walks_more_rows_than_a_window_is_the_padded_pass_and_the_reference(params,
                                                                                       forward):
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
        seqs.append((ids_of(40 + i, head + piece), list(range(at, at + blocks)), 6 - i))
        at += blocks
    cache = fresh_cache(slots=8)
    _, cache = step(params, cache, [(ids[:head], 0, blocks, slot)
                                    for (ids, blocks, slot), head in zip(seqs, heads) if head], t=16,
                    forward=forward)
    rows = [(ids[head:], head, blocks, slot) for (ids, blocks, slot), head in zip(seqs, heads)]
    mixed, after = step(params, cache, rows, t=256, bound=176, forward=forward)  # [8, 256] > 176
    padded, oracle = step(params, cache, rows, t=256, forward=forward)
    for i, (ids, _, slot) in enumerate(seqs):
        close(mixed[i], padded[i])
        close(mixed[i], want(params, ids, [len(ids) - 1])[0])
        for leaf in ("conv", "ssm"):
            close(np.asarray(after[STATE][leaf][:, slot]), np.asarray(oracle[STATE][leaf][:, slot]))
    for leaf in ("conv", "ssm"):  # the slot no row named is untouched
        np.testing.assert_array_equal(np.asarray(after[STATE][leaf][:, 7]),
                                      np.asarray(cache[STATE][leaf][:, 7]))


def test_rows_find_their_own_slots_in_whatever_order_the_slots_lie(params, forward):
    """ISSUE 53: the matrices go to the kernels by reference, a row's slot an
    index.  Two sequences whose slots (3, then 1) are neither their rows nor in
    row order, beside a dead row on the trash slot: a chunk each in one padded
    step, then decode steps of both; each reads the reference's logits, and the
    slots no row names hold what they held."""
    seqs = [(ids_of(20, 70 + 3), list(range(0, 20)), 3), (ids_of(21, 9 + 3), list(range(20, 24)), 1)]
    done = [70, 9]
    cache = fresh_cache()
    cache[STATE] = {leaf: rows.at[:, (0, 2)].set(3.0) for leaf, rows in cache[STATE].items()}
    before = cache[STATE]
    chunks = [(ids[:n], 0, blocks, slot) for (ids, blocks, slot), n in zip(seqs, done)]
    got, cache = step(params, cache, chunks + [([], 0, [], SLOTS)], t=256, forward=forward)
    for _ in range(3):
        for (ids, _, _), n, row in zip(seqs, done, got):
            close(row, want(params, ids, [n - 1])[0])
        got, cache = step(params, cache, [(ids[n:n + 1], n, blocks, slot)
                                          for (ids, blocks, slot), n in zip(seqs, done)], t=1,
                          forward=forward)
        done = [n + 1 for n in done]
    for leaf in ("conv", "ssm"):
        np.testing.assert_array_equal(np.asarray(cache[STATE][leaf][:, (0, 2)]),
                                      np.asarray(before[leaf][:, (0, 2)]))


def test_a_sequence_that_begins_reads_nothing_its_slot_was_left_with(params, forward):
    """A slot is never zeroed: the sequence that takes it over begins
    (``start_pos == 0``) over what the last one left, here the last one's
    matrices and then NaNs, and is served as over a fresh cache, in a chunk
    pass and in the decode steps that follow."""
    first, second = ids_of(22, 90), ids_of(23, 40 + 2)
    blocks, slot = list(range(5, 30)), 2
    _, used = step(params, fresh_cache(), [(first, 0, blocks, slot)], t=256)
    assert np.abs(np.asarray(used[STATE]["ssm"][:, slot])).max() > 0.1
    spoiled = dict(used)
    spoiled[STATE] = {leaf: rows.at[:, slot].set(jnp.nan) for leaf, rows in used[STATE].items()}
    for cache in (used, spoiled):
        at = 40
        (got, ), cache = step(params, cache, [(second[:at], 0, blocks, slot)], t=256, forward=forward)
        for _ in range(2):
            close(got, want(params, second, [at - 1])[0])
            (got, ), cache = step(params, cache, [(second[at:at + 1], at, blocks, slot)], t=1,
                                  forward=forward)
            at += 1
        close(got, want(params, second, [at - 1])[0])


# ------------------------------------------------- readings that must not pass
@pytest.fixture(scope="module")
def served_row(params):
    """The program's logits at the end of a 150-token prompt served in three
    chunks, and the prompt: what every wrong reading below is held against."""
    ids = ids_of(5, 150)
    blocks, cache, at = list(range(3, 3 + 40)), fresh_cache(), 0
    for size in (64, 64, 22):
        (got, ), cache = step(params, cache, [(ids[at:at + size], at, blocks, 2)], t=256)
        at += size
    return ids, got


def read_as(sizes, params, ids, **patched):
    """The reference's last logits under another reading: ``sizes`` changed, or
    functions of the reference replaced (unjitted: a patched function is no key
    of the jitted entry's cache)."""
    was = {name: getattr(ref, name) for name in patched}
    try:
        for name, fn in patched.items():
            setattr(ref, name, fn)
        with jax.default_matmul_precision("highest"):
            x = ref.hidden_states(sizes, params, jnp.asarray(ids, jnp.int32))[-1]
            return np.asarray(x @ params["embed"].astype(jnp.float32).T / sizes["logits_scaling"])
    finally:
        for name, fn in was.items():
            setattr(ref, name, fn)


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


def test_the_right_reading_passes_where_the_wrong_ones_are_held(params, served_row):
    ids, got = served_row
    close(got, read_as(SIZES, params, ids))


@pytest.mark.parametrize("reading", sorted(WRONG))
def test_a_wrong_reading_of_the_published_layer_does_not_pass(params, served_row, reading):
    ids, got = served_row
    wrong = dict(WRONG[reading])
    sizes = dict(SIZES, **wrong.pop("sizes", {}))
    others = wrong.pop("params", lambda p: p)(params)
    with pytest.raises(AssertionError):
        close(got, read_as(sizes, others, ids, **wrong))


def test_bfloat16_in_float32s_place_does_not_pass(params):
    ids = ids_of(5, 150)
    half = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    (got, ), _ = step(half, fresh_cache(jnp.bfloat16), [(ids, 0, list(range(3, 43)), 2)], t=256)
    assert np.isfinite(got).all()
    with pytest.raises(AssertionError):
        close(got, want(params, ids, [149])[0])


# ----------------------------------------------------------- through the engine
def engine(params, fast=True, budget=32, seqs=4, **sections):
    conf = {"dtype": "float32", **sections}
    if not fast:
        conf["serving_fastpath"] = {"enabled": False}
    return InferenceEngineV2(family, CFG, params, config=conf, num_blocks=96, block_size=8,
                             max_blocks_per_seq=24, token_budget=budget, max_seqs_per_step=seqs)


@pytest.fixture(scope="module")
def served(params):
    """The default engine, built once for the cases that serve a wave through it."""
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
    assert by_leaf == {"conv": 9 * 3 * 160 * 4, "ssm": 9 * 8 * 16 * 16 * 4}
    # six sequences through four slots: every hand-out starts a sequence from zero
    assert state == {"enabled": True, "state_slots": 4, "state_slots_in_use": 0,
                     "state_bytes_per_seq": family.state_bytes_per_seq(CFG),
                     "state_slots_zeroed": before[1]["state_slots_zeroed"] + 6,
                     "prefix_declined_stateful": 0}
    # the scan's counters: a pass that walks chunks counts the tokens of its rows of more than one
    # (ISSUE 55: a decode row beside them, a prompt's last piece of one token, are the update
    # kernel's) in each of the nine Mamba-2 layers; a decode step or a burst walks none
    assert c["scan_positions"] == c["scan_chunks"] * CHUNK
    assert 0 < c["scan_live_positions"] <= c["scan_positions"]
    assert c["scan_live_positions"] % 9 == 0
    # every prompt token but the pieces of one token the budget's cuts left (the wave below counts
    # them launch by launch)
    assert sum(map(len, prompts)) - len(prompts) <= c["scan_live_positions"] // 9 \
        <= sum(map(len, prompts)) < c["live_tokens"]
    assert c["moe_routed_rows"] == c["live_tokens"] * 4 * 10
    assert c["scan_overflow_windows"] == 0  # four slots: no pass holds more rows than a window
    assert set(c) == set(eng.counters.FIELDS) | {"scan_overflow_windows"}


def test_a_wave_of_decode_rows_beside_chunks_counts_what_its_scans_were_given(params):
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
    eng = engine(params, seqs=8)
    other = ServeCounters(scan=qwen3_next.state_scan(qwen3_next.Qwen3NextConfig.tiny()))
    their_layers = other.scan[2]
    assert not other.reads_spans and eng.counters.reads_spans
    launches, count = [], eng.counters.count_slots

    def recorded(n, t, b, live, blocks, **kw):
        launches.append((n, t, kw.get("flat"), kw.get("passes", 1), live, kw.get("spans")))
        other.count_slots(n, t, b, live, blocks, **{**kw, "spans": None})
        count(n, t, b, live, blocks, **kw)
    eng.counters.count_slots = recorded
    prompts = [ids_of(60 + i, n) for i, n in enumerate((5, 6, 4, 7, 5, 40))]
    got = eng.generate(prompts, max_new_tokens=4)
    for p, g in list(zip(prompts, got))[::5]:
        assert list(g) == greedy(params, p, 4)
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


def test_the_fast_path_and_the_padded_oracle_serve_the_same_tokens(params, served):
    prompts = [ids_of(50 + i, n) for i, n in enumerate((33, 7, 81))]
    fast, slow = served, engine(params, fast=False)
    compacted = fast.counters.compact_passes
    assert [list(g) for g in fast.generate(prompts, max_new_tokens=5)] == \
        [list(g) for g in slow.generate(prompts, max_new_tokens=5)]
    assert slow.counters.compact_passes == 0 < fast.counters.compact_passes - compacted


def test_a_preempted_sequence_starts_over_and_reaches_the_undisturbed_tokens(params, served):
    prompt = ids_of(30, 100)
    undisturbed = greedy(params, prompt, 5)
    eng, zeroed = served, served.manager.state_slots_zeroed
    eng.put([7], [prompt])
    for _ in range(2):
        eng.step()
    seq = eng.manager.seqs[7]
    assert seq.seen_tokens == 64 and seq.state_slot is not None
    eng.manager.preempt(seq, keep_blocks=1)  # a state keeps no block boundary: nothing is kept
    assert (seq.seen_tokens, seq.blocks, seq.state_slot) == (0, [], None)
    out = []
    while len(out) < 5:
        out.extend(eng.step().values())
    eng.flush(7)
    assert prompt + out == list(undisturbed)
    assert eng.manager.state_slots_zeroed == zeroed + 2 and eng.manager.state_slots_in_use == 0


def test_speculative_decoding_and_tensor_parallelism_are_refused(params):
    with pytest.raises(ValueError, match="per-sequence state"):
        engine(params, serving_spec_decode={"enabled": True})
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        family.forward_paged(CFG, params, None, None, None, None, fresh_cache(), block_size=BS,
                             tp_axis="tensor")


# ------------------------------------------------------------------ the experts
def test_the_expert_layer_is_this_chips_share_beside_the_whole_shared_mlp(params):
    """``sparse_moe_ffn`` against the reference's ``layer_parts`` for chip 0: a
    router over 8, 4 experts held, picks elsewhere add nothing; a softmax over
    the top-k logits is the renormalised top-k of the full softmax."""
    moe = jax.tree_util.tree_map(lambda a: a[0], params["segments"][0][0]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(9), (37, 64))
    with jax.default_matmul_precision("highest"):
        got = sparse_moe_ffn({**moe, "experts": params["experts"]}, x, 4, True, layer=jnp.int32(1))
        routed, shared = ref.layer_parts(SIZES, {**moe, "experts": params["experts"]}, x, layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(routed + shared), atol=TOL, rtol=0)
    assert np.abs(np.asarray(routed)).max() > 0.01 and np.abs(np.asarray(shared)).max() > 0.1
