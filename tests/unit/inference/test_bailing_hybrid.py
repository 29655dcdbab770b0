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
cache) in float32 at two periods of (KDA, KDA, MLA).  One tiny model, one set of
weights, one jitted forward and one engine a module; a case is data.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import bailing_hybrid as ref
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import bailing_hybrid as family
from deepspeed_tpu.models.transformer import STATE

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
NB, BS, MAXB, SLOTS = 72, 4, 48, 4
REL_TOL = 1e-4  # of logits, as a share of the largest (``close``)


@pytest.fixture(scope="module")
def params():
    drawn = ref.init_params(SIZES, jax.random.PRNGKey(7), jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 128))

    def off_neutral(path, leaf):  # a gain left out or misplaced must show
        names = [getattr(p, "key", None) for p in path]
        if any(n in ("op_norm", "ffn_norm", "final_norm", "norm", "kv_norm") for n in names):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(off_neutral, drawn)


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n).tolist()


def want(params, ids, rows):
    return np.asarray(ref.logits_rows(SIZES, params, ids, rows))


def close(got, wanted):
    """1e-4 of the largest logit.  Two float32 programs of six such layers (the
    chunked scan with its block inverse against the token-by-token recurrence,
    absorbed latent attention over a paged pool against materialised keys and a
    dense softmax, sorted dispatch against every expert) read 3e-6 apart at the
    row the wrong readings are held against; the weakest wrong reading below
    reads 2e-3, and bfloat16 in float32's place 3e-2."""
    np.testing.assert_allclose(got, wanted, atol=REL_TOL * np.abs(wanted).max(), rtol=0)


def fresh_cache(dtype=jnp.float32, slots=SLOTS):
    return family.init_paged_cache(CFG, NB, BS, dtype=dtype, state_slots=slots)


def jitted():
    return jax.jit(functools.partial(family.forward_paged, CFG),
                   static_argnames=("block_size", "live_token_bound"))


FORWARD = jitted()


@pytest.fixture
def forward(monkeypatch):
    """The jitted forward with the Pallas kernels interpreted (its own trace: the form is
    read as it is traced); ``FORWARD``, the ``jax.numpy`` form, serves every other case."""
    from deepspeed_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, "INTERPRET", True)
    return jitted()


def step(params, cache, rows, t, bound=None, forward=FORWARD):
    """One forward over ``rows`` = [(tokens, start_pos, blocks, slot)]; returns
    (logits at each row's last token, cache).  Rows are padded to a power of two."""
    n = 1 << (len(rows) - 1).bit_length()
    tokens, counts = np.zeros((n, t), np.int32), np.zeros(n, np.int32)
    starts, tables = np.zeros(n, np.int32), np.full((n, MAXB + 1), NB - 1, np.int32)
    tables[:, -1] = cache[STATE]["recurrent"].shape[1] - 1  # the trash slot
    for i, (toks, start, blocks, slot) in enumerate(rows):
        tokens[i, :len(toks)], counts[i], starts[i] = toks, len(toks), start
        tables[i, :len(blocks)], tables[i, -1] = blocks, slot
    logits, cache = forward(params, jnp.asarray(tokens), jnp.asarray(counts), jnp.asarray(starts),
                            jnp.asarray(tables), cache, block_size=BS, live_token_bound=bound)
    return [np.asarray(logits[i, len(r[0]) - 1], np.float32) for i, r in enumerate(rows)], cache


def test_the_layout_is_the_layers_as_they_are_scanned(params):
    runs = [(0, 1, 1), (1, 1, 1), (2, 1, 1), (3, 1, 2), (5, 1, 1)]
    assert family.layer_segments(CFG) == ref.segments(SIZES) == runs
    twelve = family.BailingHybridConfig(num_layers=12, num_local_experts=64, vocab_size=19648)
    assert family.layer_segments(twelve) == [(0, 1, 2), (2, 1, 3), (5, 1, 1), (6, 1, 5), (11, 1, 1)]
    assert family.layer_segments(family.BailingHybridConfig()) == [(0, 1, 2), (2, 6, 6), (38, 1, 3), (41, 1, 1)]
    assert twelve.num_experts == 512 and twelve.layer_types.count(family.MLA) == 2
    own = family.init_params(CFG, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(params)
    assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(own)] == \
        [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(params)]
    mixer = own["segments"][1][0]["mixer"]  # the draw is the reference's: no channel at the bound
    np.testing.assert_allclose(mixer["dt_bias"], params["segments"][1][0]["mixer"]["dt_bias"], rtol=1e-6)
    np.testing.assert_allclose(mixer["A_log"], params["segments"][1][0]["mixer"]["A_log"], atol=1e-6)
    assert own["experts"]["w_gate"].shape[:2] == (5, HELD)  # the held experts of every expert layer
    assert own["segments"][1][0]["moe"]["gate"]["wg"].shape[-1] == 4 * HELD  # the router's width
    cache = fresh_cache()
    # the two MLA layers alone in the latent pool; the KDA layers' two leaves apart, float32
    assert cache["latent"].shape == (2, NB, 1, BS, 128)
    assert cache[STATE]["conv"].shape == (4, SLOTS + 1, 3, 3 * 64)
    assert cache[STATE]["recurrent"].shape == (4, SLOTS + 1, 4, 16, 16)
    half = fresh_cache(jnp.bfloat16)
    assert (half["latent"].dtype, half[STATE]["conv"].dtype, half[STATE]["recurrent"].dtype) == \
        (jnp.bfloat16, jnp.bfloat16, jnp.float32)
    assert family.state_bytes_per_seq(twelve) == 10 * (2097152 + 73728) == 21708800
    assert family.paged_value_dim(twelve) == 512 and family.latent_width(twelve) == 640


@pytest.mark.parametrize("what,keys", [
    ("SwiGLU", {"expert_swiglu_limits": (0, 0, 4)}), ("SwiGLU", {"shared_swiglu_limits": (5, )}),
    ("use_kda_lora", {"use_kda_lora": True}), ("kda_safe_gate", {"kda_safe_gate": False}),
    ("kda_lower_bound", {"kda_lower_bound": -8.0}), ("q_lora_rank", {"q_lora_rank": 1536}),
    ("rope_scaling", {"rope_scaling": {"type": "yarn"}}), ("use_mla_nope", {"use_mla_nope": True}),
    ("value_norm", {"value_norm": True}), ("up_proj_norm", {"up_proj_norm": True}),
    ("use_nGPT", {"use_ngpt": True}), ("scale_router_input", {"scale_router_input": True}),
    ("granularity", {"gate_granularity": "element_wise"}), ("topk_method", {"topk_method": "greedy"})])
def test_what_is_published_otherwise_and_not_built_is_refused(what, keys):
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


@pytest.mark.parametrize("chunks", [(64, 64, 22), (1, 70, 79)],
                         ids=lambda c: "x".join(map(str, c)))
def test_prefill_in_chunks_then_decode_steps_equal_the_reference(params, chunks):
    """A chunk continues from the matrices and the shift its sequence's slot holds
    and from the latents its blocks hold, across the scan's own chunks of 64 and the
    step's; a step of one token is the one-token update."""
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


def test_a_compacted_mixed_step_gives_each_sequence_what_it_gets_alone(params, forward):
    """Two chunks, a decode row and a prompt of one token that begins, of four
    sequences on the flat [1, S] axis: the one-token rows go to the update, the others
    are laid onto a chunk's edge and scanned from their own slots' matrices, and
    nothing crosses a sequence boundary in the scan, the shift, the slots or the pool."""
    seqs = [(ids_of(2, 160), list(range(0, 41)), 0), (ids_of(3, 80), list(range(41, 62)), 3),
            (ids_of(4, 9), [62, 63, 64], 1), (ids_of(5, 1), [65], 2)]
    heads = (70, 5, 8, 0)  # tokens already in the cache: two chunks continue, one row decodes
    cache = fresh_cache()
    for (ids, blocks, slot), done in zip(seqs, heads):
        if done:
            _, cache = step(params, cache, [(ids[:done], 0, blocks, slot)], t=256, forward=forward)
    rows = [(ids[done:], done, blocks, slot) for (ids, blocks, slot), done in zip(seqs, heads)]
    mixed, after = step(params, cache, rows, t=256, bound=176, forward=forward)  # [4, 256] > 176
    for i, r in enumerate(rows):
        (alone, ), single = step(params, cache, [r], t=256, forward=forward)
        close(mixed[i], alone)
        close(mixed[i], want(params, seqs[i][0], [r[1] + len(r[0]) - 1])[0])
        for leaf in ("conv", "recurrent"):
            close(np.asarray(after[STATE][leaf][:, r[3]]), np.asarray(single[STATE][leaf][:, r[3]]))
    for leaf in ("conv", "recurrent"):  # the trash slot apart, no other slot: all four are named
        assert np.isfinite(np.asarray(after[STATE][leaf])).all()


def test_a_sequence_that_begins_reads_nothing_its_slot_was_left_with(params, forward):
    """A slot is never zeroed: the sequence that takes it over begins (``start_pos
    == 0``) over NaNs, in a slot that is neither its row nor in row order, and is
    served as over a fresh cache, in a chunk pass and in the decode steps that
    follow; the slots no row names hold what they held."""
    second = ids_of(23, 70 + 2)
    blocks, slot = list(range(5, 30)), 3
    cache = fresh_cache()
    cache[STATE] = {leaf: rows.at[:, slot].set(jnp.nan).at[:, (0, 2)].set(3.0)
                    for leaf, rows in cache[STATE].items()}
    before, at = cache[STATE], 70
    (got, _), cache = step(params, cache, [(second[:at], 0, blocks, slot), ([], 0, [], SLOTS)], t=256,
                           forward=forward)
    for _ in range(2):
        close(got, want(params, second, [at - 1])[0])
        (got, ), cache = step(params, cache, [(second[at:at + 1], at, blocks, slot)], t=1,
                              forward=forward)
        at += 1
    close(got, want(params, second, [at - 1])[0])
    for leaf in ("conv", "recurrent"):
        np.testing.assert_array_equal(np.asarray(cache[STATE][leaf][:, (0, 2)]),
                                      np.asarray(before[leaf][:, (0, 2)]))


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
            return np.asarray(x @ params["lm_head"].astype(jnp.float32))
    finally:
        for name, fn in was.items():
            setattr(ref, name, fn)


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
    half = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 and a.ndim > 1 else a, params)
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
    """Six sequences through four slots (a slot reused after its sequence retires),
    chunked prefill under a budget of 32, compacted mixed passes, decode in fused
    bursts: the reference's greedy continuation, and the counters of both caches.  The
    last two prompts share 16 leading tokens (two whole blocks): mapped blocks would
    restore the latents and start the KDA state at zero in mid-prompt, so the prefix
    cache's hit is declined, counted, and both are served whole."""
    prompts = [ids_of(10 + i, n) for i, n in enumerate((5, 90, 140, 9))]
    prompts += [ids_of(20, 16) + ids_of(21, 54), ids_of(20, 16) + ids_of(22, 3)]
    eng, before = served, (served.counters.snapshot(), served.health()["state"])
    got = eng.generate(prompts, max_new_tokens=6)
    c = eng.counters.delta_since(before[0])
    assert c["burst_tokens"] > 0 and c["compact_passes"] > 0
    for p, g in list(zip(prompts, got))[1::2]:  # one cut in three, a short one, the sharer
        assert list(g) == greedy(params, p, 6)
    tree = eng.manager.prefix_cache
    assert tree.hit_blocks_total == 0 and tree.tokens_saved_total == 0
    state = eng.health()["state"]
    by_leaf = state.pop("state_bytes_by_leaf")
    assert by_leaf == {"conv": 4 * 3 * 192 * 4, "recurrent": 4 * 4 * 16 * 16 * 4}
    assert state == {"enabled": True, "state_slots": 4, "state_slots_in_use": 0,
                     "state_bytes_per_seq": family.state_bytes_per_seq(CFG),
                     "state_slots_zeroed": before[1]["state_slots_zeroed"] + 6,
                     "prefix_declined_stateful": before[1]["prefix_declined_stateful"] + 1}
    # the scan's counters: the tokens of the rows of more than one token, in each of the four
    # KDA layers; a decode step or a burst walks no chunk
    assert c["scan_positions"] == c["scan_chunks"] * 64
    assert 0 < c["scan_live_positions"] <= c["scan_positions"] and c["scan_live_positions"] % 4 == 0
    assert sum(map(len, prompts)) - 2 * len(prompts) <= c["scan_live_positions"] // 4 \
        <= sum(map(len, prompts)) < c["live_tokens"]
    assert c["moe_routed_rows"] == c["live_tokens"] * 4 * 5  # top-4 in five expert layers
    eng.check_kv_invariant()
