"""Arcee AFMoE (ISSUE 56): attention layers of two kinds in one model: three
windowed rotary layers and one full layer without positions a period, a gated
output, QK-norm, sandwich norms, a leading dense layer, then sigmoid-routed
experts beside a shared one.

The program (``models/afmoe.py`` on ``transformer.paged_forward`` with a window a
layer of the period, through the engine's scheduler, pool and bursts) against
its own plain ``forward`` and against the plain reference
(``chipbench/references/afmoe.py``: whole sequences, a dense mask a layer kind)
in float32, at a window of 24 tokens and two periods of four expert layers.  One
tiny model, one set of weights and one engine a module; a case is data.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import afmoe as ref
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import afmoe as family
from deepspeed_tpu.models import mistral, transformer

HELD, WINDOW = 2, 24  # of 16 experts: one chip's share of eight
SIZES = {"global_attn_every_n_layers": 4, "head_dim": 16, "hidden_act": "silu", "hidden_size": 64,
         "intermediate_size": 128,
         "layer_types": ["full_attention" if i % 4 == 3 else "sliding_attention" for i in range(60)],
         "max_position_embeddings": 512, "model_type": "afmoe", "moe_intermediate_size": 32,
         "mup_enabled": True, "n_group": 1, "num_attention_heads": 4, "num_dense_layers": 1,
         "num_experts": HELD, "num_experts_per_tok": 4, "num_hidden_layers": 9,
         "num_key_value_heads": 2, "num_shared_experts": 1, "rms_norm_eps": 1e-5,
         "rope_scaling": None, "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
         "score_func": "sigmoid", "sliding_window": WINDOW, "tie_word_embeddings": False,
         "topk_group": 1, "vocab_size": 256}
CFG = family.AfmoeConfig.tiny(window=WINDOW, experts=ref.EP_CHIPS * HELD, local_experts=HELD, top_k=4)
REL_TOL = 1e-4  # of logits, as a share of the largest (``close``)
PROMPTS = (9, 40, 101, 23)  # inside the window, past it, four times it, and inside again


@pytest.fixture(scope="module")
def params():
    drawn = jax.jit(lambda key: ref.init_params(SIZES, key, jnp.float32))(jax.random.PRNGKey(7))
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 128))

    def off_neutral(path, leaf):  # a gain left out or misplaced must show
        if any("norm" in str(getattr(p, "key", "")) for p in path):
            return leaf * (1 + 0.3 * jax.random.normal(next(keys), leaf.shape))
        return leaf
    return jax.tree_util.tree_map_with_path(off_neutral, drawn)


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n).tolist()


def close(got, wanted, tol=REL_TOL):
    np.testing.assert_allclose(got, wanted, atol=tol * np.abs(wanted).max(), rtol=0)


@pytest.fixture(scope="module")
def served(params):
    eng = InferenceEngineV2(family, CFG, params, block_size=8, num_blocks=96, max_blocks_per_seq=16,
                            token_budget=32, max_seqs_per_step=4, config={"dtype": "float32"})
    prompts = [ids_of(i, n) for i, n in enumerate(PROMPTS)]
    return eng, prompts, eng.generate(prompts, max_new_tokens=6)


def test_the_layout_is_the_layers_as_they_are_scanned(params):
    assert family.layer_segments(CFG) == ref.segments(SIZES) == [(0, 1, 1), (1, 4, 2)]
    mine = family.init_params(CFG, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(jnp.shape, mine) == jax.tree_util.tree_map(jnp.shape, params)
    assert family.attention_windows(CFG) == (WINDOW, WINDOW, WINDOW, None) * 2 + (WINDOW, )
    layers, windows = family.scanned_layers(CFG, params)
    assert windows == [(WINDOW, ), (WINDOW, WINDOW, None, WINDOW)]
    # a layer's kind is its place in the period: the windowed ones hold their rotary frequencies
    assert ["inv_freq" in lp for lp in layers[1]] == [True, True, False, True]
    assert layers[1][2]["moe"]["layer"].tolist() == [2, 6] and "mlp" in layers[0][0]
    published = family.AfmoeConfig.trinity_large_preview()
    assert family.layer_segments(published)[-3:] == [(6, 4, 13), (58, 1, 1), (59, 1, 1)]
    assert family.attention_windows(published).count(None) == 15


def test_generate_through_chunks_decode_and_a_burst_is_forwards_greedy(params, served):
    """Chunked prefill (a 101-token prompt is four chunks of the budget), mixed
    compacted passes, decode and a fused burst, against the plain forward: every
    prompt on its side of the window."""
    eng, prompts, results = served
    forward = jax.jit(lambda ids: family.forward(CFG, params, ids))
    for prompt, got in zip(prompts, results):
        ids = list(prompt)
        for _ in range(6):  # one shape for all: the masks are causal, the tail is not read
            padded = jnp.asarray([ids + [0] * (max(PROMPTS) + 6 - len(ids))])
            ids.append(int(jnp.argmax(forward(padded)[0, len(ids) - 1])))
        assert list(got) == ids, len(prompt)
    counted = eng.counters.snapshot()
    assert counted["compact_passes"] > 0 and counted["burst_tokens"] > 0
    # what lies behind a window is counted beside the live blocks the one table holds
    assert 0 < counted["kv_blocks_behind_window"] < counted["live_blocks"] * 7
    assert counted["moe_routed_rows"] == counted["live_tokens"] * 4 * 8


def test_the_blocks_behind_a_window_are_counted_from_each_rows_first_token():
    from deepspeed_tpu.inference.v2.fastpath import ServeCounters
    spans = [(0, 32), (96, 5), (100, 1)]  # a chunk that begins, one four windows in, a decode row
    counted = ServeCounters(windowed=(family.attention_windows(CFG), 8))
    counted.count_slots(4, 32, 16, 38, 4 + 13 + 13, spans=spans)
    # seven windowed layers of window 24 over blocks of 8: keys 73.. and 77.. lie in block 9
    assert counted.reads_spans and counted.snapshot()["kv_blocks_behind_window"] == 7 * (0 + 9 + 9)
    # a burst of three passes moves each row a token a pass: starts 100, 101, 102 and 103, 104, 105
    counted.count_slots(2, 1, 16, 2, 13 + 14, passes=3, spans=[(100, 1), (103, 1)])
    assert counted.kv_blocks_behind_window == 7 * (18 + 9 + 9 + 9 + 10 + 10 + 10)
    # no other family's snapshot gains a key
    assert not set(ServeCounters.WINDOWED_FIELDS) & set(ServeCounters().snapshot())


def served_row(params, ids):
    """The program's logits at the last token of ``ids``: prefill in chunks of 32
    through ``forward_paged`` (padded), then read."""
    cache = family.init_paged_cache(CFG, 32, 8, dtype=jnp.float32)
    table = jnp.arange(16, dtype=jnp.int32)[None]
    for start in range(0, len(ids), 32):
        chunk = ids[start:start + 32]
        tokens = jnp.asarray([chunk + [0] * (32 - len(chunk))], jnp.int32)
        logits, cache = family.forward_paged(CFG, params, tokens, jnp.asarray([len(chunk)]),
                                             jnp.asarray([start]), table, cache, block_size=8)
    return np.asarray(logits[0, len(chunk) - 1])


# misreadings of the published layer, stated on the reference: each must NOT pass for the program
def _attention_as(window=lambda w: w, turned=lambda t: t):
    plain = ref.attention
    return lambda sizes, u, w, win, rot: plain(sizes, u, w, window(win), turned(rot))


def _no_gate(params):  # a gate of one half everywhere: the sandwich norm takes the constant out
    def zero(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        return jnp.zeros_like(leaf) if names[-2:] == ["attn", "w_gate"] else leaf
    return jax.tree_util.tree_map_with_path(zero, params)


WRONG = {
    "the full layer given the window": dict(attention=_attention_as(window=lambda w: WINDOW)),
    "a windowed layer given none": dict(attention=_attention_as(window=lambda w: None)),
    "rotary on the full layer": dict(attention=_attention_as(turned=lambda t: True)),
    "a window of one position more": dict(sizes={"sliding_window": WINDOW + 1}),
    "the gate left out": dict(params=_no_gate),
    "the bias weighs": dict(router=lambda sizes, n, gate: _biased(sizes, n, gate)),
}


def _biased(sizes, n, gate):  # weights from score + bias: the selection bias must choose and never weigh
    scores = jax.nn.sigmoid(n @ gate["wg"].astype(jnp.float32)) + gate["bias"] * 40
    top, idx = jax.lax.top_k(scores, sizes["num_experts_per_tok"])
    top = top / jnp.sum(top, -1, keepdims=True) * sizes["route_scale"]
    return jnp.zeros_like(scores).at[jnp.arange(len(n))[:, None], idx].set(top)


@pytest.fixture(scope="module")
def long_row(params):
    ids = ids_of(9, 101)
    return ids, served_row(params, ids)


def test_the_program_is_the_reference_past_both_kinds_of_window(params, long_row):
    ids, got = long_row
    close(got, np.asarray(ref.logits_rows(SIZES, params, ids, [len(ids) - 1]))[0])


@pytest.mark.parametrize("reading", sorted(WRONG))
def test_a_wrong_reading_of_the_published_layer_does_not_pass(params, long_row, monkeypatch, reading):
    ids, got = long_row
    how = WRONG[reading]
    for name in ("attention", "router"):
        if name in how:
            monkeypatch.setattr(ref, name, how[name])
    sizes = {**SIZES, **how.get("sizes", {}), "reading": reading}  # a key of its own: no cached trace
    wrong = np.asarray(ref.logits_rows(sizes, how.get("params", lambda p: p)(params), ids,
                                       [len(ids) - 1]))[0]
    assert np.abs(got - wrong).max() > 100 * REL_TOL * np.abs(wrong).max(), reading


def test_one_window_for_every_layer_traces_as_it_did(params):
    """A family that gives ``paged_forward`` one window (Mistral) has no scope of a
    layer's kind in its program; this family's windowed and full layers each have theirs."""
    def scopes_of(module, cfg, p, cache):
        text = jax.jit(lambda *a: module.forward_paged(cfg, *a, block_size=8)).lower(
            p, jnp.zeros((2, 8), jnp.int32), jnp.ones((2, ), jnp.int32), jnp.zeros((2, ), jnp.int32),
            jnp.zeros((2, 4), jnp.int32), cache).as_text(debug_info=True)
        return {name for name in ("attn_window", "attn_full", "attn_kernel") if name in text}

    cfg = mistral.MistralConfig.tiny()
    theirs = mistral.init_params(cfg, jax.random.PRNGKey(0))
    assert scopes_of(mistral, cfg, theirs, mistral.init_paged_cache(cfg, 8, 8, dtype=jnp.float32)) \
        == {"attn_kernel"}
    assert scopes_of(family, CFG, params, family.init_paged_cache(CFG, 8, 8, dtype=jnp.float32)) \
        == {"attn_window", "attn_full", "attn_kernel"}


def test_what_is_published_otherwise_and_not_built_is_refused(params):
    for keys in ({"rope_scaling": {"type": "yarn"}}, {"tie_embeddings": True},
                 {"score_func": "softmax"}, {"n_group": 2}, {"layer_types": ("chunked", ) * 60}):
        with pytest.raises(NotImplementedError, match="afmoe"):
            family.AfmoeConfig(**keys)
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        family.forward_paged(CFG, params, None, None, None, None, {"k": jnp.zeros(1)}, block_size=8,
                             tp_axis="tensor")
    with pytest.raises(ValueError, match="mix"):  # a window a layer is the attention layers' alone
        transformer.paged_forward([{transformer.STATE_MIXER: {}, "w": jnp.zeros((1, 1))}],
                                  jnp.zeros((1, 1), jnp.int32), jnp.ones(1, jnp.int32),
                                  jnp.zeros(1, jnp.int32), jnp.zeros((1, 2), jnp.int32),
                                  {"k": jnp.zeros((1, 2, 1, 8, 8))}, block_size=8,
                                  live_token_bound=None, embed=lambda t, p: jnp.zeros((1, 1, 8)),
                                  qkv=None, finish=None, head=None, window=[(None, )])
