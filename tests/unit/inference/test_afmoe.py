"""Arcee AFMoE (ISSUE 56): attention layers of two kinds in one model: three
windowed rotary layers and one full layer without positions a period, a gated
output, QK-norm, sandwich norms, a leading dense layer, then sigmoid-routed
experts beside a shared one.

The program (``models/afmoe.py`` on ``transformer.paged_forward`` with a window a
layer of the period, through the engine's scheduler, pool and bursts) against
its own plain ``forward`` and against the plain reference
(``chipbench/references/afmoe.py``: whole sequences, a dense mask a layer kind)
in float32, at a window of 24 tokens and two periods of four expert layers.
The shared cases are ``family_contract.py``'s; this file builds three engine
configurations (``served``, ``oracle``, and the contract's engine under
speculation, which this family serves).
"""

import jax
import jax.numpy as jnp
import pytest

from chipbench.references import afmoe as ref
from deepspeed_tpu.inference.v2.fastpath import ServeCounters
from deepspeed_tpu.models import afmoe as family
from deepspeed_tpu.models import mistral, transformer
from tests.unit.inference.family_contract import Family, Pool, ServingContract, WrongReadings

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
REL_TOL = 1e-4
PROMPTS = (9, 40, 101, 23)  # inside the window, past it, four times it, and inside again


def off_neutral(names, leaf, noise):  # a gain left out or misplaced must show
    return leaf * (1 + 0.3 * noise(leaf.shape)) if any("norm" in str(n) for n in names) else leaf


def layout(h, own, cache):
    assert family.attention_windows(CFG) == (WINDOW, WINDOW, WINDOW, None) * 2 + (WINDOW, )
    layers, windows = family.scanned_layers(CFG, h.params)
    assert windows == [(WINDOW, ), (WINDOW, WINDOW, None, WINDOW)]
    # a layer's kind is its place in the period: the windowed ones hold their rotary frequencies
    assert ["inv_freq" in lp for lp in layers[1]] == [True, True, False, True]
    assert layers[1][2]["moe"]["layer"].tolist() == [2, 6] and "mlp" in layers[0][0]
    published = family.AfmoeConfig.trinity_large_preview()
    assert family.layer_segments(published)[-3:] == [(6, 4, 13), (58, 1, 1), (59, 1, 1)]
    assert family.attention_windows(published).count(None) == 15


def wave(h, seen):
    """Against the plain forward too: every prompt on its side of the window."""
    forward = jax.jit(lambda ids: family.forward(CFG, h.params, ids))
    for prompt, got in zip(seen.prompts, seen.got):
        ids = list(prompt)
        for _ in range(FAMILY.new_tokens):  # one shape for all: the masks are causal, the tail is not read
            padded = jnp.asarray([ids + [0] * (max(PROMPTS) + FAMILY.new_tokens - len(ids))])
            ids.append(int(jnp.argmax(forward(padded)[0, len(ids) - 1])))
        assert got == ids, len(prompt)
    c = seen.counters
    # what lies behind a window is counted beside the live blocks the one table holds
    assert 0 < c["kv_blocks_behind_window"] < c["live_blocks"] * 7
    assert c["moe_routed_rows"] == c["live_tokens"] * 4 * 8


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


FAMILY = Family(
    module=family, reference=ref, sizes=SIZES, config=CFG,
    tolerance=REL_TOL,
    tolerance_reason="1e-4 of the largest logit: sound float32 runs of nine such layers read under 1e-5, "
    "and each misreading of the published layer below reads over a hundred times the tolerance",
    off_neutral=off_neutral, pool=Pool(72, 4, 48), segments=[(0, 1, 1), (1, 4, 2)],
    # a prompt six times the window in chunks that end inside it, on its edge and past it
    chunkings=((150, ), (64, 64, 22), (1, 70, 79)), mixed=((160, 70, 160), (80, 5, 80), (40, 39, 40)),
    engine=dict(num_blocks=96, block_size=8, max_blocks_per_seq=16, token_budget=32, max_seqs_per_step=4),
    # chunked prefill (a 101-token prompt is four chunks of the budget), mixed compacted passes,
    # decode and a fused burst; against the reference: the prompt past the window and the one four
    # times it (the wave's hook holds all four against the plain forward)
    waves=(PROMPTS, ), compared=(1, 2),
    layout=layout, wave=wave,
    # each misreading below reads over a hundred times the tolerance, six windows into a prompt
    wrong_readings=WRONG, wrong_margin=100)

class TestAfmoe(ServingContract, WrongReadings):
    family = FAMILY

    def test_one_window_for_every_layer_traces_as_it_did(self, h):
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
        assert scopes_of(family, CFG, h.params, family.init_paged_cache(CFG, 8, 8, dtype=jnp.float32)) \
            == {"attn_window", "attn_full", "attn_kernel"}

    def test_what_is_published_otherwise_and_not_built_is_refused(self, h):
        for keys in ({"rope_scaling": {"type": "yarn"}}, {"tie_embeddings": True},
                     {"score_func": "softmax"}, {"n_group": 2}, {"layer_types": ("chunked", ) * 60}):
            with pytest.raises(NotImplementedError, match="afmoe"):
                family.AfmoeConfig(**keys)
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            family.forward_paged(CFG, h.params, None, None, None, None, {"k": jnp.zeros(1)}, block_size=8,
                                 tp_axis="tensor")
        with pytest.raises(ValueError, match="mix"):  # a window a layer is the attention layers' alone
            transformer.paged_forward([{transformer.STATE_MIXER: {}, "w": jnp.zeros((1, 1))}],
                                      jnp.zeros((1, 1), jnp.int32), jnp.ones(1, jnp.int32),
                                      jnp.zeros(1, jnp.int32), jnp.zeros((1, 2), jnp.int32),
                                      {"k": jnp.zeros((1, 2, 1, 8, 8))}, block_size=8,
                                      live_token_bound=None, embed=lambda t, p: jnp.zeros((1, 1, 8)),
                                      qkv=None, finish=None, head=None, window=[(None, )])


def test_the_blocks_behind_a_window_are_counted_from_each_rows_first_token():
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
