"""Nemotron-H (ISSUE 62): a layer is ONE part alone, a Mamba-2 mixer (B and C a
GROUP of heads, a gated norm over groups of columns), ungated relu^2 experts
under a sigmoid router with a selection bias beside a shared expert, or NoPE GQA
attention with a published head width; an untied head.

The program (``models/nemotron_h.py`` on ``transformer.paged_forward``, through
the engine's scheduler, manager, bursts) against the plain reference
(``chipbench/references/nemotron_h.py``: whole sequences, the recurrence token
by token, no state, no cache) in float32 at two periods of ``ME*E`` (2 ``M``, 4
``E``, 2 ``*``: every kind of layer, two ``E`` layers a period so that a layer's place
in the one expert stack is counted across positions and repeats; the published period
``MEMEM*E`` holds the same three kinds and compiles in twice the time: its layout is
held below without a program, its programs by ``test_tpu_compile_programs.py -k
one_part_alone`` and the benchmark's rehearsal).  The shared cases are
``family_contract.py``'s; this file builds two engine configurations (``served``,
``oracle``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import nemotron_h as ref
from deepspeed_tpu.models import nemotron_h as family
from deepspeed_tpu.models.transformer import STATE, TALLY
from deepspeed_tpu.moe.serving import sparse_moe_ffn
from deepspeed_tpu.ops.linear_attention.ssd import CHUNK
from tests.unit.inference.family_contract import Family, Pool, StatefulContract, WrongReadings

HELD = 4  # of 8 experts: one chip's share of two
PATTERN = "ME*E" * 2
# ``expand`` 3 where the inner width is 8 heads x 16 = 2 x hidden: a reading of ``expand x
# hidden`` as the inner width would find 192 columns where the layout below finds 128
SIZES = {"attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 3, "head_dim": 32,
         "hidden_size": 64, "hybrid_override_pattern": PATTERN, "intermediate_size": 32,
         "layer_norm_epsilon": 1e-5, "mamba_head_dim": 16, "mamba_hidden_act": "silu",
         "mamba_num_heads": 8, "mamba_proj_bias": False, "max_position_embeddings": 512,
         "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
         "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 64, "n_group": 1,
         "n_groups": 4, "n_routed_experts": HELD, "n_shared_experts": 1, "norm_eps": 1e-5,
         "norm_topk_prob": True, "num_attention_heads": 4, "num_experts_per_tok": 3,
         "num_hidden_layers": 8, "num_key_value_heads": 2, "partial_rotary_factor": 1,
         "rope_theta": 10000, "routed_scaling_factor": 2.5, "ssm_state_size": 16,
         "tie_word_embeddings": False, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
         "vocab_size": 256}
CFG = family.NemotronHConfig.tiny(experts=ref.EP_CHIPS * HELD, held_experts=HELD, layers=8, pattern=PATTERN)
NB, BS, SLOTS = 72, 4, 4
TOL = 2e-5  # of the expert layer alone
NORMS = {"final_norm", "norm", "D"}


def off_neutral(names, leaf, noise):  # a gain or a D left out or misplaced must show
    if names[-1] == "bias":  # the router's: wide enough that weighing by it shows (drawn at 0.01)
        return leaf + 0.1 * noise(leaf.shape)
    return leaf + 0.3 * noise(leaf.shape) if NORMS & set(names) else leaf


def layout(h, own, cache):
    assert family.layer_segments(family.NemotronHConfig(num_layers=14)) == [(0, 7, 2)]
    assert family.NemotronHConfig().kinds.count("M") == family.NemotronHConfig().kinds.count("E") == 23
    published = family.NemotronHConfig(num_layers=14, held_experts=64)
    shapes = jax.eval_shape(lambda key: family.init_params(published, key), jax.random.PRNGKey(0))
    by_kind = family.layers_by_kind(published, shapes["segments"])  # an E layer's place in the one stack
    assert [np.asarray(lp["alone"]["layer"]).tolist() for lp in by_kind[0] if "alone" in lp] == [
        [0, 3], [1, 4], [2, 5]]
    assert family.layer_segments(CFG) == [(0, 4, 2)] and CFG.kinds == PATTERN
    assert [np.asarray(lp["alone"]["layer"]).tolist() for lp in family.layers_by_kind(CFG, own["segments"])[0]
            if "alone" in lp] == [[0, 2], [1, 3]]
    assert own["experts"]["w_up"].shape[:2] == (4, HELD) and "w_gate" not in own["experts"]
    e_layer = own["segments"][0][1]["alone"]
    assert e_layer["gate"]["wg"].shape[-1] == 2 * HELD and "w_gate" not in e_layer["shared"]
    # I = H P = 128 (``expand`` 3 is read nowhere): W_in's columns 128 | 128 + 2 x 4 x 16 | 8
    assert family.ssm_widths(CFG) == (128, 256, 392) == ref.ssm_widths(SIZES)[-2:] + (392, )
    assert own["segments"][0][0]["mixer"]["w_in"].shape == (2, 64, 392)
    assert own["head"].shape == (64, 256) and own["embed"].shape == (256, 64)  # untied
    # an E layer has a row in neither cache: 2 state rows, 2 pool rows for 8 layers
    assert cache["k"].shape == cache["v"].shape == (2, NB, 2, BS, 32)
    assert cache[STATE]["conv"].shape == (2, SLOTS + 1, 3, 256)
    assert cache[STATE]["ssm"].shape == (2, SLOTS + 1, 8, 16, 16)
    assert cache[TALLY].shape == (3, )
    half = h.fresh_cache(jnp.bfloat16)[STATE]
    assert (half["conv"].dtype, half["ssm"].dtype) == (jnp.bfloat16, jnp.float32)
    cut = family.NemotronHConfig(num_layers=14, held_experts=64, vocab_size=65536)
    assert family.state_bytes_per_seq(cut) == 6 * (2097152 + 36864) == 12804096
    assert family.ssm_widths(cut) == (4096, 6144, 10304)
    assert family.moe_picks_per_token(cut) == 6 * 6 and family.moe_expert_rows(cut, 64) == 6 * 256


def wave(h, seen):
    c, eng, prompts = seen.counters, seen.engine, seen.prompts
    assert eng.health()["state"]["state_bytes_by_leaf"] == {"conv": 2 * 3 * 256 * 4, "ssm": 2 * 8 * 16 * 16 * 4}
    # counted by kind of layer: the scans over the two M layers, the picks over the four E layers
    assert c["scan_positions"] == c["scan_chunks"] * CHUNK
    assert 0 < c["scan_live_positions"] <= c["scan_positions"] and c["scan_live_positions"] % 2 == 0
    assert sum(map(len, prompts)) - len(prompts) <= c["scan_live_positions"] // 2 \
        <= sum(map(len, prompts)) < c["live_tokens"]
    assert c["moe_routed_rows"] == c["live_tokens"] * 3 * 4
    # the tally of the device: about half the picks are held here (a burst's frozen rows are
    # counted as live by the device alone, so the bounds are loose)
    assert 0.3 * c["moe_routed_rows"] < c["moe_held_picks"] < 0.8 * c["moe_routed_rows"]
    assert 0 < c["moe_experts_hit"] <= min(c["moe_held_picks"], HELD * 4 * (c["dispatches"] + c["burst_tokens"]))
    assert set(c) == set(eng.counters.FIELDS) | {"scan_overflow_windows"} | set(eng.counters.TALLIED_FIELDS)


# ------------------------------------------------- readings that must not pass
def rotated(sizes, u, w):  # rotate-half rotary over q and k, as a "rope" model would
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    w = ref.f32(w)
    angle = jnp.arange(u.shape[0])[:, None] * 10000.0 ** (-jnp.arange(dh // 2) / (dh // 2))
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    turn = lambda x: jnp.concatenate([x[..., :dh // 2] * cos - x[..., dh // 2:] * sin,
                                      x[..., dh // 2:] * cos + x[..., :dh // 2] * sin], axis=-1)
    out = ref.causal_attention(turn((u @ w["wq"]).reshape(-1, h, dh)),
                               turn((u @ w["wk"]).reshape(-1, kv, dh)),
                               (u @ w["wv"]).reshape(-1, kv, dh), dh ** -0.5)
    return out.reshape(-1, h * dh) @ w["wo"]


def bias_weighs(sizes, u, gate):  # the weights taken from the BIASED scores
    gate = ref.f32(gate)
    scores = jax.nn.sigmoid(u @ gate["wg"]) + gate["bias"]
    picked, picks = jax.lax.top_k(scores, sizes["num_experts_per_tok"])
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * sizes["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(scores.shape[0])[:, None], picks].set(weights)


def _mlp(act):
    def mlp(x, w):
        w = ref.f32(w)
        return act(x @ w["w_up"].T) @ w["w_down"]
    return mlp


_scan = ref.selective_scan
_group_rms = lambda y, groups, eps: ref.rms_norm(y.reshape(y.shape[0], groups, -1), 1.0, eps).reshape(y.shape)
WRONG = {
    "a gated expert": dict(relu2_mlp=_mlp(lambda h: jax.nn.silu(h) * h)),  # the one matrix as gate and up
    "silu for relu squared": dict(relu2_mlp=_mlp(jax.nn.silu)),
    "one B/C group": dict(selective_scan=lambda x, dt, a, b, c, d, state=None: _scan(
        x, dt, a, b[:, :1], c[:, :1], d, state)),
    "the norm over all columns": dict(gated_group_norm=lambda y, z, gain, groups, eps: ref.rms_norm(
        y * jax.nn.silu(z), gain, eps)),
    "the norm before the gate": dict(gated_group_norm=lambda y, z, gain, groups, eps: _group_rms(
        y, groups, eps) * gain * jax.nn.silu(z)),
    "rotary applied": dict(attention=rotated),
    "a tied head": dict(params=lambda p: {**p, "head": p["embed"].T * 50.0}),  # at the head's scale
    "the bias weighing": dict(router=bias_weighs),
    "routed_scaling_factor left out": dict(sizes={"routed_scaling_factor": 1.0}),
}


FAMILY = Family(
    module=family, reference=ref, sizes=SIZES, config=CFG,
    tolerance=1e-4,
    tolerance_reason="""1e-4 of the largest logit.  Two float32 programs of eight such layers
    (the chunked scan against the token-by-token recurrence, sorted dispatch of the
    held picks against every expert, a paged softmax against a dense one) read 3e-6
    apart at the row the wrong readings are held against; the weakest wrong reading
    below reads over 1e-2 (the margin asks ten times the tolerance), and bfloat16 in
    float32's place over 1e-2.  (``expand x hidden`` as the inner width changes
    shapes and cannot be run: the layout case holds it.)""",
    off_neutral=off_neutral, pool=Pool(NB, BS, 48, SLOTS), state_leaves=("conv", "ssm"),
    segments=[(0, 4, 2)],
    chunkings=((150, ), (64, 64, 22)), decode_steps=2, layout=layout, wave=wave,
    wrong_readings=WRONG, wrong_margin=10.0)


class TestNemotronH(StatefulContract, WrongReadings):
    family = FAMILY

    @pytest.mark.parametrize("what,keys", [
        ("dense layer", {"hybrid_override_pattern": "ME-*", "num_layers": 4}),
        ("projection bias", {"mamba_proj_bias": True}), ("tied head", {"tie_embeddings": True}),
        ("mlp_hidden_act", {"mlp_hidden_act": "silu"}), ("group-limited", {"n_group": 8, "topk_group": 4}),
        ("B/C groups", {"n_groups": 7})])
    def test_what_is_published_otherwise_and_not_built_is_refused(self, what, keys):
        with pytest.raises(NotImplementedError, match=what):
            family.NemotronHConfig(**keys)

    def test_a_dt_clamp_is_refused_where_a_checkpoints_config_states_one(self):
        from types import SimpleNamespace
        with pytest.raises(NotImplementedError, match="dt clamp"):
            family.config_from_hf(SimpleNamespace(time_step_limit=(0.001, 0.1)))

    def test_the_kernels_interpreted_serve_chunks_a_mixed_pass_and_decode_as_the_reference(self, h):
        """The Pallas kernels interpreted (``ssd_scan`` with four groups a grid step,
        ``ssd_update`` likewise, the paged kernel, ``gmm``): a prompt in two chunks,
        then one compacted pass of its third chunk beside a decode row of another
        sequence and a prompt of one token, then a decode step."""
        forward = h.interpreted()
        a, b, c = h.ids_of(81, 100), h.ids_of(82, 12), h.ids_of(83, 1)
        blocks = [list(range(0, 25)), list(range(25, 29)), [29]]
        cache = h.fresh_cache()
        _, cache = h.step(cache, [(a[:64], 0, blocks[0], 3), (b[:11], 0, blocks[1], 0)], t=64, forward=forward)
        rows = [(a[64:99], 64, blocks[0], 3), (b[11:], 11, blocks[1], 0), (c, 0, blocks[2], 2)]
        got, cache = h.step(cache, rows, t=64, bound=48, forward=forward)  # [4, 64] > 48: compacted
        for row, (ids, upto) in zip(got, ((a, 99), (b, 12), (c, 1))):
            h.close(row, h.want(ids, [upto - 1])[0])
        (got, ), _ = h.step(cache, [(a[99:], 99, blocks[0], 3)], t=1, forward=forward)
        h.close(got, h.want(a, [99])[0])

    # ------------------------------------------------------------------ the experts
    def test_the_two_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(self, h, monkeypatch):
        """``sparse_moe_ffn`` on chip 0's share and on chip 1's (the router's columns
        and bias rolled so that chip 1's experts stand first) against the reference's
        ``layer_parts``, and both shares' routed parts plus the shared expert ONCE
        against the layer with all eight experts held."""
        sizes = {**SIZES, "n_routed_experts": 2 * HELD}
        monkeypatch.setattr(ref, "EP_CHIPS", 1)  # every expert the router scores is drawn
        whole = jax.jit(lambda key: ref.init_params(sizes, key, jnp.float32))(jax.random.PRNGKey(3))
        assert whole["experts"]["w_up"].shape[:2] == (4, 2 * HELD)
        moe = jax.tree_util.tree_map(lambda a: a[1], whole["segments"][0][1]["alone"])
        x = jax.random.normal(jax.random.PRNGKey(9), (37, 64))
        route = dict(top_k=3, renormalise=True, scaling=2.5, scoring="sigmoid", norm_eps=1e-20)
        program = jax.jit(lambda m, a: sparse_moe_ffn(m, a, layer=jnp.int32(3), **route))  # a trace a share's shapes
        parts_of = jax.jit(lambda m, a, chip: ref.layer_parts(sizes, m, a, chip=chip, layer=3), static_argnums=2)
        with jax.default_matmul_precision("highest"):
            uncut = program({**moe, "experts": whole["experts"]}, x)
            routed, shared = parts_of({**moe, "experts": whole["experts"]}, x, 0)
            np.testing.assert_allclose(np.asarray(uncut), np.asarray(routed + shared), atol=TOL, rtol=0)
            parts = []
            for chip in range(2):
                mine = jax.tree_util.tree_map(lambda a: a[:, chip * HELD:(chip + 1) * HELD], whole["experts"])
                # this chip's experts first among the router's outputs: held = the first HELD
                gate = {k: jnp.roll(v, -chip * HELD, axis=-1) for k, v in moe["gate"].items()}
                got = program({"gate": gate, "experts": mine}, x)
                want, _ = parts_of({**moe, "experts": mine}, x, chip)
                np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL, rtol=0)
                parts.append(got)
            np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared), np.asarray(uncut),
                                       atol=TOL, rtol=0)
        assert all(np.abs(np.asarray(p)).max() > 0.01 for p in parts + [shared])
