"""The plain reference of Ling-3.0-flash's layers (``chipbench/references/
bailing_hybrid.py``): that the configuration is the published row cut as it says,
what the reference's own pieces state (the delta rule's decay a channel, the gate's
bound, the rotary's pairs, the router's groups), that the eight chips' shares add up
to the uncut layer, and the program's share against chip 0's
(``tests/unit/inference/test_bailing_hybrid.py`` holds the program to the reference in
float32, with the misreadings that must not pass; ``test_readers_kda.py`` walks the
benchmark's own comparison at the rehearsal).  The installed
``transformers`` has no ``bailing_hybrid``: nothing here can hold the reference to the
source's code."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common
from chipbench.references import bailing_hybrid as ref

CONFIG = "ling-3.0-flash-serve-ep8-12l"
SPEC = common.load_json("configs", CONFIG + ".json")
TINY = {**common.published_sizes(SPEC, True), "num_hidden_layers": 3, "layer_group_size": 3}


def drawn(sizes, seed=3):
    return jax.jit(lambda key: ref.init_params(sizes, key, jnp.float32))(jax.random.PRNGKey(seed))


@pytest.mark.reads_benchmark
def test_the_configuration_is_the_published_model_cut_to_one_chips_share():
    published = common.load_json("published", SPEC["published"] + ".json")["config"]
    changed = {k for k, v in published.items() if SPEC[k] != v}
    assert changed == set(SPEC["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    sizes = common.published_sizes(SPEC, False)
    assert ref.router_width(sizes) == published["num_experts"] == 512
    assert sizes["vocab_size"] * sizes["n_group"] == published["vocab_size"]
    kinds = ref.layer_kinds(sizes)  # two whole periods, the two leading layers dense as published
    assert [k for k, _ in kinds] == (["kda"] * 5 + ["mla"]) * 2
    assert [dense for _, dense in kinds] == [True] * 2 + [False] * 10
    assert ref.segments(sizes) == [(0, 1, 2), (2, 1, 3), (5, 1, 1), (6, 1, 5), (11, 1, 1)]
    shapes = jax.eval_shape(lambda key: ref.init_params(sizes, key, jnp.bfloat16), jax.random.PRNGKey(0))
    assert common.count_params(shapes) == 4_632_393_792  # 9.26 GB at 2 bytes
    assert shapes["experts"]["w_gate"].shape == (10, 64, 2560, 768)
    assert shapes["segments"][1][0]["moe"]["gate"]["wg"].shape == (3, 2560, 512)
    engine = SPEC["engine"]  # the longest prompt and its answer fit a sequence's table
    assert (32721 + 32) <= engine["max_blocks_per_seq"] * engine["block_size"]
    with pytest.raises(NotImplementedError, match="swiglu limit"):  # the clamp is refused, not guessed
        ref.check({**sizes, "num_hidden_layers": 42})  # the lists keep their 42 published entries


def test_the_draw_keeps_every_channels_decay_off_the_bound():
    sizes = common.published_sizes(SPEC, False)
    mixer = jax.eval_shape(lambda key: ref.init_params(sizes, key, jnp.float32),
                           jax.random.PRNGKey(0))["segments"][1][0]["mixer"]
    assert (mixer["A_log"].shape, mixer["dt_bias"].shape) == ((3, 32), (3, 4096))  # a head's, a channel's
    mixer = jax.tree_util.tree_map(lambda a: a[0], drawn(TINY)["segments"][0][0]["mixer"])
    u = jnp.zeros((1, TINY["hidden_size"]))
    decay = np.exp(np.asarray(ref.kda_gate(TINY, u, ref.f32(mixer))))  # at f = 0
    assert 0.85 < decay.min() < 0.95 and 0.999 < decay.max() < 1.0
    u = jax.random.normal(jax.random.PRNGKey(1), (64, TINY["hidden_size"]))
    g = np.asarray(ref.kda_gate(TINY, u, ref.f32(mixer)))
    assert g.min() > TINY["kda_lower_bound"] / 2 and g.max() < 0  # bounded, and none at the bound


def test_the_delta_rule_decays_a_channel_and_continues_from_its_state():
    """One head of two channels by hand: channel 0 forgets, channel 1 keeps; and a sequence cut
    in two continues from the state."""
    q = k = jnp.asarray([[[1.0, 0.0]], [[0.0, 1.0]], [[1.0, 1.0]]])
    v = jnp.asarray([[[2.0]], [[3.0]], [[0.0]]])
    alpha = jnp.asarray([[[1.0, 1.0]], [[0.5, 1.0]], [[0.5, 1.0]]])
    beta = jnp.asarray([[1.0], [1.0], [0.0]])
    o, s = ref.delta_rule(q, k, v, alpha, beta)
    # S after token 0: [[2], [0]]; token 1: channel 0 halves, [[1], [3]]; token 2 writes nothing
    np.testing.assert_allclose(np.asarray(s)[0, :, 0], [0.5, 3.0])
    np.testing.assert_allclose(np.asarray(o)[:, 0, 0], [2.0, 3.0, 3.5])
    _, first = ref.delta_rule(q[:2], k[:2], v[:2], alpha[:2], beta[:2])
    o2, s2 = ref.delta_rule(q[2:], k[2:], v[2:], alpha[2:], beta[2:], first)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s))
    np.testing.assert_allclose(np.asarray(o2)[0], np.asarray(o)[2])


def test_the_rotary_turns_neighbouring_pairs():
    x = jnp.asarray([[[1.0, 0.0, 1.0, 0.0]]])
    out = np.asarray(ref.rotary_pairs(x, jnp.asarray([1.0]), 100.0))[0, 0]
    np.testing.assert_allclose(out, [np.cos(1.0), np.sin(1.0), np.cos(0.1), np.sin(0.1)], rtol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each chip's routed part over its own group of the experts, the shared expert counted
    once, against the layer with every expert held: the statement of the share (at the
    rehearsal's four groups of eight)."""
    sizes = TINY
    groups, held = sizes["n_group"], sizes["num_experts"]
    wide = {**sizes, "n_group": 1, "num_experts": ref.router_width(sizes)}
    params = drawn(sizes)
    every = jax.jit(lambda key: ref.init_params({**wide, "topk_group": 1}, key, jnp.float32))(
        jax.random.PRNGKey(9))
    moe = jax.tree_util.tree_map(lambda a: a[0], params["segments"][1][0]["moe"])
    experts = jax.tree_util.tree_map(lambda a: a[0], every["experts"])
    assert experts["w_gate"].shape[0] == groups * held == moe["gate"]["wg"].shape[-1]
    n = jax.random.normal(jax.random.PRNGKey(5), (24, sizes["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        whole, shared = ref.layer_parts(sizes, {**moe, "experts": experts}, n)
        parts = [ref.layer_parts(sizes, {**moe, "experts": jax.tree_util.tree_map(
            lambda a, c=chip: a[held * c:held * (c + 1)], experts)}, n, chip=chip)[0]
            for chip in range(groups)]
    np.testing.assert_allclose(sum(parts) + shared, whole + shared, atol=2e-5)
    assert all(np.abs(np.asarray(p)).max() > 0 for p in parts)  # every chip's part is no zero
    picked = np.asarray(ref.router(sizes, n, moe["gate"]))
    assert ((picked > 0).sum(-1) == sizes["num_experts_per_tok"]).all()
    np.testing.assert_allclose(picked.sum(-1), sizes["routed_scaling_factor"], rtol=1e-5)
    in_groups = (picked.reshape(len(picked), groups, held) > 0).any(-1).sum(-1)
    assert (in_groups <= sizes["topk_group"]).all()  # a token's picks lie on at most topk_group chips
    # the bias chooses and never weighs: the weights are the picked scores' shares
    scores = np.asarray(jax.nn.sigmoid(n @ moe["gate"]["wg"]))
    top = np.where(picked > 0, scores, 0)
    np.testing.assert_allclose(picked, top / top.sum(-1, keepdims=True) * sizes["routed_scaling_factor"],
                               rtol=1e-5)


def test_the_programs_share_is_the_references_chip_zero():
    """``moe/serving.py sparse_moe_ffn`` over the held experts under the family's routing
    keywords against the reference's routed part of chip 0 plus the shared expert."""
    from deepspeed_tpu.moe.serving import sparse_moe_ffn
    sizes = TINY
    params = drawn(sizes)
    moe = jax.tree_util.tree_map(lambda a: a[0], params["segments"][1][0]["moe"])
    experts = jax.tree_util.tree_map(lambda a: a[0], params["experts"])
    n = jax.random.normal(jax.random.PRNGKey(5), (40, sizes["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want = sum(ref.layer_parts(sizes, {**moe, "experts": experts}, n))
        got = sparse_moe_ffn({**moe, "experts": experts}, n, sizes["num_experts_per_tok"], True,
                             n_group=sizes["n_group"], topk_group=sizes["topk_group"],
                             scaling=sizes["routed_scaling_factor"], scoring="sigmoid")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
