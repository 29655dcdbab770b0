"""The plain reference of Trinity-Large-Preview's layers (``chipbench/references/
afmoe.py``): what its two masks state, that the eight chips' shares add up to the
uncut layer, and the program against it through the benchmark's own comparison at
the rehearsal (``tests/unit/inference/test_afmoe.py`` holds the program to it in
float32, with the misreadings that must not pass).  The installed ``transformers``
has no ``afmoe``: nothing here can hold the reference to HF's code."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common
from chipbench.references import afmoe as ref

CONFIG = "trinity-large-serve-ep8-8l"
SPEC = common.load_json("configs", CONFIG + ".json")
TINY = {**common.published_sizes(SPEC, True), "num_hidden_layers": 3, "num_dense_layers": 1,
        "sliding_window": 8}


def drawn(sizes, seed=3):
    return jax.jit(lambda key: ref.init_params(sizes, key, jnp.float32))(jax.random.PRNGKey(seed))


@pytest.mark.reads_benchmark
def test_the_configuration_is_the_published_model_cut_to_one_chips_share():
    published = common.load_json("published", SPEC["published"] + ".json")["config"]
    changed = {k for k, v in published.items() if SPEC[k] != v}
    assert changed == set(SPEC["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    sizes = common.published_sizes(SPEC, False)
    assert ref.router_width(sizes) == published["num_experts"] == 256
    assert sizes["vocab_size"] * ref.EP_CHIPS == published["vocab_size"]
    # two whole periods of the attention pattern: the six dense layers, then two expert layers
    kinds = ref.layer_kinds(sizes)
    assert [k for k, _ in kinds] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert [dense for _, dense in kinds] == [True] * 6 + [False] * 2
    assert ref.segments(sizes) == [(0, 1, 3), (3, 1, 1), (4, 1, 2), (6, 1, 1), (7, 1, 1)]
    shapes = jax.eval_shape(lambda key: ref.init_params(sizes, key, jnp.bfloat16), jax.random.PRNGKey(0))
    assert common.count_params(shapes) == 3_206_780_416  # 6.41 GB at 2 bytes
    assert shapes["experts"]["w_gate"].shape == (2, 32, 3072, 3072)
    engine = SPEC["engine"]  # the longest prompt and its answer fit a sequence's table
    assert (32721 + 32) <= engine["max_blocks_per_seq"] * engine["block_size"]


def test_a_windowed_layer_sees_its_window_and_a_full_layer_everything():
    """The masks as the reference states them, on its own: with every layer
    windowed a token four windows back cannot reach the last row through three
    layers; with the last of them full it does."""
    sizes = {**TINY, "layer_types": ["sliding_attention"] * 3}
    params = drawn(sizes)
    ids = np.random.default_rng(0).integers(0, sizes["vocab_size"], 40)
    other = ids.copy()
    other[3] = (other[3] + 1) % sizes["vocab_size"]
    row = lambda s, p, i: np.asarray(ref.logits_rows(s, p, i.tolist(), [39]))[0]
    np.testing.assert_array_equal(row(sizes, params, ids), row(sizes, params, other))
    sizes = {**sizes, "layer_types": ["sliding_attention", "sliding_attention", "full_attention"]}
    params = drawn(sizes)  # the runs of layers are other runs: the same draw laid out anew
    assert np.abs(row(sizes, params, ids) - row(sizes, params, other)).max() > 1e-3


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each chip's routed part over its own 2 of 16 experts, the shared expert
    counted once, against the layer with all 16 held: the statement of the share."""
    sizes = {**TINY, "num_experts": 2}
    wide = {**sizes, "num_experts": ref.router_width(sizes)}
    params = drawn(sizes)
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    every = jax.jit(lambda key: ref.init_params(wide, key, jnp.float32))(jax.random.PRNGKey(9))
    moe = jax.tree_util.tree_map(lambda a: a[0], params["segments"][-1][0]["moe"])
    experts = jax.tree_util.tree_map(lambda a: a[0, :ref.router_width(sizes)], every["experts"])
    n = jax.random.normal(keys[0], (24, sizes["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        whole, shared = ref.layer_parts(sizes, {**moe, "experts": experts}, n)
        parts = [ref.layer_parts(sizes, {**moe, "experts": jax.tree_util.tree_map(
            lambda a, c=chip: a[2 * c:2 * c + 2], experts)}, n, chip=chip)[0]
            for chip in range(ref.EP_CHIPS)]
    np.testing.assert_allclose(sum(parts) + shared, whole + shared, atol=2e-5)
    assert all(np.abs(np.asarray(p)).max() > 0 for p in parts[:2])  # this chip's part is no zero
    picked = np.asarray(ref.router(sizes, n, moe["gate"]))
    assert ((picked > 0).sum(-1) == sizes["num_experts_per_tok"]).all()
    np.testing.assert_allclose(picked.sum(-1), sizes["route_scale"], rtol=1e-5)
    # the bias chooses and never weighs: the weights are the picked scores' shares
    scores = np.asarray(jax.nn.sigmoid(n @ moe["gate"]["wg"]))
    top = np.where(picked > 0, scores, 0)
    np.testing.assert_allclose(picked, top / top.sum(-1, keepdims=True) * sizes["route_scale"], rtol=1e-5)
