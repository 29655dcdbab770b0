"""The plain Nemotron-H reference against what can be held here.

``transformers`` 4.57 has no ``nemotron_h`` (it is remote code), so the whole
model has no written source on this machine; the Mamba-2 mixer WITH GROUPS and its
grouped gated norm are the same mathematics as ``transformers``'
``Zamba2MambaMixer`` / ``Zamba2RMSNormGated``, which is here: the reference's
``mamba2`` against its ``torch_forward`` on the same weights (4 groups of 2 heads;
TWO DEPARTURES of that torch path: it clamps ``dt`` from below at ``time_step_min``,
which Nemotron-H's keys do not ask for, so the config here sets it to 1e-9 and the
clamp holds nothing; and in transformers 4.57.6 what it passes from one of ITS
chunks to the next reads 2.25 off the token-by-token recurrence with ONE group as
with four, so the sequence is held inside one chunk of 128, where it reads 3e-6).  Beside it: the recurrence continued from a carried state,
every head reading ITS group, the ungated expert, the router's bias choosing and
not weighing, and the share: the two chips' routed parts plus the shared expert
counted once are the uncut layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import nemotron_h as ref

SIZES = {"attention_bias": False, "chunk_size": 32, "conv_kernel": 4, "expand": 2, "head_dim": 32,
         "hidden_size": 64, "hybrid_override_pattern": "MEM*EME", "intermediate_size": 32,
         "layer_norm_epsilon": 1e-5, "mamba_head_dim": 16, "mamba_num_heads": 8, "n_groups": 4,
         "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 8,
         "num_attention_heads": 4, "num_experts_per_tok": 3, "num_hidden_layers": 7,
         "num_key_value_heads": 2, "routed_scaling_factor": 2.5, "ssm_state_size": 16, "vocab_size": 256}
S, TOL = 75, 2e-5


def drawn(sizes, seed=3):
    params = ref.init_params(sizes, jax.random.PRNGKey(seed), jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def off_neutral(path, leaf):  # a gain, a D or a dt_bias left out or misplaced must show
        names = [getattr(p, "key", None) for p in path]
        if any(n in ("final_norm", "norm", "dt_bias", "D") for n in names):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(off_neutral, params)


def test_the_mixer_with_groups_is_zamba2s_torch_forward():
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.zamba2.modeling_zamba2")
    from transformers.models.zamba2.configuration_zamba2 import Zamba2Config
    cfg = Zamba2Config(hidden_size=64, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_ngroups=4,
                       n_mamba_heads=8, mamba_headdim=16, chunk_size=128, use_conv_bias=True,
                       add_bias_linear=False, time_step_min=1e-9, num_hidden_layers=2, vocab_size=256)
    mixer = hf.Zamba2MambaMixer(cfg, layer_idx=0).eval()
    w = jax.tree_util.tree_map(lambda a: a[0], drawn(SIZES)["segments"][0][0]["mixer"])
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    with torch.no_grad():
        mixer.in_proj.weight.copy_(t(w["w_in"]).T)
        mixer.conv1d.weight.copy_(t(w["filter"]).T[:, None, :])
        mixer.conv1d.bias.copy_(t(w["conv_bias"]))
        for name in ("A_log", "dt_bias", "D"):
            getattr(mixer, name).copy_(t(w[name]))
        mixer.norm.weight.copy_(t(w["norm"]))
        mixer.out_proj.weight.copy_(t(w["w_out"]).T)
        u = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (S, 64)), np.float32)
        want = mixer.torch_forward(t(u)[None])[0].numpy()
    assert mixer.norm.group_size == 32 and mixer.n_groups == 4
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.mamba2(SIZES, jnp.asarray(u), w))
    np.testing.assert_allclose(got, want, atol=TOL * np.abs(want).max(), rtol=0)
    assert np.abs(want).max() > 0.5


def test_every_head_reads_its_own_group_and_a_scan_continues_from_its_state():
    rng = np.random.default_rng(0)
    h, g, p, n = 8, 4, 5, 6
    x, dt = rng.normal(size=(40, h, p)).astype(np.float32), rng.uniform(0.1, 1, size=(40, h)).astype(np.float32)
    b, c = (rng.normal(size=(40, g, n)).astype(np.float32) for _ in range(2))
    a, d = -rng.uniform(0.01, 1, size=h).astype(np.float32), rng.normal(size=h).astype(np.float32)
    y, last = ref.selective_scan(x, dt, a, b, c, d)
    state = np.zeros((h, p, n), np.float64)
    for t in range(40):  # the recurrence as the issue writes it, head by head
        for head in range(h):
            group = head // (h // g)
            state[head] = np.exp(dt[t, head] * a[head]) * state[head] + dt[t, head] * np.outer(x[t, head], b[t, group])
            np.testing.assert_allclose(np.asarray(y[t, head]), state[head] @ c[t, group] + d[head] * x[t, head],
                                       atol=1e-4)
    np.testing.assert_allclose(np.asarray(last), state, atol=1e-4)
    head_y, kept = ref.selective_scan(x[:17], dt[:17], a, b[:17], c[:17], d)
    tail_y, end = ref.selective_scan(x[17:], dt[17:], a, b[17:], c[17:], d, kept)
    np.testing.assert_allclose(np.concatenate([head_y, tail_y]), np.asarray(y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(end), np.asarray(last), atol=1e-5)


def test_the_layers_parts_are_as_the_issue_states_them():
    """An ungated expert, the bias choosing and not weighing, the grouped norm, one part a layer."""
    u = jax.random.normal(jax.random.PRNGKey(1), (9, 64))
    w = {"w_up": jax.random.normal(jax.random.PRNGKey(2), (32, 64)), "w_down": jax.random.normal(jax.random.PRNGKey(3), (32, 64))}
    want = np.square(np.maximum(np.asarray(u) @ np.asarray(w["w_up"]).T, 0)) @ np.asarray(w["w_down"])
    np.testing.assert_allclose(np.asarray(ref.relu2_mlp(u, w)), want, rtol=2e-4, atol=2e-3)
    gate = {"wg": jax.random.normal(jax.random.PRNGKey(4), (64, 16)) / 8, "bias": jnp.zeros(16).at[5].set(10.0)}
    combine = np.asarray(ref.router(SIZES, u, gate))
    scores = np.asarray(jax.nn.sigmoid(u @ gate["wg"]))
    assert (combine[:, 5] > 0).all() and ((combine > 0).sum(-1) == 3).all()  # the bias chose expert 5
    np.testing.assert_allclose(combine.sum(-1), 2.5, rtol=1e-5)               # renormalised, times 2.5
    picked = combine > 0
    np.testing.assert_allclose(combine[picked] / 2.5, (scores / (scores * picked).sum(-1, keepdims=True))[picked],
                               rtol=1e-5)                                      # and weighed nothing
    y, z = np.asarray(jax.random.normal(jax.random.PRNGKey(6), (2, 3, 128)))
    gated = (y * z / (1 + np.exp(-z))).reshape(3, 4, 32)
    np.testing.assert_allclose(np.asarray(ref.gated_group_norm(y, z, np.full(128, 2.0, np.float32), 4, 1e-5)),
                               (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(3, 128) * 2,
                               rtol=1e-4, atol=1e-5)
    assert ref.layer_kinds(SIZES) == "MEM*EME" and ref.segments(SIZES) == [(i, 1, 1) for i in range(7)]
    assert ref.segments({**SIZES, "hybrid_override_pattern": "MEMEM*E" * 2, "num_hidden_layers": 14}) == [(0, 7, 2)]


def test_the_two_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(monkeypatch):
    monkeypatch.setattr(ref, "EP_CHIPS", 1)
    params = drawn(SIZES)
    moe = jax.tree_util.tree_map(lambda a: a[0], params["segments"][1][0]["alone"])
    x = jax.random.normal(jax.random.PRNGKey(9), (21, 64))
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.layer_parts(SIZES, {**moe, "experts": params["experts"]}, x, layer=0)
        halves = [ref.layer_parts(SIZES, {**moe, "experts": jax.tree_util.tree_map(
            lambda a, c=chip: a[:, c * 4:(c + 1) * 4], params["experts"])}, x, chip=chip, layer=0) for chip in (0, 1)]
    np.testing.assert_allclose(np.asarray(halves[0][0] + halves[1][0]), np.asarray(routed), atol=TOL)
    for _, again in halves:  # the shared expert is the same on every chip: counted once
        np.testing.assert_array_equal(np.asarray(again), np.asarray(shared))
    assert all(np.abs(np.asarray(part)).max() > 0.01 for part, _ in halves)


def test_the_model_is_causal_and_reads_the_first_layers_of_the_published_pattern():
    params = drawn(SIZES)
    ids = np.random.default_rng(1).integers(0, 256, 48)
    whole = np.asarray(ref.logits_rows(SIZES, params, list(ids), [10, 30]))
    other = np.asarray(ref.logits_rows(SIZES, params, list(ids[:31]) + [7] * 17, [10, 30]))
    np.testing.assert_allclose(whole, other, atol=1e-5)
    assert whole.shape == (2, 256) and np.abs(whole).max() > 0.05
    with pytest.raises(AssertionError):
        ref.layer_kinds({**SIZES, "hybrid_override_pattern": "ME-*EME"})
