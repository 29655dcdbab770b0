"""The plain DeepSeek-V2 reference against the program at a tiny size on the
CPU: ``forward_paged`` driven as the engine drives it (prefill in chunks, then
decode, through the latent paged pool; compacted and padded; prompts past the
rotary's original length), absorbed attention against expanded, the four
chips' shares against the uncut layer, wrong readings of the architecture
that each have to fail, and the cell's rehearsal."""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common
from chipbench.references import deepseek_v2 as ref

YARN = {"type": "yarn", "factor": 16, "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 16}
# hidden 128, 8 heads, latent 32 + rotary 16, 16 experts in 4 groups of which 2
# are kept, top 4, a dense layer and 2 expert layers; this chip holds 4 experts
SIZES = {"hidden_size": 128, "intermediate_size": 256, "moe_intermediate_size": 64,
         "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_attention_heads": 8,
         "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
         "v_head_dim": 16, "n_routed_experts": 4, "n_shared_experts": 2, "num_experts_per_tok": 4,
         "n_group": 4, "topk_group": 2, "routed_scaling_factor": 16, "norm_topk_prob": False,
         "vocab_size": 256, "max_position_embeddings": 256, "rope_theta": 10000,
         "rms_norm_eps": 1e-6, "rope_scaling": YARN}
BLOCK, CHUNK, BOUND = 8, 8, 16
LENGTHS, DECODED = (29, 11, 21), 3  # prompt tokens (two past YaRN's original 16), then one at a time
# Both sides are float32 at ``highest`` matmul precision on the CPU and differ
# by the order of their sums and by where they round: the program multiplies q
# into the latent (absorbed) where the reference expands k and v to heads, and
# sorts rows for a grouped matmul where the reference runs every expert.  1e-6
# of the largest logit was read.  A wrong reading of the architecture moves the
# logits by 5% (the group limit dropped) to 60% of it; each has to pass 100
# tolerances, and a bfloat16 reference (rounding 4e-3) would pass 40.
TOLERANCE = 2e-5


def drawn(seed):
    params = jax.jit(lambda k: ref.init_params(SIZES, k, jnp.float32))(jax.random.PRNGKey(seed))
    # gains that are not one, or a gain laid out wrongly would change nothing
    for i, stack in enumerate(("dense_layers", "layers")):
        for j, name in enumerate(("q_norm", "kv_norm")):
            gain = params[stack]["attn"][name]
            params[stack]["attn"][name] = gain + 0.5 * jax.random.normal(
                jax.random.PRNGKey(100 + 2 * i + j), gain.shape)
    rng = np.random.default_rng(seed)
    return params, [rng.integers(0, 256, n + DECODED).tolist() for n in LENGTHS]


def program_config(**changes):
    from deepspeed_tpu.models import deepseek_v2
    return dataclasses.replace(deepseek_v2.DeepseekV2Config(
        vocab_size=256, hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
        num_layers=3, first_k_dense=1, num_heads=8, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16, num_experts=16,
        num_local_experts=4, n_shared_experts=2, top_k=4, n_group=4, topk_group=2,
        routed_scaling_factor=16.0, max_seq_len=256, rope_theta=10000.0, rope_scaling=YARN,
        rms_eps=1e-6), **changes)


def program_logits(cfg, params, seqs, bound):
    """Every position's logits from ``forward_paged``: steps of at most
    ``BOUND`` live tokens over rows ``[4, CHUNK]`` (a row is dead throughout)
    while prompts last, then ``[4, 1]`` steps, all through one latent pool."""
    from deepspeed_tpu.models import deepseek_v2
    rows, width = 4, 8
    kv = deepseek_v2.init_paged_cache(cfg, rows * width + 1, BLOCK, dtype=jnp.float32)
    assert [leaf.shape for leaf in jax.tree_util.tree_leaves(kv)] == [
        (3, rows * width + 1, 1, BLOCK, 128)]  # one vector a token a layer, held once
    tables = np.full((rows, width), rows * width, np.int32)  # unused entries: the trash block
    for r in range(len(seqs)):
        tables[r] = np.arange(r * width, (r + 1) * width)
    seen = [0] * len(seqs)
    out = [np.zeros((len(s), cfg.vocab_size), np.float32) for s in seqs]
    fwd = jax.jit(lambda kv, tok, n, start: deepseek_v2.forward_paged(
        cfg, params, tok, n, start, jnp.asarray(tables), kv, block_size=BLOCK,
        live_token_bound=bound))
    compacted = 0
    while any(seen[r] < len(s) for r, s in enumerate(seqs)):
        prefill = any(seen[r] < len(s) - DECODED for r, s in enumerate(seqs))
        t, budget = (CHUNK, BOUND) if prefill else (1, rows)
        tok, n = np.zeros((rows, t), np.int32), np.zeros(rows, np.int32)
        for r, s in enumerate(seqs):
            end = len(s) - DECODED if prefill else len(s)
            n[r] = max(0, min(end - seen[r], t, budget))
            budget -= n[r]
            tok[r, :n[r]] = s[seen[r]:seen[r] + n[r]]
        start = np.asarray(seen + [0] * (rows - len(seqs)), np.int32)
        with jax.default_matmul_precision("highest"):
            logits, kv = fwd(kv, jnp.asarray(tok), jnp.asarray(n), jnp.asarray(start))
        compacted += bound is not None and rows * t > bound
        for r in range(len(seqs)):
            out[r][seen[r]:seen[r] + n[r]] = np.asarray(logits[r, :n[r]])
            seen[r] += int(n[r])
    assert compacted == (0 if bound is None else 4)  # the chunk steps, and they alone
    return out


def worst_error(cfg, params, seqs, bound):
    got = program_logits(cfg, params, seqs, bound)
    worst = 0.0
    for ids, mine in zip(seqs, got):
        want = np.asarray(ref.logits_rows(SIZES, params, ids, list(range(len(ids)))))
        worst = max(worst, float(np.abs(mine - want).max() / np.abs(want).max()))
    return worst


def test_reference_imports_nothing_of_the_programs_models_and_sets_highest_precision():
    source = inspect.getsource(ref)
    assert "deepspeed_tpu" not in source.split('"""', 2)[2]
    assert 'jax.default_matmul_precision("highest")' in source
    assert ref.EP_CHIPS == 4 and ref.router_width(SIZES) == 16  # the share it was written for


@pytest.mark.parametrize("bound", [BOUND, None], ids=["compacted", "padded"])
def test_paged_forward_in_chunks_then_decode_equals_the_reference(bound):
    params, seqs = drawn(3)
    assert worst_error(program_config(), params, seqs, bound) < TOLERANCE


def rotate_halves(x, positions, inv_freq, table_scale=1.0):
    angle = positions.astype(jnp.float32)[..., None, None] * jnp.asarray(inv_freq)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


@pytest.mark.parametrize("wrong", [
    "shared_expert_left_out", "scaling_factor_left_out", "m_squared_left_out",
    "group_limit_left_out", "top_k_weights_renormalised", "yarn_left_out",
    "rotary_over_halves_not_pairs", "every_expert_taken_as_held"])
def test_each_wrong_reading_of_the_architecture_fails_the_tolerance(wrong, monkeypatch):
    from deepspeed_tpu.models import deepseek_v2
    from deepspeed_tpu.moe import serving
    cfg = program_config()
    params, seqs = drawn(3)
    if wrong == "shared_expert_left_out":
        whole = serving.sparse_moe_ffn
        monkeypatch.setattr(serving, "sparse_moe_ffn", lambda moe, *a, **k: whole(
            {name: w for name, w in moe.items() if name != "shared"}, *a, **k))
    elif wrong == "scaling_factor_left_out":
        cfg = program_config(routed_scaling_factor=1.0)
    elif wrong == "m_squared_left_out":
        monkeypatch.setattr(deepseek_v2, "softmax_scale", lambda config: 32 ** -0.5)
    elif wrong == "group_limit_left_out":
        cfg = program_config(n_group=1, topk_group=1)
    elif wrong == "top_k_weights_renormalised":
        cfg = program_config(norm_topk_prob=True, routed_scaling_factor=1.0)
    elif wrong == "yarn_left_out":
        cfg = program_config(rope_scaling=None)
    elif wrong == "rotary_over_halves_not_pairs":
        monkeypatch.setattr(deepseek_v2, "rotate_pairs", rotate_halves)
    else:  # a router as wide as what is held: picks on the absent experts land on the held
        wg = params["layers"]["moe"]["gate"]["wg"]
        params["layers"]["moe"]["gate"]["wg"] = wg[..., :4]
        cfg = program_config(n_group=1, topk_group=1)
    assert worst_error(cfg, params, seqs, BOUND) > 100 * TOLERANCE


def test_absorbed_attention_over_the_latent_equals_expanded_attention_over_heads():
    """q multiplied into the latent (one 48-wide key a token, its first 32
    columns the value, for all heads) against k_nope and v expanded a head:
    the same numbers to float32 rounding, through the kernel's dense twin."""
    from deepspeed_tpu.ops.attention.paged import paged_attention
    heads, rank, rope, nope, dv, s, bs = 8, 32, 16, 16, 16, 40, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    c_kv, k_pe = jax.random.normal(keys[0], (s, rank)), jax.random.normal(keys[1], (s, rope))
    q = jax.random.normal(keys[2], (s, heads, nope + rope))
    w_kvb = jax.random.normal(keys[3], (rank, heads, nope + dv)) * rank ** -0.5
    with jax.default_matmul_precision("highest"):
        k_v = jnp.einsum("sr,rhd->shd", c_kv, w_kvb)
        k = jnp.concatenate([k_v[..., :nope], jnp.broadcast_to(k_pe[:, None], (s, heads, rope))], -1)
        expanded = ref.causal_attention(q, k, k_v[..., nope:], 0.3)
        q_lat = jnp.einsum("shd,rhd->shr", q[..., :nope], w_kvb[..., :nope])
        pool = jnp.concatenate([c_kv, k_pe], -1).reshape(s // bs, 1, bs, rank + rope)
        out = paged_attention(
            jnp.concatenate([q_lat, q[..., nope:]], -1)[None], pool, None,
            jnp.arange(s // bs, dtype=jnp.int32)[None], jnp.asarray([s], jnp.int32),
            jnp.asarray([0], jnp.int32), jnp.asarray([s], jnp.int32), block_size=bs,
            softmax_scale=0.3, value_dim=rank)
        absorbed = jnp.einsum("shr,rhd->shd", out[0], w_kvb[..., nope:])
    assert absorbed.shape == expanded.shape == (s, heads, dv)
    assert float(jnp.abs(absorbed - expanded).max() / jnp.abs(expanded).max()) < 2e-6


def test_the_four_chips_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of the four shares (each its
    own 4 of the 16 experts, the router over all 16) plus the shared expert
    counted once are the uncut layer; through the reference's own
    ``layer_parts`` and through the program's ``sparse_moe_ffn``, which holds
    experts 0..3 of whatever router it is handed."""
    from deepspeed_tpu.moe.serving import sparse_moe_ffn
    uncut = {**SIZES, "n_routed_experts": 16}  # EP_CHIPS would make the router 64 wide
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    d, f, e, held = 128, 64, 16, 4
    lin = lambda k, *shape: jax.random.normal(k, shape) * shape[-2] ** -0.5
    moe = {"gate": {"wg": lin(keys[0], d, e)},
           "experts": {"w_gate": lin(keys[1], e, d, f), "w_up": lin(keys[2], e, d, f),
                       "w_down": lin(keys[3], e, f, d)},
           "shared": {"w_gate": lin(keys[4], d, 2 * f), "w_up": lin(keys[4], d, 2 * f),
                      "w_down": lin(keys[5], 2 * f, d)}}
    n2 = jax.random.normal(jax.random.PRNGKey(6), (24, d))
    share = lambda c: jax.tree_util.tree_map(lambda w: w[c * held:(c + 1) * held], moe["experts"])
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.layer_parts(uncut, moe, n2)
        whole = routed + shared
        by_reference = sum(ref.layer_parts(uncut, {**moe, "experts": share(c)}, n2, chip=c)[0]
                           for c in range(4)) + shared
        # chip c sees its own experts first: the router's columns rolled by whole groups
        by_program = sum(sparse_moe_ffn(
            {"gate": {"wg": jnp.roll(moe["gate"]["wg"], -c * held, axis=1)}, "experts": share(c)},
            n2, 4, False, n_group=4, topk_group=2, scaling=16.0) for c in range(4)) + shared
        chip0 = sparse_moe_ffn({**moe, "experts": share(0)}, n2, 4, False, n_group=4,
                               topk_group=2, scaling=16.0)
    scale = float(jnp.abs(whole).max())
    assert float(jnp.abs(by_reference - whole).max()) / scale < 2e-6
    assert float(jnp.abs(by_program - whole).max()) / scale < 2e-6
    # and a share is not the whole: chip 0's routed part plus the shared expert
    part = ref.layer_parts(uncut, {**moe, "experts": share(0)}, n2, chip=0)[0] + shared
    assert float(jnp.abs(chip0 - part).max()) / scale < 2e-6
    assert float(jnp.abs(part - whole).max()) / scale > 0.05


def test_router_is_group_limited_scaled_and_never_renormalised():
    m = jax.random.normal(jax.random.PRNGKey(0), (32, 128))
    wg = jax.random.normal(jax.random.PRNGKey(1), (128, 16)) / 11
    combine = np.asarray(ref.router(SIZES, m, wg))
    probs = np.asarray(jax.nn.softmax(m @ wg, axis=-1))
    assert ((combine > 0).sum(axis=1) == 4).all()
    assert np.allclose(combine[combine > 0], 16 * probs[combine > 0], rtol=1e-6)
    for row, p in zip(combine, probs):  # by a plain loop: the two best groups, then the top 4
        best_groups = np.argsort(-p.reshape(4, 4).max(axis=1))[:2]
        allowed = [e for e in range(16) if e // 4 in best_groups]
        assert set(np.nonzero(row)[0]) == set(sorted(allowed, key=lambda e: -p[e])[:4])
    # the limit binds: somewhere a plain top-4 would have reached into a third group
    plain = np.argsort(-probs, axis=1)[:, :4]
    assert any(set(plain[i]) != set(np.nonzero(combine[i])[0]) for i in range(32))


def test_yarn_frequencies_blend_from_kept_to_interpolated_and_positions_past_the_original_count():
    published = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                 "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096}
    inv = ref.yarn_inv_freq(64, 10000.0, published)
    plain = ref.yarn_inv_freq(64, 10000.0, None)
    assert np.allclose(inv[:10], plain[:10]) and np.allclose(inv[-8:], plain[-8:] / 40)
    assert (np.diff(inv / plain) <= 1e-6).all()  # from kept to divided by 40, never back
    assert ref.yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=1e-4)
    from deepspeed_tpu.models import deepseek_v2
    assert np.allclose(deepseek_v2.rotary_inv_freq(deepseek_v2.DeepseekV2Config()), inv)
    assert deepseek_v2.softmax_scale(deepseek_v2.DeepseekV2Config()) == pytest.approx(
        192 ** -0.5 * 1.2608 ** 2, rel=1e-4)


def test_padding_after_the_last_row_changes_nothing_and_blocks_neither():
    params, seqs = drawn(4)
    ids = seqs[1]
    a = np.asarray(ref.logits_rows(SIZES, params, ids, [5, len(ids) - 1]))
    b = np.asarray(ref.logits_rows(SIZES, params, ids + [0] * 12, [5, len(ids) - 1]))
    assert np.allclose(a, b, atol=1e-5)
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (50, 4, 24)) for i in range(2))
    v = jax.random.normal(jax.random.PRNGKey(2), (50, 4, 16))
    assert np.allclose(ref.causal_attention(q, k, v, 0.2, q_block=8),
                       ref.causal_attention(q, k, v, 0.2, q_block=64), atol=1e-5)


def test_the_configuration_keeps_every_published_width_and_states_the_share():
    spec = common.load_json("configs", "deepseek-v2-serve-ep4-5l.json")
    published = common.load_json("published", "deepseek-v2.json")["config"]
    assert sorted(spec["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert {k for k in published if spec[k] != published[k]} == set(spec["reduced"])
    assert (spec["n_routed_experts"] * ref.EP_CHIPS, spec["vocab_size"] * ref.EP_CHIPS) == (
        published["n_routed_experts"], published["vocab_size"])
    sizes = common.published_sizes(spec, False)
    module, cfg = common.program_model(spec, sizes)
    shapes = jax.eval_shape(lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.bfloat16))
    assert shapes["layers"]["moe"]["gate"]["wg"].shape == (4, 5120, 160)   # the router as published
    assert shapes["layers"]["moe"]["experts"]["w_gate"].shape == (4, 40, 5120, 1536)
    assert shapes["dense_layers"]["mlp"]["w_gate"].shape == (1, 5120, 12288)
    assert common.count_params(shapes) == 5_163_975_680  # 10.33 GB at 2 bytes
    pool = jax.eval_shape(lambda: module.init_paged_cache(cfg, 1024, 128))
    assert [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)] == [(5, 1024, 1, 128, 640)]
    assert cfg.rope_scaling == tuple(sorted(published["rope_scaling"].items()))


def test_engine_agrees_and_the_fp8_control_does_not(rehearse):
    """The new cell's rehearsal: inside its limits as built, outside them
    with the weights rounded through fp8."""
    sound = rehearse("--workload", "serve.mla-long-prompt", "--seed", "11", "--seconds", "0")
    control = rehearse("--workload", "serve.mla-long-prompt", "--seed", "11", "--seconds", "0",
                       "--control", "1")
    spec = common.load_json("configs", "deepseek-v2-serve-ep4-5l.json")
    limit = common.correct_limits(spec, rehearse=True)["logit_rel_rms_limit"]
    assert sound.line["would_be_correct"] is True
    assert control.line["would_be_correct"] is False
    assert sound.number("logit_rel_rms") < limit < control.number("logit_rel_rms")
    assert control.number("logit_rel_rms") > 3 * sound.number("logit_rel_rms")
