"""Data pipeline / compression / 1-bit / PLD tests
(reference tests/unit/runtime/test_data.py, compression/, onebit/, test_pld.py)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.compat import shard_map
from deepspeed_tpu.compression import (fake_quantize, init_compression, row_prune_mask,
                                       sparse_prune_mask)
from deepspeed_tpu.runtime.comm import onebit_allreduce
from deepspeed_tpu.runtime.data_pipeline import (CurriculumScheduler, DeepSpeedDataSampler,
                                                 RandomLTDScheduler, random_ltd_layer)
from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop, layer_keep_prob

from .simple_model import init_mlp_params, mlp_loss_fn, random_batch


# ----------------------------------------------------------------- curriculum
def test_curriculum_fixed_linear():
    s = CurriculumScheduler({"schedule_type": "fixed_linear", "min_difficulty": 8,
                             "max_difficulty": 64, "schedule_config":
                             {"total_curriculum_step": 100, "difficulty_step": 8}})
    assert s.get_difficulty(0) == 8
    assert s.get_difficulty(50) == 8 + (64 - 8) // 2 // 8 * 8
    assert s.get_difficulty(1000) == 64


def test_curriculum_fixed_discrete():
    s = CurriculumScheduler({"schedule_type": "fixed_discrete",
                             "schedule_config": {"difficulty": [8, 16, 32], "max_step": [10, 20, 30]}})
    assert s.get_difficulty(5) == 8 and s.get_difficulty(15) == 16 and s.get_difficulty(99) == 32


def test_data_sampler_resume_and_partition():
    mk = lambda: DeepSpeedDataSampler(total_samples=64, micro_batch_size=2,
                                      data_parallel_rank=0, data_parallel_size=2,
                                      gradient_accumulation_steps=2, seed=3)
    s1 = mk()
    it1 = iter(s1)
    first = [next(it1) for _ in range(3)]
    sd = s1.state_dict()
    # all ranks' batches are disjoint within a step
    s_r1 = DeepSpeedDataSampler(total_samples=64, micro_batch_size=2, data_parallel_rank=1,
                                data_parallel_size=2, gradient_accumulation_steps=2, seed=3)
    other = next(iter(s_r1))
    assert not (set(first[0]) & set(other))
    # resume reproduces the stream
    s2 = mk()
    s2.load_state_dict(sd)
    assert next(iter(s2)) == next(it1)


def test_random_ltd():
    sched = RandomLTDScheduler({"min_value": 16, "max_value": 64,
                                "schedule_config": {"seq_per_step": 16, "require_steps": 10}})
    assert sched.update_seq(0) == 16 and sched.update_seq(10) == 64
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 8))
    marker = lambda t: t + 1.0
    out = random_ltd_layer(marker, x, jax.random.PRNGKey(1), keep=8)
    changed = np.sum(np.any(np.asarray(out != x), axis=(0, 2)))
    assert changed == 8  # exactly `keep` token positions processed


# ---------------------------------------------------------------- compression
def test_fake_quantize_bounds():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    q8 = fake_quantize(w, bits=8)
    assert float(jnp.abs(q8 - w).max()) < float(jnp.abs(w).max()) / 100
    q2 = fake_quantize(w, bits=2)
    assert len(np.unique(np.asarray(q2))) <= 4


def test_prune_masks():
    w = jnp.asarray(np.random.default_rng(0).normal(size=(32, 16)).astype(np.float32))
    m = sparse_prune_mask(w, 0.25)
    assert abs(float(m.mean()) - 0.25) < 0.05
    r = row_prune_mask(w, 0.5)
    kept_rows = np.unique(np.asarray(r).sum(axis=0))
    assert set(kept_rows.tolist()) <= {0.0, 32.0}


def test_init_compression_targets_modules():
    params = init_mlp_params(jax.random.PRNGKey(0), hidden=32, nlayers=2)
    cfg = {"weight_quantization": {"different_groups": {
        "g": {"params": {"target_bits": 4}, "modules": ["layer_0"]}}}}
    out = init_compression(params, cfg)
    assert not np.allclose(np.asarray(out["layer_0"]["w"]), np.asarray(params["layer_0"]["w"]))
    np.testing.assert_array_equal(np.asarray(out["layer_1"]["w"]), np.asarray(params["layer_1"]["w"]))


# ----------------------------------------------------------------- 1-bit comm
@pytest.mark.slow
def test_onebit_allreduce_error_feedback_converges():
    mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
    n = 1024
    gs = jax.random.normal(jax.random.PRNGKey(0), (8, n))
    ref = np.asarray(gs).mean(axis=0)

    def body(g, e, se):
        est, new_e, new_se = onebit_allreduce(g[0], e[0], "dp", se)
        return est, new_e[None, :], new_se

    f = shard_map(body, mesh=mesh, in_specs=(P("dp", None), P("dp", None), P("dp")),
                  out_specs=(P(None), P("dp", None), P("dp")), check_vma=False)
    est, err, serr = f(gs, jnp.zeros((8, n)), jnp.zeros((n,)))
    # single step: correlated with true mean
    assert np.corrcoef(np.asarray(est), ref)[0, 1] > 0.5
    # repeated reduction of the SAME gradient with worker+server error feedback
    # -> converges
    accum = np.zeros(n)
    e, se = jnp.zeros((8, n)), jnp.zeros((n,))
    for i in range(24):
        est, e, se = f(gs, e, se)
        accum += np.asarray(est)
    # time-averaged estimate approaches the true mean (error feedback property)
    assert np.corrcoef(accum / 24, ref)[0, 1] > 0.97


# ------------------------------------------------------------------------ PLD
def test_pld_theta_schedule():
    pld = ProgressiveLayerDrop(theta=0.5, gamma=0.01)
    t0 = pld.update_state(0)
    t_mid = pld.update_state(100)
    t_end = pld.update_state(100000)
    assert t0 == 1.0 and t0 > t_mid > t_end
    assert abs(t_end - 0.5) < 1e-3
    assert layer_keep_prob(0.5, 9, 10) == pytest.approx(0.5)
    assert layer_keep_prob(0.5, 0, 10) == pytest.approx(0.95)


def test_head_prune_mask_whole_heads():
    from deepspeed_tpu.compression import head_prune_mask
    rng = np.random.default_rng(0)
    H, hd, dm = 4, 8, 32
    w = jnp.asarray(rng.normal(size=(H * hd, dm)).astype(np.float32))
    m = np.asarray(head_prune_mask(w, num_heads=H, density=0.5, head_axis="in"))
    per_head = m.reshape(H, hd, dm)
    # each head fully kept or fully zero, exactly 2 of 4 kept
    kept = [bool(per_head[h].all()) for h in range(H)]
    zeroed = [bool((per_head[h] == 0).all()) for h in range(H)]
    assert all(k or z for k, z in zip(kept, zeroed))
    assert sum(kept) == 2
    # out-axis variant: columns grouped by head
    m2 = np.asarray(head_prune_mask(w.T, num_heads=H, density=0.5, head_axis="out"))
    assert m2.T.reshape(H, hd, dm).sum(axis=(1, 2)).tolist() == per_head.sum(axis=(1, 2)).tolist()


def test_channel_prune_and_quant_act():
    from deepspeed_tpu.compression import QuantAct, channel_prune_mask
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    m = np.asarray(channel_prune_mask(w, 0.5))
    rows = m.sum(axis=1)
    assert set(rows.tolist()) <= {0.0, 8.0} and rows.sum() == 8 * 8
    x = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    q = QuantAct(bits=8, dynamic=True)(x)
    assert float(jnp.abs(q - x).max()) < float(jnp.abs(x).max()) / 50
    # static mode: calibrate, freeze, reuse
    qa = QuantAct(bits=8, dynamic=False)
    qa(x); qa(x * 2)
    qa.freeze()
    frozen_max = qa.running_max
    qa(x * 100)  # frozen: range must not move
    assert qa.running_max == frozen_max


def test_layer_reduction_and_redundancy_clean():
    from deepspeed_tpu.compression import layer_reduction, redundancy_clean
    stacked = {"w": jnp.arange(6 * 4).reshape(6, 4).astype(jnp.float32)}
    student = layer_reduction(stacked, [0, 2, 4])
    np.testing.assert_array_equal(np.asarray(student["w"][:, 0]), [0, 8, 16])
    # redundancy_clean with layer_reduction section drops teacher layers
    params = {"blocks": {"w": jnp.ones((6, 4, 4))}, "head": jnp.ones((4, 4))}
    out = redundancy_clean(params, {"layer_reduction": {
        "enabled": True, "keep_number_layer": 3, "teacher_layer": 6,
        "module_name_prefix": "blocks"}})
    assert out["blocks"]["w"].shape == (3, 4, 4)
    assert out["head"].shape == (4, 4)


def test_init_compression_head_and_channel_groups():
    from deepspeed_tpu.compression import init_compression
    rng = np.random.default_rng(2)
    params = {"attn": {"wo": jnp.asarray(rng.normal(size=(32, 32)).astype(np.float32))},
              "mlp": {"w": jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))}}
    cfg = {"head_pruning": {"shared_parameters": {"num_heads": 4},
                            "different_groups": {"h": {"params": {"dense_ratio": 0.5},
                                                       "modules": ["attn.wo"]}}},
           "channel_pruning": {"different_groups": {"c": {"params": {"dense_ratio": 0.5},
                                                          "modules": ["mlp"]}}}}
    out = init_compression(params, cfg)
    wo = np.asarray(out["attn"]["wo"]).reshape(4, 8, 32)
    assert sum(bool((wo[h] == 0).all()) for h in range(4)) == 2
    mlp_rows = np.asarray(out["mlp"]["w"]).sum(axis=1)
    assert (mlp_rows == 0).sum() == 16


# ----------------------------------------------------------------------- WOQ
def test_woq_pack_dequant_roundtrip():
    from deepspeed_tpu.inference.quantization import (dequantize_tree, packed_nbytes,
                                                      quantize_tree)
    rng = np.random.default_rng(3)
    params = {"w1": jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32)),
              "norm": jnp.ones((64,), jnp.float32)}
    packed = quantize_tree(params, bits=8, group_size=64)
    from deepspeed_tpu.inference.quantization import is_woq_leaf
    assert is_woq_leaf(packed["w1"]) and not is_woq_leaf(packed["norm"])
    # packed rest size ~ 1/4 the bf16 dense size + scales
    assert packed_nbytes(packed) < params["w1"].size * 2
    dense = dequantize_tree(packed, dtype=jnp.float32)
    err = np.abs(np.asarray(dense["w1"]) - np.asarray(params["w1"])).max()
    assert err < np.abs(np.asarray(params["w1"])).max() / 50
    np.testing.assert_array_equal(np.asarray(dense["norm"]), np.ones(64))


def test_woq_int4_inside_jit():
    from deepspeed_tpu.inference.quantization import dequantize_tree, quantize_tree
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    packed = quantize_tree({"w": w}, bits=4, group_size=64)

    @jax.jit
    def matmul(p, x):
        dense = dequantize_tree(p, dtype=jnp.float32)
        return x @ dense["w"]

    x = jnp.ones((2, 64))
    out = matmul(packed, x)
    ref = x @ w
    # int4 tolerance: ~6% of magnitude
    assert float(jnp.abs(out - ref).max()) < float(jnp.abs(ref).max()) * 0.2


def test_layer_reduction_rejects_mixed_tree():
    from deepspeed_tpu.compression import layer_reduction
    mixed = {"blocks": jnp.ones((6, 4)), "embed": jnp.ones((32000, 8))}
    with pytest.raises(ValueError, match="homogeneous"):
        layer_reduction(mixed, [0, 2])
    with pytest.raises(ValueError, match="out of range"):
        layer_reduction({"w": jnp.ones((4, 4))}, [0, 9])


def test_head_prune_mask_stacked_layers():
    from deepspeed_tpu.compression import head_prune_mask
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=(3, 16, 8)).astype(np.float32))  # [L, d, d]
    m = np.asarray(head_prune_mask(w, num_heads=4, density=0.5, head_axis="in"))
    for l in range(3):
        per_head = m[l].reshape(4, 4, 8)
        assert sum(bool(per_head[h].all()) for h in range(4)) == 2


def test_quant_act_static_rejects_tracer():
    from deepspeed_tpu.compression import QuantAct
    qa = QuantAct(bits=8, dynamic=False)
    with pytest.raises(RuntimeError, match="EAGERLY"):
        jax.jit(qa)(jnp.ones((4, 4)))
    # frozen static mode IS jit-safe
    qa(jnp.ones((4, 4)))
    qa.freeze()
    out = jax.jit(qa)(jnp.ones((4, 4)))
    assert np.isfinite(np.asarray(out)).all()
