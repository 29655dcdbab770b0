"""Ulysses SP tests — the reference has NO in-tree Ulysses unit tests
(SURVEY.md §4.3); these provide the all-to-all attention parity coverage the
rebuild requires: sharded attention must equal single-device attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.compat import shard_map
from deepspeed_tpu.models.transformer import sdpa
from deepspeed_tpu.parallel import MeshTopology, set_topology
from deepspeed_tpu.sequence import DistributedAttention, single_all_to_all, ulysses_attention


@pytest.fixture
def seq_topo():
    topo = MeshTopology.from_axis_dict({"sequence": 8})
    set_topology(topo)
    return topo


def _qkv(b=2, s=32, h=8, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: rng.normal(size=(b, s, h, d)).astype(np.float32)
    return mk(), mk(), mk()


def test_single_all_to_all_roundtrip(seq_topo):
    x = np.arange(8 * 4 * 8.0, dtype=np.float32).reshape(8, 4, 8)  # [S, B, H]

    def body(v):
        swapped = single_all_to_all(v, scatter_idx=2, gather_idx=0)
        return single_all_to_all(swapped, scatter_idx=0, gather_idx=2)

    f = shard_map(body, mesh=seq_topo.mesh, in_specs=P("sequence"), out_specs=P("sequence"), check_vma=False)
    np.testing.assert_allclose(np.asarray(f(x)), x, rtol=1e-6)


def test_distributed_attention_matches_local(seq_topo):
    """Sharded Ulysses attention == unsharded attention (parity discipline)."""
    q, k, v = _qkv()
    expected = np.asarray(sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))

    dist_attn = DistributedAttention(lambda q, k, v: sdpa(q, k, v, causal=True),
                                     scatter_idx=2, gather_idx=1)
    f = shard_map(dist_attn, mesh=seq_topo.mesh,
                  in_specs=(P(None, "sequence"), P(None, "sequence"), P(None, "sequence")),
                  out_specs=P(None, "sequence"), check_vma=False)
    out = np.asarray(f(q, k, v))
    np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)


def test_ulysses_gspmd_wrapper_matches_local(seq_topo):
    q, k, v = _qkv(seed=3)
    expected = np.asarray(sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))
    attn = ulysses_attention(topo=seq_topo)
    seq_sharding = NamedSharding(seq_topo.mesh, P(None, "sequence"))
    qs = jax.device_put(q, seq_sharding)
    ks = jax.device_put(k, seq_sharding)
    vs = jax.device_put(v, seq_sharding)
    out = np.asarray(jax.jit(lambda a, b, c: attn(a, b, c, causal=True))(qs, ks, vs))
    np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)


def test_ulysses_degrades_without_seq_axis():
    topo = MeshTopology.from_axis_dict({"data": 8})
    set_topology(topo)
    q, k, v = _qkv(seed=5)
    attn = ulysses_attention(topo=topo)
    out = np.asarray(attn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))
    expected = np.asarray(sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_llama_with_ulysses_attention(seq_topo):
    """End-to-end: llama forward with sequence-sharded activations."""
    from deepspeed_tpu.models import llama
    cfg = llama.LlamaConfig.tiny(heads=8, kv_heads=8, seq=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32))
    base = np.asarray(llama.forward(cfg, params, jnp.asarray(ids)))
    ulysses = np.asarray(llama.forward(cfg, params, jnp.asarray(ids),
                                       attention_fn=ulysses_attention(topo=seq_topo)))
    np.testing.assert_allclose(base, ulysses, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ulysses_composes_with_zero3():
    """Ulysses SP x ZeRO-3 through the full engine: opt state shards over the
    sequence axis too (reference seq_data_parallel_group, engine.py:1515),
    and training converges."""
    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.parallel import reset_topology

    reset_topology()
    topo = MeshTopology.from_axis_dict({"data": 2, "sequence": 4})
    set_topology(topo)
    cfg = llama.LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=8, kv_heads=8, seq=32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    attn = ulysses_attention()
    eng, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=llama.make_loss_fn(cfg, attention_fn=attn),
        model_parameters=params, topology=topo,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
                "zero_optimization": {"stage": 3}, "bf16": {"enabled": False}})
    # ZeRO state partitioned over sequence as well as data (small leaves may
    # stay replicated; at least the big moment buffers must pick it up)
    specs = [str(l.sharding.spec) for l in jax.tree_util.tree_leaves(eng.state.opt_state)]
    assert any("sequence" in s for s in specs), specs
    ids = np.random.default_rng(0).integers(0, 64, (eng.train_batch_size, 32))
    batch = llama.causal_lm_batch(ids)
    losses = [float(eng.train_batch(batch).loss) for _ in range(5)]
    assert losses[-1] < losses[0], losses


# ------------------------------------------------------------- ring attention
def test_ring_attention_matches_local(seq_topo):
    """Blockwise KV-ring attention == unsharded attention, causal and not."""
    from deepspeed_tpu.sequence.ring import ring_attention
    q, k, v = _qkv(b=2, s=64, h=4, d=16, seed=7)
    for causal in (True, False):
        expected = np.asarray(sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                   causal=causal))
        attn = ring_attention(topo=seq_topo)
        seq_sharding = NamedSharding(seq_topo.mesh, P(None, "sequence"))
        out = np.asarray(jax.jit(lambda a, b_, c: attn(a, b_, c, causal=causal))(
            jax.device_put(q, seq_sharding), jax.device_put(k, seq_sharding),
            jax.device_put(v, seq_sharding)))
        np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ring_attention_gqa_and_grads(seq_topo):
    from deepspeed_tpu.sequence.ring import ring_attention
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(1, 32, 8, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 16)).astype(np.float32))
    attn = ring_attention(topo=seq_topo)

    def loss_ring(q, k, v):
        return jnp.sum(attn(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(sdpa(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_llama_trains_with_ring_attention():
    """End-to-end: ring-attention llama trains under the engine on a
    sequence=4 x data=2 mesh (long-context CP x ZeRO composition)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.parallel import reset_topology
    from deepspeed_tpu.sequence.ring import ring_attention
    reset_topology()
    topo = MeshTopology.from_axis_dict({"data": 2, "sequence": 4})
    set_topology(topo)
    cfg = llama.LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2, seq=64)
    eng, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=llama.make_loss_fn(cfg, attention_fn=ring_attention()),
        model_parameters=llama.init_params(cfg, jax.random.PRNGKey(0)), topology=topo,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
                "zero_optimization": {"stage": 2}, "bf16": {"enabled": False}})
    ids = np.random.default_rng(0).integers(0, 64, (eng.train_batch_size, 64))
    batch = llama.causal_lm_batch(ids)
    losses = [float(eng.train_batch(batch).loss) for _ in range(5)]
    assert losses[-1] < losses[0], losses


def test_ring_memory_beats_ulysses_at_long_seq():
    """VERDICT r3 #5 'done': ring's compiled per-device peak memory undercuts
    Ulysses by the O(S/P) vs O(S) activation gap."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from deepspeed_tpu.sequence.layer import ulysses_attention
    from deepspeed_tpu.sequence.ring import ring_attention
    from deepspeed_tpu.parallel import MeshTopology, set_topology

    topo = MeshTopology.from_axis_dict({"sequence": 8})
    set_topology(topo)
    b, s, h, d = 1, 16384, 8, 64
    spec = NamedSharding(topo.mesh, PartitionSpec(None, "sequence", None, None))
    shape = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)

    def peak(fn):
        c = jax.jit(lambda q, k, v: fn(q, k, v, causal=True),
                    in_shardings=(spec, spec, spec), out_shardings=spec).lower(
                        shape, shape, shape).compile()
        ma = c.memory_analysis()
        return ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes

    ring_peak = peak(ring_attention(topo=topo))
    uly_peak = peak(ulysses_attention())
    assert ring_peak * 4 < uly_peak, (ring_peak, uly_peak)


@pytest.mark.slow
def test_ring_causal_skips_masked_steps_runtime():
    """Causal rings skip fully-masked block pairs (lax.cond on the source
    rank).  XLA's static cost analysis charges both cond branches, so the
    ~2x aggregate saving only shows at RUNTIME: the causal ring must run
    meaningfully faster than the always-compute bidirectional one.  Slow
    lane: wall-time assertion, min-of-3 to shrug off background load."""
    import time
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from deepspeed_tpu.sequence.ring import ring_attention
    from deepspeed_tpu.parallel import MeshTopology, set_topology

    topo = MeshTopology.from_axis_dict({"sequence": 8})
    set_topology(topo)
    b, s, h, d = 1, 8192, 4, 64
    spec = NamedSharding(topo.mesh, PartitionSpec(None, "sequence", None, None))
    shape = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    ring = ring_attention(topo=topo)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, h, d), np.float32), jnp.bfloat16)

    def timed(causal):
        c = jax.jit(lambda q, k, v: ring(q, k, v, causal=causal),
                    in_shardings=(spec, spec, spec), out_shardings=spec).lower(
                        shape, shape, shape).compile()
        np.asarray(c(q, q, q))  # warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = c(q, q, q)
            np.asarray(out)
            best = min(best, time.perf_counter() - t0)
        return best

    t_causal, t_full = timed(True), timed(False)
    assert t_causal < 0.9 * t_full, (t_causal, t_full)


def test_ring_causal_odd_local_seq_falls_back(seq_topo):
    """Odd local seq can't split into zigzag halves — the v2 cond-skip path
    must serve those shapes (and stay numerically correct)."""
    from deepspeed_tpu.sequence.ring import ring_attention
    q, k, v = _qkv(b=1, s=56, h=4, d=16, seed=11)  # 56/8 = 7 tokens/rank, odd
    expected = np.asarray(sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))
    attn = ring_attention(topo=seq_topo)
    seq_sharding = NamedSharding(seq_topo.mesh, P(None, "sequence"))
    out = np.asarray(jax.jit(lambda a, b_, c: attn(a, b_, c, causal=True))(
        jax.device_put(q, seq_sharding), jax.device_put(k, seq_sharding),
        jax.device_put(v, seq_sharding)))
    np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)


def test_ring_zigzag_equals_v2_schedule(seq_topo):
    """The zigzag causal schedule and the v2 cond-skip schedule compute the
    same attention (they differ only in layout/balance)."""
    import functools

    from deepspeed_tpu.sequence.ring import (_ring_attention_local,
                                             _ring_attention_zigzag)
    q, k, v = _qkv(b=2, s=64, h=4, d=16, seed=12)
    seq_sharding = NamedSharding(seq_topo.mesh, P(None, "sequence"))
    args = [jax.device_put(x, seq_sharding) for x in (q, k, v)]
    spec = P(None, "sequence", None, None)

    def run(body):
        return np.asarray(jax.jit(shard_map(
            body, mesh=seq_topo.mesh, in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False))(*args))

    zig = run(functools.partial(_ring_attention_zigzag, axis_name="sequence"))
    v2 = run(functools.partial(_ring_attention_local, axis_name="sequence", causal=True))
    np.testing.assert_allclose(zig, v2, rtol=1e-4, atol=1e-5)
