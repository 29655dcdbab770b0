"""Sharded serving fast path (ISSUE 15): the ≤1-sync serve loop under TP×DP.

PR 5's fast path (device-resident batch state, async pipelining, adaptive
decode fusion, AOT prewarm) used to fall back to the rebuild-per-step slow
path whenever tp > 1 because DeviceBatchState committed single-device
buffers.  The rebuilt batch state replicates over the engine's mesh, so every
invariant the single-chip suite pins must now hold on the 8-device CPU mesh:
byte-identical tokens vs the ``serving_fastpath.enabled=False`` oracle
(strict/non-strict, greedy/sampled, under faults / deadlines / CoW prefix
sharing), ≤1 host sync per steady iteration, zero warm recompiles, and AOT
prewarm buckets that are actually HIT by the first sharded dispatch.
"""

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.fastpath import PENDING_TOKEN
from deepspeed_tpu.parallel import MeshTopology
from deepspeed_tpu.models import llama
from tests.unit.fault_injection_serving import FakeClock, FaultyBlockedAllocator
from tests.unit.inference.scenario import run_scenario

NO_FUSION = 10**6  # fusion_min_steps too high to ever fire: forces stepwise

PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17], [20, 21]]


def _cfg(seq=256):
    return llama.LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                                  kv_heads=2, seq=seq)


_PARAMS = {}


def _engine(config=None, *, axes=None, seq=256, **kw):
    """tp=2 engine by default (axes={'tensor': 2, 'data': -1}); axes=None
    with tp=0 gives the single-chip twin for cross-checks."""
    cfg = _cfg(seq)
    if seq not in _PARAMS:
        _PARAMS[seq] = llama.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(config=config if config is not None else {"dtype": "float32"},
                    num_blocks=64, block_size=8, max_blocks_per_seq=8,
                    token_budget=32, max_seqs_per_step=8)
    defaults.update(kw)
    topo = MeshTopology.from_axis_dict(axes) if axes is not None else None
    return InferenceEngineV2(llama, cfg, _PARAMS[seq], topology=topo, **defaults)


TP2 = {"tensor": 2, "data": -1}
TP2_DP4 = {"tensor": 2, "data": 4}  # the explicit TP×DP mesh


# ----------------------------------------------------- reference equivalence
@pytest.mark.slow
def test_tp2_fastpath_matches_reference_and_single_chip():
    fast = _engine(axes=TP2).generate(PROMPTS, max_new_tokens=9)
    ref = _engine({"dtype": "float32", "serving_fastpath": {"enabled": False}},
                  axes=TP2).generate(PROMPTS, max_new_tokens=9)
    assert fast == ref
    # the sharded fast path also reproduces the single-chip fast path exactly
    assert fast == _engine().generate(PROMPTS, max_new_tokens=9)
    for toks in fast:
        assert PENDING_TOKEN not in toks
    fast_ns = _engine(axes=TP2).generate(PROMPTS, max_new_tokens=9, strict=False)
    assert [r.tokens for r in fast_ns] == ref
    assert all(r.status == "ok" for r in fast_ns)


@pytest.mark.slow  # heavy tp=2 interplay variant: slow lane (fast_then_slow)
def test_tp2_sampled_matches_reference():
    """Sampled serving at tp=2: candidate-set sampling + the carried rng are
    shared by both loops, so fastpath on/off must be sample-identical."""
    conf = {"dtype": "float32", "temperature": 0.9, "top_k": 20, "seed": 5}
    fast = _engine(dict(conf), axes=TP2).generate(PROMPTS, max_new_tokens=7,
                                                  greedy=False)
    ref = _engine({**conf, "serving_fastpath": {"enabled": False}},
                  axes=TP2).generate(PROMPTS, max_new_tokens=7, greedy=False)
    assert fast == ref


@pytest.mark.slow  # heavy tp=2 interplay variant: slow lane (fast_then_slow)
def test_tpdp_mesh_2x4_fastpath_matches_reference():
    """The full TP×DP mesh (tensor=2, data=4): batch state replicates over
    BOTH axes and the pipelined loop still matches the oracle."""
    fast_eng = _engine(axes=TP2_DP4)
    fast = fast_eng.generate(PROMPTS, max_new_tokens=6)
    ref = _engine({"dtype": "float32", "serving_fastpath": {"enabled": False}},
                  axes=TP2_DP4).generate(PROMPTS, max_new_tokens=6)
    assert fast == ref
    c = fast_eng.counters
    assert c.host_syncs <= c.loop_iterations + c.flushes, c.snapshot()


@pytest.mark.slow  # heavy tp=2 interplay variant: slow lane (fast_then_slow)
def test_tp2_pipelined_stepwise_matches_reference_incl_eos():
    """Fusion disabled at tp=2: every decode goes through the deferred-pick
    pipeline (dispatch N, absorb N-1) over the sharded buffers, including the
    eos/max_new overshoot truncation."""
    ref_eng = _engine({"dtype": "float32", "serving_fastpath": {"enabled": False}},
                      axes=TP2)
    ref = ref_eng.generate(PROMPTS, max_new_tokens=7)
    pl_eng = _engine({"dtype": "float32",
                      "serving_fastpath": {"fusion_min_steps": NO_FUSION}},
                     axes=TP2)
    got = pl_eng.generate(PROMPTS, max_new_tokens=7)
    assert got == ref
    assert pl_eng.counters.burst_tokens == 0  # really went stepwise
    eos = ref[0][len(PROMPTS[0]) + 3]
    a = _engine({"dtype": "float32",
                 "serving_fastpath": {"fusion_min_steps": NO_FUSION}}, axes=TP2)
    b = _engine({"dtype": "float32", "serving_fastpath": {"enabled": False}},
                axes=TP2)
    got = a.generate(PROMPTS, max_new_tokens=7, eos_token_id=eos)
    want = b.generate(PROMPTS, max_new_tokens=7, eos_token_id=eos)
    assert got == want
    assert a.health()["live_seqs"] == 0
    assert a.manager.allocator.free_blocks == b.manager.allocator.free_blocks


# ------------------------------------------------------- host-sync invariants
def test_tp2_steady_state_decode_at_most_one_sync_per_iteration():
    eng = _engine({"dtype": "float32",
                   "serving_fastpath": {"fusion_min_steps": NO_FUSION}}, axes=TP2)
    eng.generate(PROMPTS, max_new_tokens=12)
    c = eng.counters
    assert c.loop_iterations > 0
    assert c.host_syncs <= c.loop_iterations + c.flushes, c.snapshot()


def test_tp2_fused_decode_is_sub_one_sync_per_token():
    eng = _engine(axes=TP2)
    out = eng.generate(PROMPTS, max_new_tokens=16)
    c = eng.counters
    tokens = sum(len(t) - len(p) for t, p in zip(out, PROMPTS))
    assert c.burst_tokens > c.step_tokens  # fusion carried the decode
    assert c.host_syncs < tokens / 2, c.snapshot()
    assert c.host_syncs <= c.loop_iterations + c.flushes


def test_tp2_bounded_compiles_across_three_wave_scenario():
    """The acceptance scenario: 3 arrival waves landing mid-decode at tp=2 —
    bounded cold compiles, ZERO warm recompiles, sub-1-sync-per-token."""
    eng = _engine(axes=TP2, num_blocks=128, max_blocks_per_seq=16,
                  token_budget=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 128, 16).tolist() for _ in range(6)]
    arrivals = {0: [0, 1, 2], 5: [3], 9: [4, 5]}
    run_scenario(eng, prompts, arrivals, max_new=8)
    cold = eng.counters.snapshot()
    assert 0 < cold["compiles"] <= 24, cold
    tokens, _, _, stalled, link = run_scenario(eng, prompts, arrivals, max_new=8)
    assert not stalled and tokens == 6 * 8
    assert link["compiles"] == 0, link
    assert link["burst_tokens"] > 0
    assert link["host_syncs"] < tokens


# ------------------------------------------------------------- AOT prewarm
def test_tp2_prewarmed_buckets_are_hit_not_recompiled():
    """Satellite: `_aot_compile_fwd` lowers against SHARDED avals at tp>1, so
    a prewarmed executable is actually hit by the first sharded dispatch.
    Proof by counters/cache keys: prewarm every forward bucket a scenario
    uses, then serve it — the forward-bucket key set must not grow (every
    dispatch hit a prewarmed executable; an aval mismatch would raise on an
    AOT-compiled callable rather than silently retracing)."""
    probe = _engine(axes=TP2)
    probe.generate(PROMPTS, max_new_tokens=6)
    fwd_keys = [k for k in probe._fwd_cache
                if isinstance(k, tuple) and len(k) == 3
                and all(isinstance(v, int) for v in k)]
    assert fwd_keys  # the scenario compiled at least one forward bucket

    eng = _engine(axes=TP2)
    for key in fwd_keys:
        eng._aot_compile_fwd(*key)
    compiled_fwds = {k: eng._fwd_cache[k] for k in fwd_keys}
    out = eng.generate(PROMPTS, max_new_tokens=6)
    assert out == probe.generate(PROMPTS, max_new_tokens=6)
    after = [k for k in eng._fwd_cache
             if isinstance(k, tuple) and len(k) == 3
             and all(isinstance(v, int) for v in k)]
    assert sorted(after) == sorted(fwd_keys), \
        f"sharded dispatch missed the prewarmed buckets: {after} vs {fwd_keys}"
    for k, v in compiled_fwds.items():
        assert eng._fwd_cache[k] is v  # the AOT executable itself was used


# --------------------------------------------- interplay with serving features
@pytest.mark.slow  # heavy tp=2 interplay variant: slow lane (fast_then_slow)
def test_tp2_fastpath_matches_reference_under_allocator_faults():
    def run(conf):
        eng = _engine(conf, axes=TP2)
        eng.manager.allocator = FaultyBlockedAllocator(64, fail_rate=0.3, seed=7)
        free0 = eng.manager.allocator.free_blocks
        res = eng.generate(PROMPTS, max_new_tokens=6, strict=False)
        assert eng.manager.allocator.injected_failures > 0
        assert eng.manager.allocator.free_blocks == free0
        return [(r.status, r.tokens) for r in res]

    fast = run({"dtype": "float32"})
    ref = run({"dtype": "float32", "serving_fastpath": {"enabled": False}})
    assert fast == ref
    healthy = _engine(axes=TP2).generate(PROMPTS, max_new_tokens=6)
    assert [t for _, t in fast] == healthy


@pytest.mark.slow  # heavy tp=2 interplay variant: slow lane (fast_then_slow)
def test_tp2_fastpath_matches_reference_under_expiring_deadlines():
    def run(conf):
        clock = FakeClock(tick=0.05)
        eng = _engine(conf, axes=TP2, clock=clock)
        res = eng.generate([[1, 2, 3, 4, 5], [7, 8, 9]], max_new_tokens=64,
                           strict=False, ttl_s=0.4)
        return [(r.uid, r.status, r.tokens) for r in res], clock.calls

    fast, fast_calls = run({"dtype": "float32"})
    ref, ref_calls = run({"dtype": "float32",
                          "serving_fastpath": {"enabled": False}})
    assert fast == ref
    assert fast_calls == ref_calls  # identical clock consumption = same policy
    assert any(status == "deadline_expired" for _, status, _ in fast)
    for _, _, toks in fast:
        assert PENDING_TOKEN not in toks


HEADER = list(range(100, 124))  # 3 full shared blocks at block_size=8


@pytest.mark.slow
def test_tp2_prefix_cache_cow_matches_reference_and_keeps_kv_sharded():
    """CoW prefix sharing at tp=2: the device block copy (`_cow_copy_block`)
    must run against the HEAD-SHARDED pool without collapsing its placement,
    and tokens must match both the slow-path oracle and the cache-off run."""
    rng = np.random.default_rng(3)
    # the duplicate of a full-block prompt is cached to its LAST token: the
    # scheduler defers it one step, the retry maps the whole prompt off the
    # tree, and the recomputed final position rides the CoW device copy
    prompts = [HEADER, HEADER, HEADER + rng.integers(1, 128, 4).tolist()]

    def run(conf):
        eng = _engine(conf, axes=TP2)
        out = eng.generate(prompts, max_new_tokens=6)
        return eng, out

    fast, out_fast = run({"dtype": "float32",
                          "serving_prefix_cache": {"enabled": True}})
    pc = fast.health()["prefix_cache"]
    assert pc["hits_total"] > 0 and pc["cow_copies_total"] >= 1, pc
    # the copied pool is still head-sharded over 'tensor' (tp=2)
    shard = fast.kv["k"].sharding.shard_shape(fast.kv["k"].shape)
    assert shard[2] == _cfg().num_kv_heads // 2
    fast.check_kv_invariant()

    _, out_ref = run({"dtype": "float32",
                      "serving_prefix_cache": {"enabled": True},
                      "serving_fastpath": {"enabled": False}})
    assert out_fast == out_ref
    _, out_nocache = run({"dtype": "float32",
                          "serving_prefix_cache": {"enabled": False}})
    assert out_fast == out_nocache


# ------------------------------------------------------------- observability
def test_tp2_health_reports_parallelism_shape():
    eng = _engine(axes=TP2)
    eng.generate([PROMPTS[0]], max_new_tokens=3)
    fp = eng.health()["fastpath"]
    assert fp["tp"] == 2
    assert fp["mesh_shape"]["tensor"] == 2
    assert fp["host_syncs"] >= 1
    single = _engine()
    assert single.health()["fastpath"]["tp"] == 1
    assert single.health()["fastpath"]["mesh_shape"] == {}
