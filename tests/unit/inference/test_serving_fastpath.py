"""Serving fast path suite (ISSUE 5): device-resident batch state, async step
pipelining, adaptive decode fusion — and the invariants that make the win
provable: <=1 host sync per steady-state serve-loop iteration, bounded compile
count across a mixed-arrival scenario, and byte-identical results against the
``serving_fastpath.enabled=False`` reference loop (including under injected
allocator faults and expiring deadlines)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.entries.serve import LogitSpy
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.fastpath import (PENDING_TOKEN, DeferredTokens,
                                                 DeviceBatchState, ServeCounters)
from deepspeed_tpu.models import bloom, falcon, gptj, llama, mistral, opt, phi, qwen
from deepspeed_tpu.models.transformer import flat_slots
from deepspeed_tpu.parallel import MeshTopology
from tests.unit.fault_injection_serving import FakeClock, FaultyBlockedAllocator
from tests.unit.inference.scenario import run_scenario

NO_FUSION = 10**6  # fusion_min_steps too high to ever fire: forces stepwise


def _cfg(seq=256):
    return llama.LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                                  kv_heads=2, seq=seq)


_PARAMS = {}


def _engine(config=None, *, seq=256, **kw):
    cfg = _cfg(seq)
    if seq not in _PARAMS:
        _PARAMS[seq] = llama.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(config=config if config is not None else {"dtype": "float32"},
                    num_blocks=64, block_size=8, max_blocks_per_seq=8,
                    token_budget=32, max_seqs_per_step=8)
    defaults.update(kw)
    return InferenceEngineV2(llama, cfg, _PARAMS[seq], **defaults)


_REFERENCE = {"dtype": "float32", "serving_fastpath": {"enabled": False}}
_STEPWISE = {"dtype": "float32", "serving_fastpath": {"fusion_min_steps": NO_FUSION}}


@functools.lru_cache(maxsize=None)
def _shared_engine(kind="fast"):
    """One engine a configuration for the cases that only serve waves through
    it and read tokens, or counters as deltas: the default, the padded oracle
    (``reference``) and the pipeline with fusion off (``stepwise``).  A case
    that counts compiles, injects faults, samples or brings a clock or a
    collector builds its own with ``_engine``."""
    return _engine({"fast": None, "reference": _REFERENCE, "stepwise": _STEPWISE}[kind])


def _no_pending(results):
    for r in results:
        toks = r.tokens if hasattr(r, "tokens") else r
        assert PENDING_TOKEN not in toks, f"placeholder escaped: {toks}"


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17], [20, 21]]


# ----------------------------------------------------- reference equivalence
def test_fastpath_matches_reference_strict_and_nonstrict():
    fast = _shared_engine().generate(PROMPTS, max_new_tokens=9)
    ref = _shared_engine("reference").generate(PROMPTS, max_new_tokens=9)
    assert fast == ref
    _no_pending(fast)
    fast_ns = _shared_engine().generate(PROMPTS, max_new_tokens=9, strict=False)
    assert [r.tokens for r in fast_ns] == ref
    assert all(r.status == "ok" for r in fast_ns)


def test_pipelined_stepwise_matches_reference_incl_eos():
    """Fusion disabled: every decode step goes through the deferred-pick
    pipeline (dispatch N, absorb N-1), including the eos/max_new overshoot
    truncation — tokens must still be byte-identical."""
    a, b = _shared_engine("stepwise"), _shared_engine("reference")
    ref = b.generate(PROMPTS, max_new_tokens=7)
    got = a.generate(PROMPTS, max_new_tokens=7)
    assert got == ref
    assert a.counters.burst_tokens == 0  # really went stepwise
    # eos mid-decode: the in-flight overshoot token must be truncated away
    eos = ref[0][len(PROMPTS[0]) + 3]
    got = a.generate(PROMPTS, max_new_tokens=7, eos_token_id=eos)
    want = b.generate(PROMPTS, max_new_tokens=7, eos_token_id=eos)
    assert got == want
    _no_pending(got)
    assert a.health()["live_seqs"] == 0
    assert a.manager.allocator.free_blocks == b.manager.allocator.free_blocks


@pytest.mark.slow
def test_fastpath_matches_reference_under_allocator_faults():
    """Injected allocator faults only delay scheduling; the fast path must
    produce the same tokens as the faulted reference AND the healthy run,
    with the pool fully reclaimed."""
    def run(conf):
        eng = _engine(conf)
        eng.manager.allocator = FaultyBlockedAllocator(64, fail_rate=0.3, seed=7)
        free0 = eng.manager.allocator.free_blocks
        res = eng.generate(PROMPTS, max_new_tokens=6, strict=False)
        assert eng.manager.allocator.injected_failures > 0
        assert eng.manager.allocator.free_blocks == free0
        return [(r.status, r.tokens) for r in res]

    fast = run({"dtype": "float32"})
    ref = run(_REFERENCE)
    assert fast == ref
    healthy = _engine().generate(PROMPTS, max_new_tokens=6)
    assert [t for _, t in fast] == healthy


def test_fastpath_matches_reference_under_expiring_deadlines():
    """With deadlines live the pipeline disengages (wave-boundary flush rule),
    so eviction timing — and therefore the partial token lists — must be
    byte-identical to the reference loop on the same fake clock."""
    def run(conf):
        clock = FakeClock(tick=0.05)
        eng = _engine(conf, clock=clock)
        res = eng.generate([[1, 2, 3, 4, 5], [7, 8, 9]], max_new_tokens=64,
                           strict=False, ttl_s=0.4)
        return [(r.uid, r.status, r.tokens) for r in res], clock.calls

    fast, fast_calls = run({"dtype": "float32"})
    ref, ref_calls = run(_REFERENCE)
    assert fast == ref
    assert fast_calls == ref_calls  # identical clock consumption = same policy
    assert any(status == "deadline_expired" for _, status, _ in fast)
    for _, _, toks in fast:
        assert PENDING_TOKEN not in toks


# ------------------------------------------------------- host-sync invariants
def test_steady_state_decode_at_most_one_sync_per_iteration():
    eng = _shared_engine("stepwise")
    before = eng.counters.snapshot()
    eng.generate(PROMPTS, max_new_tokens=12)
    c = eng.counters.delta_since(before)
    assert c["loop_iterations"] > 0
    assert c["host_syncs"] <= c["loop_iterations"] + c["flushes"], c


def test_fused_decode_is_sub_one_sync_per_token():
    eng = _shared_engine()
    before = eng.counters.snapshot()
    out = eng.generate(PROMPTS, max_new_tokens=16)
    c = eng.counters.delta_since(before)
    tokens = sum(len(t) - len(p) for t, p in zip(out, PROMPTS))
    assert c["burst_tokens"] > c["step_tokens"]  # fusion carried the decode
    assert c["host_syncs"] < tokens / 2, c
    assert c["host_syncs"] <= c["loop_iterations"] + c["flushes"]


def test_bounded_compiles_across_three_wave_scenario():
    """The mixed-arrival scenario (3 waves landing mid-decode): the cold
    pass compiles a bounded program set; an identical warm pass — same widths
    thanks to the sticky-table reset on idle — compiles NOTHING."""
    eng = _engine(num_blocks=128, max_blocks_per_seq=16, token_budget=64)
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


def test_serving_scenario_stall_guard():
    """A scheduler that never emits must not spin the scenario forever."""

    class StuckEngine:
        def __init__(self):
            self.manager = type("M", (), {"seqs": {0: type("S", (), {
                "pending_tokens": 1, "done": False})()}})()
            self.counters = ServeCounters()
        def put(self, uids, prompts):
            pass
        def step(self):
            return {}
        def decode_burst(self, k, **kw):
            return None  # not fusible: the scenario must fall back to step()
        def flush(self, uid):
            pass

    tokens, dt, lats, hit_stall, link = run_scenario(
        StuckEngine(), [[1, 2]], {0: [0]}, max_new=4)
    assert tokens == 0 and lats == []  # bailed via the stall counter
    assert hit_stall  # and the bail is reported, not silent (ISSUE 4 review)
    assert link["host_syncs"] == 0  # nothing ever reached the device


# ------------------------------------------------------------ rng determinism
def test_burst_and_stepwise_sample_identical_tokens():
    """Satellite: the fused burst threads one split key per step (no pre-split
    of the carried key), so sampled decode is sample-for-sample identical to
    the stepwise pick for the same seed."""
    conf = {"dtype": "float32", "temperature": 1.0, "top_k": 20, "seed": 5}
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]

    a = _engine(dict(conf), max_seqs_per_step=4, token_budget=16)
    a.put([0, 1], prompts)
    while len(a.step(greedy=False)) < 2:
        pass
    stepwise = {0: [], 1: []}
    for _ in range(5):
        for u, t in a.step(greedy=False).items():
            stepwise[u].append(t)

    b = _engine(dict(conf), max_seqs_per_step=4, token_budget=16)
    b.put([0, 1], prompts)
    while len(b.step(greedy=False)) < 2:
        pass
    burst = b.decode_burst(5, greedy=False)
    assert burst == stepwise
    # and the carried-out rng advances: a second burst continues the stream
    again = b.decode_burst(5, greedy=False)
    assert again is not None and again != burst


# -------------------------------------------------------- bucket hysteresis
def test_table_width_steps_and_hysteresis():
    eng = _engine(max_blocks_per_seq=64)
    # grows in TABLE_STEP multiples, not powers of two
    assert eng._table_width_for(1) == 4
    assert eng._table_width_for(5) == 8
    assert eng._table_width_for(9) == 12
    # sticky: a smaller batch keeps the reached width (no recompile flap)...
    for _ in range(eng.TABLE_SHRINK_PATIENCE - 1):
        assert eng._table_width_for(2) == 12
    # ...until the shrink patience runs out
    assert eng._table_width_for(2) == 4
    # interleaving a tall step resets the patience counter
    assert eng._table_width_for(11) == 12
    for _ in range(eng.TABLE_SHRINK_PATIENCE // 2):
        assert eng._table_width_for(2) == 12
    assert eng._table_width_for(10) == 12
    # capped at max_blocks_per_seq
    assert eng._table_width_for(200) == 64


def test_table_width_reference_mode_keeps_doubling():
    eng = _engine(_REFERENCE, max_blocks_per_seq=64)
    assert eng._table_width_for(5) == 8
    assert eng._table_width_for(9) == 16
    assert eng._table_width_for(2) == 2  # no hysteresis in the oracle


def test_table_width_resets_on_idle_engine():
    eng = _engine()
    eng._table_width_for(7)  # -> 8, sticky
    assert eng._table_width == 8
    eng.put([0], [[1, 2, 3]])  # manager was empty: fresh serve, fresh widths
    assert eng._table_width == 0
    eng.flush(0)


# --------------------------------------------------------------- unit pieces
def test_device_batch_state_uploads_only_deltas():
    c = ServeCounters()
    state = DeviceBatchState(c)
    key = (4, 2, 4)
    row = lambda i, tok, nt, sp, tab: (i, np.asarray([i, tok, 0, nt, sp] + tab,
                                                     np.int32))
    rows = [row(0, 5, 1, 3, [1, 2, 9, 9]), row(1, 6, 1, 4, [3, 9, 9, 9])]
    state.update(key, rows, n_active=2, trash_block=9)
    up0, ints0 = c.uploads, c.upload_ints
    # identical step: nothing crosses the link
    state.update(key, rows, n_active=2, trash_block=9)
    assert (c.uploads, c.upload_ints) == (up0, ints0)
    # one changed row: exactly one upload, O(row) ints
    rows2 = [rows[0], row(1, 7, 1, 5, [3, 9, 9, 9])]
    state.update(key, rows2, n_active=2, trash_block=9)
    assert c.uploads == up0 + 1
    assert c.upload_ints - ints0 <= 2 * (3 + 2 + 4)  # padded to pow2 rows
    # shrinking neutralizes the stale row (n_tokens=0, tables=trash)
    state.update(key, [rows2[0]], n_active=1, trash_block=9)
    slot = state.slot(key, 9)
    assert slot.active_rows == 1
    assert int(np.asarray(slot.n_tokens)[1]) == 0
    assert list(np.asarray(slot.tables)[1]) == [9, 9, 9, 9]


def test_deferred_tokens_patch_and_overshoot_drop():
    class Seq:
        def __init__(self, toks):
            self.tokens = toks

    class Mgr:
        def __init__(self):
            self.seqs = {0: Seq([1, 2, PENDING_TOKEN]), 1: Seq([5, PENDING_TOKEN])}

    mgr = Mgr()
    c = ServeCounters()
    import jax.numpy as jnp
    d = DeferredTokens(toks_dev=jnp.asarray([42, 43], jnp.int32),
                       emits=[(0, 2, 0), (1, 1, 1), (7, 0, 1)],
                       row_of={0: 0, 1: 1, 7: 1}, counters=c)
    d.drop_emit(7)  # retired mid-flight
    out = d.patch(mgr)
    assert out == {0: 42, 1: 43}
    assert mgr.seqs[0].tokens == [1, 2, 42] and mgr.seqs[1].tokens == [5, 43]
    assert c.host_syncs == 1
    assert d.patch(mgr) == {0: 42, 1: 43}  # idempotent, no second sync
    assert c.host_syncs == 1


def test_prewarm_populates_bucket_cache():
    eng = _engine()
    assert not eng._fwd_cache
    eng.generate([[1, 2, 3]], max_new_tokens=2)
    # prewarm ran at intake: at least one AOT bucket landed in the cache and
    # the compile counter saw it
    assert eng.counters.compiles >= 1 and eng._fwd_cache


def test_fastpath_gauges_flow_through_telemetry(tmp_path):
    import json

    from deepspeed_tpu.monitor.telemetry import TelemetryCollector
    from deepspeed_tpu.runtime.config import TelemetryConfig
    jsonl = str(tmp_path / "fastpath.jsonl")
    collector = TelemetryCollector(config=TelemetryConfig(jsonl_path=jsonl))
    eng = _engine(telemetry=collector)
    eng.generate([[1, 2, 3, 4], [6, 7]], max_new_tokens=4)
    collector.close()
    with open(jsonl) as fh:
        records = [json.loads(line) for line in fh]
    gauges = [r for r in records if r.get("kind") == "gauges"
              and "fastpath_host_syncs" in r]
    assert gauges
    last = gauges[-1]
    for key in ("fastpath_dispatches", "fastpath_compiled_programs",
                "fastpath_burst_fraction", "fastpath_upload_ints"):
        assert key in last
    assert eng.health()["fastpath"]["host_syncs"] >= 1


# ------------------------------- live tokens, not the padded bucket (ISSUE 25)
# token_budget 16 under buckets of up to 4 x 16 slots: the mixed steps compact.
# Request 2's 40-token prompt prefills in chunks beside the decodes of 0, 1 and
# 3, and in the first step the prompts of 0 and 1 end while 2's first chunk
# fills what is left of the budget: three chunks share that step.
_COMPACT_PROMPTS = [[5, 6, 7], [9, 10, 11, 12, 13], list(range(20, 60)), [70, 71]]
_TINY = dict(vocab=128, hidden=64, layers=2, heads=4, seq=256)
_FAMILIES = {
    "llama": lambda: (llama, llama.LlamaConfig.tiny(kv_heads=4, **_TINY)),
    # the window (16) is shorter than the long prompt
    "mistral": lambda: (mistral, mistral.MistralConfig.tiny(kv_heads=4, window=16, **_TINY)),
    "qwen": lambda: (qwen, qwen.QwenConfig.tiny(kv_heads=2, **_TINY)),  # qkv biases
    "phi": lambda: (phi, phi.PhiConfig.tiny(**_TINY)),  # parallel residual, partial rotary
    "falcon": lambda: (falcon, falcon.FalconConfig.tiny(kv_heads=1, **_TINY)),  # MQA
    "gptj": lambda: (gptj, gptj.GPTJConfig.tiny(**_TINY)),  # interleaved rotary
    "opt": lambda: (opt, opt.OPTConfig.tiny(**_TINY)),  # learned positions
    "bloom": lambda: (bloom, bloom.BloomConfig.tiny(**_TINY)),  # ALiBi, embedding norm
}


def _compacting_engine(family, fastpath, tp=1):
    module, cfg = _FAMILIES[family]()
    topo = (MeshTopology.from_axis_dict({"tensor": tp}, devices=jax.devices()[:tp])
            if tp > 1 else None)
    return InferenceEngineV2(
        module, cfg, module.init_params(cfg, jax.random.PRNGKey(1)), topology=topo,
        config={"dtype": "float32", "serving_fastpath": {"enabled": fastpath}},
        num_blocks=64, block_size=8, max_blocks_per_seq=8, token_budget=16,
        max_seqs_per_step=4)


# One engine a (family, fastpath, tp) for the cases that only serve through it:
# a drained engine replays a wave step for step (its prefix tree is empty, its
# table width reset), so they read tokens, and counters as deltas.  A case that
# breaks a step or reaches into the scheduler builds its own.
_shared_compacting_engine = functools.lru_cache(maxsize=None)(_compacting_engine)


@pytest.mark.parametrize("family,tp", [("llama", 1), ("mistral", 1), ("llama", 4), ("falcon", 1),
                                       ("bloom", 1), ("opt", 1)],
                         ids=["llama", "mistral-window", "llama-tp4", "falcon-parallel-residual",
                              "bloom-alibi", "opt-learned-positions"])
def test_compacted_mixed_wave_matches_the_padded_reference(family, tp):
    """Tokens against ``_step_reference``, and the logits row that ends each
    prompt's prefill read by the chip benchmark's own reader: through
    ``engine._compiled_fwd(n, t, b)`` and its six-argument callable, at
    ``logits[row, n_tokens[row] - 1]`` of its result, which since ISSUE 44 is
    each row's last live logits alone, ``[n, 1, V]``: the reader's index past
    the end clamps to ``[row, 0]`` (``test_last_rows_head.py`` pins that).
    Every family is handed the bound (ISSUE 29): the engine asks no module what
    its forward takes."""
    served = {}
    for fastpath in (True, False):
        eng = _shared_compacting_engine(family, fastpath, tp)
        before = eng.counters.snapshot()
        with LogitSpy(eng, _COMPACT_PROMPTS) as spy:
            served[fastpath] = (eng.generate(_COMPACT_PROMPTS, max_new_tokens=8), spy.rows,
                                eng.counters.delta_since(before), eng)
    (fast, fast_rows, c, fast_eng), (ref, ref_rows, ref_c, _) = served[True], served[False]
    assert fast == ref
    _no_pending(fast)
    assert sorted(fast_rows) == sorted(ref_rows) == [0, 1, 2, 3]
    for i in fast_rows:
        np.testing.assert_allclose(fast_rows[i], ref_rows[i], atol=1e-5, rtol=0)
    # the wave really ran compacted: the two mixed buckets are over the bound
    names = {e["name"] for e in fast_eng.ledger.events if e["site"] == "fwd"}
    assert {"fwd_n4_t8_b4", "fwd_n4_t16_b8"} <= names
    assert c["compact_passes"] >= 2 and ref_c["compact_passes"] == 0
    assert c["live_tokens"] == ref_c["live_tokens"] <= c["token_slots"]
    assert c["token_slots"] < ref_c["token_slots"]
    fast_eng.check_kv_invariant()


def _ragged_chunk(rng, counts, t, block_size, num_blocks, width):
    """A ragged [n, t] chunk with ``counts`` live tokens a row, each live row
    at a random start inside its own blocks, and a pool of random content."""
    n = len(counts)
    counts = np.asarray(counts, np.int32)
    room = width * block_size - counts
    start = np.where(counts > 0, rng.integers(0, room + 1), 0).astype(np.int32)
    tables = np.full((n, width), num_blocks - 1, np.int32)
    free = rng.permutation(num_blocks - 1)
    for i in np.nonzero(counts)[0]:
        tables[i] = free[i * width:(i + 1) * width]
    tokens = np.zeros((n, t), np.int32)
    for i in range(n):
        tokens[i, :counts[i]] = rng.integers(1, 128, size=counts[i])
    return tokens, counts, start, tables


@functools.lru_cache(maxsize=None)
def _family_forward(make):
    """``make() -> (module, cfg)``, once a family: the module, its config, its
    parameters with every leaf moved off its start (biases start at zero and
    gains at one) and ONE jitted ``forward_paged(params, tokens, counts, start,
    tables, kv, bound, last_rows)`` over blocks of 8, compiled once a ``(bound,
    last_rows)``: the chunk and the pool are arguments, so a case is a call."""
    module, cfg = make()
    leaves, tree = jax.tree_util.tree_flatten(module.init_params(cfg, jax.random.PRNGKey(3)))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(jax.random.PRNGKey(i), leaf.shape, leaf.dtype)
        for i, leaf in enumerate(leaves)])
    forward = jax.jit(lambda params, tokens, counts, start, tables, kv, bound, last_rows:
                      module.forward_paged(cfg, params, tokens, counts, start, tables, kv,
                                           block_size=8, live_token_bound=bound,
                                           last_rows=last_rows), static_argnums=(6, 7))
    return module, cfg, params, forward


_COUNTS = {"empty-row-between": [10, 0, 1, 5], "one-row-exactly-S": [16, 0, 0, 0],
           "leading-empty-rows": [0, 0, 0, 9], "decodes-around-a-chunk-exactly-S": [1, 1, 13, 1],
           "under-S": [0, 7, 0, 0], "every-row-exactly-S": [4, 4, 4, 4]}
# the index arithmetic is the driver's, the same for every family: Llama and
# Mistral meet all six shapes, the others the three that differ most
_THREE = ("empty-row-between", "leading-empty-rows", "decodes-around-a-chunk-exactly-S")


@pytest.mark.parametrize("family,counts", [
    pytest.param(family, counts, id=f"{shape}-{family}")
    for shape, counts in _COUNTS.items() for family in _FAMILIES
    if family in ("llama", "mistral") or shape in _THREE])
def test_forward_paged_compacted_agrees_with_padded(family, counts):
    module, cfg, params, forward = _family_forward(_FAMILIES[family])
    rng = np.random.default_rng(sum(c * 17**i for i, c in enumerate(counts)))
    num_blocks, block_size, t, bound = 33, 8, 16, 16
    tokens, counts, start, tables = _ragged_chunk(rng, counts, t, block_size, num_blocks, 8)
    assert flat_slots(len(counts), t, bound) == 16 and counts.sum() <= 16
    kv = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), jnp.float32),
        module.init_paged_cache(cfg, num_blocks, block_size, dtype=jnp.float32))
    padded, kv_padded = forward(params, tokens, counts, start, tables, kv, None, False)
    flat, kv_flat = forward(params, tokens, counts, start, tables, kv, bound, False)
    live = np.arange(t)[None, :] < counts[:, None]
    assert flat.shape == padded.shape
    np.testing.assert_allclose(np.asarray(flat)[live], np.asarray(padded)[live],
                               atol=1e-5, rtol=0)
    assert not np.asarray(flat)[~live].any()  # nothing lands where no live token sits
    for name in ("k", "v"):  # every block but the trash block: written ones and untouched ones
        np.testing.assert_allclose(np.asarray(kv_flat[name])[:, :-1],
                                   np.asarray(kv_padded[name])[:, :-1], atol=1e-5, rtol=0)
        written = np.asarray(kv_flat[name])[:, :-1] != np.asarray(kv[name])[:, :-1]
        assert written.any(axis=(0, 2, 4)).sum() == counts.sum()  # one (block, offset) a token


@pytest.mark.parametrize("n,t,bound,slots", [(32, 256, 256, 256), (4, 256, 256, 256),
                                             (32, 1, 256, None), (1, 256, 256, None),
                                             (8, 5, 32, 32), (8, 4, 32, None),
                                             (4, 8, 20, 24), (4, 8, None, None)])
def test_flat_slots_compacts_only_a_bucket_over_the_bound(n, t, bound, slots):
    assert flat_slots(n, t, bound) == slots


def test_a_step_over_the_bound_is_refused_before_dispatch():
    eng = _compacting_engine("llama", True)
    eng.scheduler.token_budget = 64  # raised behind the engine's back
    eng.put([0], [list(range(1, 41))])
    before = eng.counters.snapshot()
    with pytest.raises(RuntimeError, match=r"40 live tokens over the 16 token slots"):
        eng.step()
    assert eng.counters.snapshot() == before  # nothing was launched or uploaded
    # the reference step runs the padded bucket, which has room for them
    ref = _compacting_engine("llama", False)
    ref.scheduler.token_budget = 64
    ref.put([0], [list(range(1, 41))])
    assert len(ref.step()) == 1
