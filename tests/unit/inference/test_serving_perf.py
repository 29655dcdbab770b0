"""Serving performance observatory suite (ISSUE 16): FakeClock-exact phase
attribution, compile-ledger classes (prewarmed/cold/warm),
zero-perturbation byte-identity (tokens + ServeCounters with the
observatory on vs off and with a jax.profiler trace open vs none, fastpath
AND reference paths), Chrome-trace phase
tracks and the serve-iteration jax.profiler window — all on the CPU backend
with deterministic clocks."""

import contextlib
import json

import jax
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import llama
from deepspeed_tpu.monitor.exposition import parse_exposition, render
from deepspeed_tpu.monitor.metrics import MetricsRegistry, populate_from_engine
from deepspeed_tpu.monitor.perf import (CLASS_COLD, CLASS_PREWARMED, CLASS_WARM,
                                        PHASES, CompileLedger, StepPhaseProfiler)
from deepspeed_tpu.monitor.telemetry import TelemetryCollector
from deepspeed_tpu.runtime.config import ServingPerfConfig, TelemetryConfig
from tests.unit.fault_injection_serving import FakeClock
from tests.unit.inference.test_serving_programs_slots_spans import _ProfilerTrace


class _TracerStub:
    """Records phase_span/event calls; stands in for RequestTracer."""

    def __init__(self):
        self.spans = []
        self.events = []

    def phase_span(self, name, start_s, dur_s, track=0):
        self.spans.append((name, start_s, dur_s, track))

    def event(self, name, **fields):
        self.events.append((name, fields))


# -------------------------------------------------------- phase profiler unit
def _profiler(tick=0.01, *, tracer=None, **cfg_kw):
    cfg = ServingPerfConfig(enabled=True, **cfg_kw)
    clock = FakeClock(tick=tick)
    return StepPhaseProfiler(cfg, clock=clock, tracer=tracer), clock


def test_profiler_exact_attribution_and_residual_to_other():
    prof, _ = _profiler(tick=0.01)
    prof.begin_iteration()
    prof.mark("admission_pump")   # 1 tick
    prof.mark("dispatch")         # 1 tick
    prof.mark("dispatch")         # accumulates: 2 ticks total
    prof.end_iteration()          # residual tick -> "other"
    # FakeClock advances 0.01 per read: every span is an exact clock delta
    assert prof.totals["admission_pump"] == pytest.approx(0.01)
    assert prof.totals["dispatch"] == pytest.approx(0.02)
    assert prof.totals["other"] > 0.0
    assert prof.iterations == 1
    # the defining invariant: spans sum to the iteration wall EXACTLY
    assert sum(prof.totals.values()) == prof.wall_s


def test_profiler_spans_sum_to_wall_across_iterations():
    prof, _ = _profiler(tick=0.003)
    for i in range(7):
        prof.begin_iteration()
        for phase in PHASES[:1 + (i % 4)]:
            prof.mark(phase)
        prof.end_iteration()
    assert prof.iterations == 7
    assert sum(prof.totals.values()) == pytest.approx(prof.wall_s, abs=1e-12)


def test_profiler_quantiles_fakeclock_exact():
    prof, _ = _profiler(tick=0.02)
    for _ in range(4):
        prof.begin_iteration()
        prof.mark("burst")  # every sample is exactly one 0.02 tick
        prof.end_iteration()
    h = prof.hists["burst"]
    assert h.count == 4
    # deterministic quantiles: the answering bucket's representative, not an
    # interpolation — identical across reruns
    assert h.quantile(0.5) == h.representative(h._index(0.02))
    assert h.quantile(0.99) == h.representative(h._index(0.02))
    snap = prof.snapshot()
    assert snap["phases"]["burst"]["count"] == 4
    assert snap["phases"]["burst"]["p50"] == h.quantile(0.5)


def test_profiler_disabled_never_reads_clock():
    cfg = ServingPerfConfig(enabled=False)
    clock = FakeClock(tick=1.0)
    prof = StepPhaseProfiler(cfg, clock=clock)
    prof.begin_iteration()
    prof.mark("dispatch")
    prof.end_iteration()
    assert clock.calls == 0, "disabled observatory must not consume the clock"
    assert prof.iterations == 0 and prof.snapshot()["phases"] == {}


def test_profiler_marks_outside_iteration_ignored_without_clock_reads():
    prof, clock = _profiler(tick=0.01)
    prof.mark("expire")  # engine's _expire_live also runs outside _serve_loop
    assert clock.calls == 0 and prof.totals["expire"] == 0.0


def test_profiler_zero_tick_clock_still_fills_families():
    # a zero-tick FakeClock makes every span 0.0 — samples must still land
    # (underflow bucket) so phase families are non-empty in smoke checks
    prof, _ = _profiler(tick=0.0)
    prof.begin_iteration()
    prof.mark("flush")
    prof.end_iteration()
    assert prof.hists["flush"].count == 1
    assert prof.hists["flush"].quantile(0.5) == 0.0


def test_profiler_phase_budget_line_and_chrome_spans():
    tracer = _TracerStub()
    prof, _ = _profiler(tick=0.01, tracer=tracer, phase_budget_every=2)
    for _ in range(5):
        prof.begin_iteration()
        prof.mark("dispatch")
        prof.end_iteration()
    budgets = [f for n, f in tracer.events if n == "phase_budget"]
    assert len(budgets) == 2  # after iterations 2 and 4
    assert budgets[0]["iters"] == 2 and budgets[0]["wall_s"] > 0
    assert budgets[0]["top"] in PHASES
    # one Chrome span per marked phase per iteration, on the phase's track
    dispatch_spans = [s for s in tracer.spans if s[0] == "dispatch"]
    assert len(dispatch_spans) == 5
    assert all(s[3] == PHASES.index("dispatch") for s in dispatch_spans)


# -------------------------------------------------------- compile ledger unit
class _Counters:
    def __init__(self):
        self.compiles = 0


def test_ledger_classes_warm_detection_and_counter_parity():
    counters, tracer = _Counters(), _TracerStub()
    led = CompileLedger(counters, tracer=tracer)
    assert led.record("fwd", (1, 8, 4), prewarmed=True) == CLASS_PREWARMED
    assert led.record("fwd", (2, 8, 4)) == CLASS_COLD
    assert led.record("scatter", "sig-a") == CLASS_COLD
    # same (site, key) again: a warm recompile — the runtime event dslint's
    # recompile-risk rule predicts statically
    assert led.record("fwd", (2, 8, 4)) == CLASS_WARM
    assert led.by_site["fwd"] == {CLASS_PREWARMED: 1, CLASS_COLD: 1, CLASS_WARM: 1}
    assert led.warm_by_site == {"fwd": 1} and led.warm_total == 1
    assert counters.compiles == led.total == 4  # exactly one bump per record
    warm_events = [f for n, f in tracer.events if n == "warm_recompile"]
    # the program's name rides every record; where none is given it is the site
    assert warm_events == [{"site": "fwd", "key": "(2, 8, 4)", "program": "fwd",
                            "builds": 2}]
    snap = led.snapshot()
    assert snap["warm_total"] == 1 and snap["recent"][-1]["class"] == CLASS_WARM


def test_ledger_name_rides_the_event_and_never_decides_warm():
    tracer = _TracerStub()
    led = CompileLedger(tracer=tracer)
    assert led.record("fwd", (2, 8, 4), name="fwd_n2_t8_b4") == CLASS_COLD
    # the key alone decides warm: the same key under another name is a rebuild
    assert led.record("fwd", (2, 8, 4), name="something_else") == CLASS_WARM
    assert [e["name"] for e in led.events] == ["fwd_n2_t8_b4", "something_else"]
    assert tracer.events[-1][1]["program"] == "something_else"


def test_ledger_same_key_different_sites_not_warm():
    led = CompileLedger()
    assert led.record("pick", (4, 8)) == CLASS_COLD
    assert led.record("burst", (4, 8)) == CLASS_COLD  # different seam, not warm
    assert led.warm_total == 0


def test_ledger_compile_wall_accumulates():
    led = CompileLedger()
    led.record("fwd", (1, 1, 1), wall_s=0.25, prewarmed=True)
    led.record("fwd", (2, 1, 1), wall_s=0.5, prewarmed=True)
    assert led.compile_wall_s == pytest.approx(0.75)


# --------------------------------------------------------- engine integration
def _tiny_engine(**kw):
    cfg = llama.LlamaConfig.tiny(vocab=64, hidden=32, layers=1, heads=2,
                                 kv_heads=2, seq=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    defaults = dict(config={"dtype": "float32"},
                    num_blocks=32, block_size=8, max_blocks_per_seq=8,
                    token_budget=32, max_seqs_per_step=4)
    defaults.update(kw)
    return InferenceEngineV2(llama, cfg, params, **defaults)

_PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [10, 11, 12]]


def test_engine_phase_families_fill_and_sum_to_wall():
    eng = _tiny_engine(clock=FakeClock(tick=0.001),
                       config={"dtype": "float32",
                               "serving_perf": {"enabled": True}})
    eng.generate(_PROMPTS, max_new_tokens=6)
    prof = eng.phase_profiler
    assert prof.iterations > 0
    # the serve loop touches every family in a mixed prefill/decode run
    for phase in ("admission_pump", "scatter_upload", "dispatch",
                  "absorb_patch", "expire", "other"):
        assert prof.hists[phase].count > 0, f"phase {phase} never sampled"
    assert sum(prof.totals.values()) == pytest.approx(prof.wall_s, abs=1e-9)
    snap = eng.health()["perf"]
    assert snap["phases"]["dispatch"]["p50"] is not None
    assert snap["compile_ledger"]["warm_total"] == 0
    assert "roofline" not in snap  # removed with RooflineModel (ISSUE 24)


@pytest.mark.parametrize("observed_by", ["phase_profiler", "profiler_trace"])
@pytest.mark.parametrize("fastpath", [True, False])
def test_tokens_and_counters_byte_identical_observatory_on_vs_off(fastpath, observed_by,
                                                                  tmp_path):
    """The zero-perturbation acceptance: neither enabling the observatory nor
    an open jax.profiler trace (the serve loop's spans are always written)
    changes a token or the value of any ServeCounters field, the slot
    counters among them, on both the fastpath and the reference
    (fastpath-off) serve paths."""
    def run(observed):
        eng = _tiny_engine(
            clock=FakeClock(tick=0.001),
            config={"dtype": "float32",
                    "serving_fastpath": {"enabled": fastpath},
                    "serving_perf": {"enabled": observed
                                     and observed_by == "phase_profiler"}})
        traced = observed and observed_by == "profiler_trace"
        with _ProfilerTrace(tmp_path) if traced else contextlib.nullcontext():
            toks = eng.generate(_PROMPTS, max_new_tokens=6)
        return toks, eng.counters.snapshot()

    toks_off, counters_off = run(False)
    toks_on, counters_on = run(True)
    assert toks_on == toks_off
    assert set(counters_on) == set(type(_tiny_engine().counters).FIELDS)
    assert counters_on == counters_off
    assert 0 < counters_on["live_tokens"] <= counters_on["token_slots"]
    assert 0 < counters_on["live_blocks"] <= counters_on["table_slots"]


def test_engine_ledger_attributes_prewarm_and_traffic():
    eng = _tiny_engine()
    eng.generate(_PROMPTS, max_new_tokens=4)
    led = eng.ledger
    assert led.warm_total == 0, "steady-state serve must not recompile"
    fwd = led.by_site.get("fwd", {})
    assert fwd.get(CLASS_PREWARMED, 0) > 0, "prewarm buckets unattributed"
    # ledger is the single source of truth for the compiles counter
    assert eng.counters.compiles == led.total


def test_engine_forced_recompile_classified_warm():
    eng = _tiny_engine(config={"dtype": "float32",
                               "serving_tracing": {"enabled": True},
                               "serving_perf": {"enabled": True}})
    eng.generate(_PROMPTS, max_new_tokens=4)
    assert eng.ledger.warm_total == 0
    eng._fwd_cache.clear()          # forced: every cached program rebuilds
    eng.generate(_PROMPTS, max_new_tokens=4)
    # the cache held fwd buckets AND pick/burst programs: all rebuild warm
    assert eng.ledger.warm_total > 0
    assert eng.ledger.by_site["fwd"].get(CLASS_WARM, 0) > 0
    assert sum(eng.ledger.warm_by_site.values()) == eng.ledger.warm_total
    tail = [e for e in eng.tracer.recorder.tail() if e["event"] == "warm_recompile"]
    assert tail and "fwd" in {e["site"] for e in tail}


def test_metrics_families_for_observatory():
    eng = _tiny_engine(config={"dtype": "float32",
                               "serving_perf": {"enabled": True}})
    eng.generate(_PROMPTS, max_new_tokens=4)
    reg = MetricsRegistry()
    populate_from_engine(reg, eng)
    fams = parse_exposition(render(reg))  # strict-parse clean
    phase_hist = fams["dstpu_serving_phase_seconds"]
    phases_seen = {dict(labels)["phase"] for _, labels, _ in phase_hist["samples"]
                   if dict(labels).get("phase")}
    assert {"dispatch", "admission_pump"} <= phases_seen
    compile_rows = {tuple(sorted(dict(labels).items()))
                    for _, labels, _ in fams["dstpu_serving_compiles_total"]["samples"]}
    assert any(("site", "fwd") in row for row in compile_rows)
    recompiles = fams["dstpu_serving_recompiles_total"]["samples"]
    assert recompiles and all(v == 0.0 for _, _, v in recompiles)
    # the cost_analysis gauges are gone; what the device was asked to compute
    # is counted in slots, exported by the route the other counters take
    assert not any("roofline" in f or "hbm_bytes" in f or "flops_utilization" in f
                   for f in fams)
    snap = eng.counters.snapshot()
    for field in ("token_slots", "live_tokens", "table_slots", "live_blocks"):
        (_, _, value), = fams[f"dstpu_fastpath_{field}_total"]["samples"]
        assert value == snap[field] > 0
        assert eng.health()["fastpath"][field] == snap[field]


def test_chrome_trace_contains_phase_tracks(tmp_path):
    trace_path = str(tmp_path / "phases.trace.json")
    eng = _tiny_engine(clock=FakeClock(tick=0.001),
                       config={"dtype": "float32",
                               "serving_tracing": {"enabled": True,
                                                   "chrome_trace_path": trace_path},
                               "serving_perf": {"enabled": True}})
    eng.generate(_PROMPTS, max_new_tokens=4)
    events = json.load(open(trace_path))
    if isinstance(events, dict):
        events = events["traceEvents"]
    phase_events = [e for e in events if e.get("cat") == "phase"]
    assert phase_events, "no phase track events in the Chrome trace"
    assert {e["name"] for e in phase_events} <= set(PHASES)
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in phase_events)


def _patch_trace_stubs(collector, monkeypatch):
    """Replace the jax.profiler start/stop with call-recording stubs that
    keep the collector's ``_tracing`` bookkeeping honest."""
    calls = []

    def start():
        calls.append("start")
        collector._tracing = True
        return True

    def stop():
        calls.append("stop")
        collector._tracing = False

    monkeypatch.setattr(collector, "start_trace", start)
    monkeypatch.setattr(collector, "stop_trace", stop)
    return calls


def test_serve_profiler_window_one_per_generate(monkeypatch):
    """Satellite: profile_serve_iteration_start/stop drive one jax.profiler
    window per generate(), [start, stop) on the per-generate iteration index."""
    collector = TelemetryCollector(config=TelemetryConfig(
        enabled=True,
        profile_serve_iteration_start=1, profile_serve_iteration_stop=3))
    calls = _patch_trace_stubs(collector, monkeypatch)
    eng = _tiny_engine(telemetry=collector)
    eng.generate(_PROMPTS, max_new_tokens=6)
    assert calls == ["start", "stop"], calls
    eng.generate(_PROMPTS, max_new_tokens=6)  # window re-arms per generate()
    assert calls == ["start", "stop"] * 2, calls


def test_serve_profiler_window_closed_at_generate_end(monkeypatch):
    # stop index beyond the loop's iteration count: serve_profile_end must
    # close the window rather than leak the trace across generate() calls
    collector = TelemetryCollector(config=TelemetryConfig(
        enabled=True,
        profile_serve_iteration_start=0, profile_serve_iteration_stop=10_000))
    calls = _patch_trace_stubs(collector, monkeypatch)
    eng = _tiny_engine(telemetry=collector)
    eng.generate(_PROMPTS, max_new_tokens=4)
    assert calls == ["start", "stop"], calls


@pytest.mark.parametrize("knob", ["hbm_gbps_spec", "peak_flops_per_chip",
                                  "capture_cost_analysis"])
def test_config_names_a_removed_roofline_knob_as_unknown(knob):
    # the three knobs went with RooflineModel: a config that still sets one is
    # told so by name, as for any unknown field, and not silently ignored
    with pytest.raises(ValueError, match=f"unknown config field '{knob}'"):
        _tiny_engine(config={"dtype": "float32", "serving_perf": {knob: 1.0}})


def test_config_rejects_stop_before_start():
    with pytest.raises(Exception):
        TelemetryConfig(profile_serve_iteration_start=5,
                        profile_serve_iteration_stop=3)
