"""Unified telemetry subsystem.

One collector joins the observability islands the reference spreads over
``wall_clock_breakdown`` timers, ``see_memory_usage``, the comms logger, the
FLOPs profiler and the monitor writers (deepspeed/runtime/engine.py
``_report_progress`` + monitor/monitor.py): per train step it assembles ONE
structured record — loss, grad-norm, lr, step wall-time, samples/sec,
tokens/sec, model-FLOPs-utilization, HBM high-water mark — and fans it out to

- ``MonitorMaster`` (TensorBoard / W&B / CSV writers, rank-0 only), and
- a rank-0 JSONL sink (``TelemetryConfig.jsonl_path``), one json object per
  line, machine-readable for regression tracking (the engine's report about
  itself).

It also owns config-driven ``jax.profiler`` capture windows
(``profile_step_start``/``profile_step_stop`` → ``start_trace``/``stop_trace``
into a TensorBoard-readable directory) and hands out ``StepTraceAnnotation`` /
``TraceAnnotation`` context managers so the engine's step, batch-prep and
checkpoint IO show up as named ranges in the trace.

MFU derivation: ``flops_per_step`` comes ONCE from the XLA cost analysis of the
compiled train step (FlopsProfiler), divided by the measured wall-time and the
per-chip peak FLOPs × chip count.  Peak FLOPs resolve from
``TelemetryConfig.peak_flops_per_chip`` or from the device kind
(accelerator/device_peaks.py); a device that is not in the table (CPU test
backend) yields ``mfu: null`` unless the config pins a peak.
"""

import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

from ..accelerator.device_peaks import DEVICE_PEAKS
from ..utils.logging import logger
from ..utils.memory import device_memory_stats

Event = Tuple[str, float, int]

_FLOPS_UNSET = object()  # distinguishes "not yet profiled" from "profiling failed"


def detect_peak_flops_per_chip() -> Optional[float]:
    """Per-chip bf16 peak of the attached device, from the one table keyed by
    ``device_kind`` (accelerator/device_peaks.py); None when the device is not
    in it (e.g. the CPU test backend), so MFU stays null rather than being
    computed against another chip's peak."""
    import jax
    peaks = DEVICE_PEAKS.get(jax.devices()[0].device_kind)
    return peaks.bf16_flops if peaks is not None else None


class TelemetryCollector:
    """Assembles per-step records and fans them out (monitor + JSONL).

    Disabled collectors (``config.enabled`` false and no ``jsonl_path``) keep
    every method a cheap no-op, so call sites never branch.
    """

    def __init__(self, config=None, monitor=None, batch_size: int = 1,
                 n_chips: Optional[int] = None):
        from ..runtime.config import TelemetryConfig
        self.config = config if config is not None else TelemetryConfig()
        self.monitor = monitor
        self.batch_size = max(int(batch_size), 1)
        self.enabled = bool(self.config.enabled)
        try:
            import jax
            self._is_rank0 = jax.process_index() == 0
            self.n_chips = int(n_chips) if n_chips else jax.device_count()
        except Exception:
            self._is_rank0 = True
            self.n_chips = int(n_chips) if n_chips else 1
        self.peak_flops_per_chip = (self.config.peak_flops_per_chip
                                    if self.config.peak_flops_per_chip is not None
                                    else detect_peak_flops_per_chip())
        self._flops_per_step: Any = _FLOPS_UNSET
        self._jsonl_fh = None
        self._unflushed = 0
        self._tracing = False
        self._profile_done = False  # the capture window fires at most once
        self.records_written = 0
        # requests/sec rate tracking for serving gauges (name -> (t, count))
        self._rates: Dict[str, Tuple[float, float]] = {}
        # host-side caches for the pull-based ops plane (monitor/metrics.py
        # populate_from_telemetry): the newest train-step record, the newest
        # gauges per prefix, and lifetime resilience-event counts — reading
        # them re-reads values this collector already assembled, so an ops
        # refresh can never trigger a device sync
        self.last_train_record: Optional[Dict[str, Any]] = None
        self.last_gauges: Dict[str, Dict[str, Any]] = {}
        self.resilience_counts: Dict[str, int] = {}

    # ------------------------------------------------------------- flops / mfu
    def wants_flops(self) -> bool:
        """True while the one-time train-step cost analysis is still pending."""
        return self.enabled and self._flops_per_step is _FLOPS_UNSET

    def set_flops_per_step(self, flops: Optional[float]) -> None:
        self._flops_per_step = float(flops) if flops else None

    @property
    def flops_per_step(self) -> Optional[float]:
        return None if self._flops_per_step is _FLOPS_UNSET else self._flops_per_step

    def _mfu(self, step_time_s: Optional[float]) -> Optional[float]:
        flops = self.flops_per_step
        if not flops or not step_time_s or not self.peak_flops_per_chip:
            return None
        return flops / step_time_s / (self.peak_flops_per_chip * self.n_chips)

    # ----------------------------------------------------------------- records
    def record_train_step(self, *, step: int, samples: int, loss: Optional[float] = None,
                          grad_norm: Optional[float] = None, lr: Optional[float] = None,
                          step_time_s: Optional[float] = None, tokens: Optional[int] = None,
                          extra: Optional[Dict[str, Any]] = None) -> Optional[Dict[str, Any]]:
        """One structured record per optimizer step; returns the record (None
        when disabled).  ``tokens`` is the global token count this step; when
        the batch has no sequence dim it defaults to one token per sample so
        tokens/sec degrades to samples/sec instead of going null."""
        if not self.enabled:
            return None
        tokens = int(tokens) if tokens else self.batch_size
        step_time_ms = step_time_s * 1e3 if step_time_s else None
        samples_per_sec = self.batch_size / step_time_s if step_time_s else None
        tokens_per_sec = tokens / step_time_s if step_time_s else None
        flops = self.flops_per_step
        record: Dict[str, Any] = {
            "kind": "train_step",
            "step": int(step),
            "samples": int(samples),
            "loss": loss,
            "grad_norm": grad_norm,
            "lr": lr,
            "step_time_ms": step_time_ms,
            "samples_per_sec": samples_per_sec,
            "tokens_per_sec": tokens_per_sec,
            "flops_per_step": flops,
            "tflops_per_sec": (flops / step_time_s / 1e12 if flops and step_time_s else None),
            "mfu": self._mfu(step_time_s),
            "hbm": device_memory_stats(),
            "timestamp": time.time(),
        }
        if extra:
            record.update(extra)
        self.last_train_record = record
        self._write_jsonl(record)
        return record

    def record_gauges(self, gauges: Dict[str, Any], step: int,
                      prefix: str = "Inference",
                      timestamp: Optional[float] = None) -> Optional[Dict[str, Any]]:
        """Point-in-time gauges (scheduler/serving state) → monitor events and
        a ``kind: gauges`` JSONL record.  ``timestamp`` lets a caller on an
        injectable clock (the v2 serving engine under a FakeClock) stamp the
        record deterministically; None keeps the wall-clock default."""
        if not self.enabled:
            return None
        self.record_events([(f"{prefix}/{k}", float(v), int(step))
                            for k, v in gauges.items() if v is not None])
        record = {"kind": "gauges", "prefix": prefix, "step": int(step),
                  "timestamp": time.time() if timestamp is None else float(timestamp),
                  **gauges}
        # cache the GAUGES only, not the whole record — the ops adapter
        # exports every numeric cached key as a metric family, and the
        # record's step/timestamp bookkeeping must not become one
        self.last_gauges[prefix] = dict(gauges)
        self._write_jsonl(record)
        return record

    def record_resilience(self, event: str, *, step: int = 0, samples: int = 0,
                          **fields) -> Optional[Dict[str, Any]]:
        """Fault-path happenings (save retries, fallback loads, watchdog trips,
        preemption saves) → a ``kind: resilience`` JSONL record plus monitor
        events for the numeric fields, so recoveries are visible in the same
        stream as the steps they interrupt."""
        if not self.enabled:
            return None
        record = {"kind": "resilience", "event": event, "step": int(step),
                  "timestamp": time.time(), **fields}
        self.resilience_counts[event] = self.resilience_counts.get(event, 0) + 1
        self._write_jsonl(record)
        self.record_events([(f"Resilience/{event}/{k}", float(v), int(samples))
                            for k, v in fields.items()
                            if isinstance(v, (int, float)) and not isinstance(v, bool)])
        return record

    def record_trace(self, trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """One completed request-lifecycle trace (monitor/tracing.py
        RequestTracer) → a ``kind: trace`` JSONL record: uid, terminal
        status, span chain, SLO marks (ttft_s/e2e_s/queue_wait_s)."""
        if not self.enabled:
            return None
        record = {"kind": "trace", "timestamp": time.time(), **trace}
        self._write_jsonl(record)
        return record

    def record_events(self, events: List[Event]) -> None:
        """Fan events out to MonitorMaster (rank-0; no JSONL — events are the
        monitor-native shape, records are the JSONL-native shape)."""
        if not self.enabled or not events:
            return
        if self.monitor is not None and self._is_rank0:
            self.monitor.write_events(list(events))

    def rate(self, name: str, count: float) -> Optional[float]:
        """Per-second rate of a monotonically increasing counter between
        successive calls (None on the first observation of ``name``)."""
        now = time.perf_counter()
        prev = self._rates.get(name)
        self._rates[name] = (now, count)
        if prev is None or now <= prev[0]:
            return None
        return (count - prev[1]) / (now - prev[0])

    # ------------------------------------------------------------- JSONL sink
    def _write_jsonl(self, record: Dict[str, Any]) -> None:
        path = self.config.jsonl_path
        if path is None or not self._is_rank0:
            return
        if self._jsonl_fh is None:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            self._jsonl_fh = open(path, "a")
        self._jsonl_fh.write(json.dumps(record) + "\n")
        self.records_written += 1
        # buffered flush policy (ISSUE 6 satellite): the default of 1 keeps
        # the every-record durability tests rely on; high-rate trace streams
        # raise jsonl_flush_every so flushes amortize off the serve loop
        self._unflushed += 1
        if self._unflushed >= self.config.jsonl_flush_every:
            self._jsonl_fh.flush()
            self._unflushed = 0

    def flush_jsonl(self) -> None:
        """Force out any buffered JSONL records (close() does this too)."""
        if self._jsonl_fh is not None:
            self._jsonl_fh.flush()
        self._unflushed = 0

    # ------------------------------------------------- jax.profiler windows
    @property
    def tracing(self) -> bool:
        return self._tracing

    def profile_step_boundary(self, step: int) -> None:
        """Drive the configured capture window; call at the top of each train
        step with the CURRENT global step.  The window is [start, stop):
        start_trace fires entering any step inside the window (>= start, so a
        checkpoint-resumed run landing mid-window still captures), stop_trace
        entering ``profile_step_stop`` (or at close()); one window per run."""
        if not self.enabled:
            return
        start, stop = self.config.profile_step_start, self.config.profile_step_stop
        if self._tracing and stop >= 0 and step >= stop:
            self.stop_trace()
            self._profile_done = True
        if (not self._tracing and not self._profile_done and start >= 0
                and step >= start and (stop < 0 or step < stop)):
            self.start_trace()

    def serve_profile_begin(self) -> None:
        """Arm the serve-iteration capture window for one ``generate()`` call
        (ISSUE 16 satellite): the per-generate done-flag resets so every
        generate() can capture its own [start, stop) iteration window."""
        self._serve_profile_done = False

    def profile_serve_boundary(self, iteration: int) -> None:
        """Drive the serve-loop capture window; call at the top of each serve
        iteration with the CURRENT per-generate iteration index.  Same
        [start, stop) semantics as :meth:`profile_step_boundary`, but keyed on
        ``profile_serve_iteration_start/stop`` and re-armed per generate()."""
        if not self.enabled:
            return
        start = self.config.profile_serve_iteration_start
        stop = self.config.profile_serve_iteration_stop
        done = getattr(self, "_serve_profile_done", False)
        if self._tracing and stop >= 0 and iteration >= stop:
            self.stop_trace()
            self._serve_profile_done = True
        if (not self._tracing and not done and start >= 0
                and iteration >= start and (stop < 0 or iteration < stop)):
            self.start_trace()

    def serve_profile_end(self) -> None:
        """Close any serve window still open when generate() returns — one
        window per generate(), never a trace leaking across calls."""
        if (self.enabled and self._tracing
                and self.config.profile_serve_iteration_start >= 0):
            self.stop_trace()
            self._serve_profile_done = True

    def start_trace(self) -> bool:
        if self._tracing:
            return False
        try:
            import jax
            os.makedirs(self.config.profile_dir, exist_ok=True)
            jax.profiler.start_trace(self.config.profile_dir)
            self._tracing = True
            logger.info(f"telemetry: jax.profiler trace started -> {self.config.profile_dir}")
        except Exception as e:  # a failed trace must never kill training
            logger.warning(f"telemetry: start_trace failed: {e}")
        return self._tracing

    def stop_trace(self) -> None:
        if not self._tracing:
            return
        try:
            import jax
            jax.profiler.stop_trace()
            logger.info(f"telemetry: jax.profiler trace stopped ({self.config.profile_dir})")
        except Exception as e:
            logger.warning(f"telemetry: stop_trace failed: {e}")
        finally:
            self._tracing = False

    def step_annotation(self, step: int):
        """StepTraceAnnotation for the train step — the marker TensorBoard's
        profile tooling groups per-step stats by.  Opened whether telemetry
        is enabled or not (as :meth:`annotation` is): whoever runs the
        profiler gets the host spans, and enabling telemetry to have them
        would also buy the per-step loss sync, changing what is measured.
        Outside a profiler session a TraceMe is a flag check."""
        import jax
        return jax.profiler.StepTraceAnnotation("train_step", step_num=int(step))

    def annotation(self, name: str):
        """Named TraceAnnotation (batch-prep, checkpoint IO, eval, ...)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    # ---------------------------------------------------------------- teardown
    def close(self) -> None:
        self.stop_trace()
        if self._jsonl_fh is not None:
            self._jsonl_fh.close()  # close() flushes any buffered records
            self._jsonl_fh = None
        self._unflushed = 0

    def __del__(self):
        try:
            self.close()
        except Exception:  # dslint: disable=silent-except  # interpreter-shutdown teardown: logging/profiler may already be torn down, raising from __del__ only prints noise
            pass
