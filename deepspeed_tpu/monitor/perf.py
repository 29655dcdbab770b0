"""Serving performance observatory (ISSUE 16).

Two instruments that let the v2 serving stack attribute where a serve
iteration's wall-clock went and where every XLA compile came from — the
serving twin of the training-side ``wall_clock_breakdown`` story (PARITY row
43).  (A third, a roofline from ``compiled.cost_analysis()`` over host wall
time, was removed in ISSUE 24: it saw no Pallas kernel and no fused burst.
What the device computes is counted in ``ServeCounters`` slots and read from
the device trace by program name — ``chipbench/``.)

- :class:`StepPhaseProfiler` — mark-based per-iteration phase attribution for
  the serve loop.  The engine calls ``begin_iteration()`` at the top of each
  loop pass and ``mark(phase)`` after each phase's work; the profiler charges
  the time since the previous mark to that phase and sends whatever is left at
  ``end_iteration()`` to the ``other`` phase, so per-iteration phase spans sum
  to the iteration wall time *exactly* (FakeClock tests assert equality, not
  tolerance).  Per-phase :class:`~.tracing.StreamingHistogram` s give
  deterministic quantiles; every phase marked in an iteration records one
  sample (a 0.0 span lands in the underflow bucket, so families fill even
  under a zero-tick FakeClock).
- :class:`CompileLedger` — single source of truth for ``ServeCounters.compiles``.
  Every compile seam (engine fwd buckets, AOT prewarm, pick/burst programs,
  cow-copy, fastpath scatter/feed) records ``(site, key)`` here; the ledger
  classifies each as ``prewarmed`` / ``cold`` / first-seen vs ``warm``
  (a key recompiled after being seen — the runtime twin of dslint's
  ``recompile-risk`` rule) and bumps the counter exactly once per record, so
  counter values are unchanged from the pre-ledger ``+= 1`` sites.  Each
  record also carries the program's ``name``: the ``__name__`` the program
  was jitted under, so the device trace's program line (``jit_<name>``), the
  ledger event and a ``warm_recompile`` flight-recorder line agree.  A seam
  also hands over the executable (or a way to compile it again), which
  ``monitor/program_scopes.py`` keeps by that name: which scope each operation
  of a compiled program belongs to, read when asked and never on a step.

Zero-device-sync contract (same as heartbeat/metrics/exposition/ops_server,
enforced by the dslint whole-file scan): nothing here imports jax or numpy,
and every timestamp is a host float handed in by the engine's injectable
clock.  The profiler reads that clock ONLY while ``enabled`` — observatory
off adds zero clock reads, so FakeClock call counts (and therefore tokens and
``ServeCounters``) are byte-identical with the observatory on or off.
"""

import collections
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import program_scopes
from .tracing import StreamingHistogram

# serve-loop phases, in rough per-iteration order; ``other`` absorbs the
# residual (heartbeat stamp, ops refresh, watchdog, journal flush) so the
# per-iteration spans always sum to the full iteration wall time.  The ONE
# list of phase names: ``StepPhaseProfiler.mark`` takes these, and the serve
# loop's ``jax.profiler`` spans (``engine_v2._phase_annotation``) are named
# by them too (``<phase>`` or, nested inside one, ``<phase>.<part>``)
PHASES = ("admission_pump", "scatter_upload", "dispatch", "absorb_patch",
          "burst", "flush", "expire", "other")

# compile classes a ledger record can carry
CLASS_PREWARMED = "prewarmed"  # built ahead of traffic by _prewarm
CLASS_COLD = "cold"            # first build of this (site, key) under traffic
CLASS_WARM = "warm"            # rebuilt after already being seen: a recompile


class StepPhaseProfiler:
    """Mark-based phase attribution for the serve loop.

    ``begin_iteration()`` opens an iteration; ``mark(phase)`` charges the time
    since the previous mark (or the iteration start) to ``phase``;
    ``end_iteration()`` charges the residual to ``other``, folds the
    per-iteration spans into per-phase histograms and lifetime totals, and
    optionally emits an every-N-iterations phase-budget line to the flight
    recorder.  Marks outside an open iteration are ignored (the engine's
    public ``step()``/``decode_burst()`` run outside the serve loop too).
    """

    def __init__(self, config=None, *, clock: Optional[Callable[[], float]] = None,
                 tracer=None):
        cfg = config
        self.enabled = bool(getattr(cfg, "enabled", False))
        self.budget_every = int(getattr(cfg, "phase_budget_every", 50))
        bpd = int(getattr(cfg, "histogram_buckets_per_decade", 6))
        min_s = float(getattr(cfg, "histogram_min_s", 1e-7))
        self._clock = clock if clock is not None else (lambda: 0.0)
        self._tracer = tracer
        self.hists: Dict[str, StreamingHistogram] = {
            p: StreamingHistogram(buckets_per_decade=bpd, min_value=min_s)
            for p in PHASES}
        self.totals: Dict[str, float] = {p: 0.0 for p in PHASES}
        self.iterations = 0
        self.wall_s = 0.0
        self._active = False
        self._t_iter0 = 0.0
        self._t_mark = 0.0
        self._spans: Dict[str, float] = {}
        # window accumulator for the flight-recorder phase-budget line
        self._win_spans: Dict[str, float] = {p: 0.0 for p in PHASES}
        self._win_iters = 0

    def begin_iteration(self) -> None:
        if not self.enabled:
            return
        now = self._clock()
        self._active = True
        self._t_iter0 = now
        self._t_mark = now
        self._spans = {}

    def mark(self, phase: str) -> None:
        """Charge time since the previous mark to ``phase``."""
        if not self.enabled or not self._active:
            return
        now = self._clock()
        span = now - self._t_mark
        self._t_mark = now
        self._spans[phase] = self._spans.get(phase, 0.0) + span

    def end_iteration(self) -> None:
        if not self.enabled or not self._active:
            return
        now = self._clock()
        self._spans["other"] = self._spans.get("other", 0.0) + (now - self._t_mark)
        self._active = False
        wall = now - self._t_iter0
        self.iterations += 1
        self.wall_s += wall
        self._win_iters += 1
        start = self._t_iter0
        for phase, span in self._spans.items():
            self.hists[phase].add(span)
            self.totals[phase] += span
            self._win_spans[phase] += span
            if self._tracer is not None:
                self._tracer.phase_span(phase, start, span,
                                        track=PHASES.index(phase))
            start += span
        if self._win_iters >= self.budget_every:
            self._emit_budget()

    def _emit_budget(self) -> None:
        """Flight-recorder line: where the last window's wall time went."""
        if self._tracer is not None:
            total = sum(self._win_spans.values()) or 1.0
            fields = {p: round(self._win_spans[p], 6) for p in PHASES
                      if self._win_spans[p] > 0.0}
            top = max(self._win_spans, key=lambda p: self._win_spans[p])
            self._tracer.event("phase_budget", iters=self._win_iters,
                               wall_s=round(total, 6), top=top, **fields)
        self._win_spans = {p: 0.0 for p in PHASES}
        self._win_iters = 0

    def histograms(self) -> Dict[str, StreamingHistogram]:
        """Per-phase histograms that have seen at least one sample."""
        return {p: h for p, h in self.hists.items() if h.count}

    def snapshot(self) -> Dict[str, Any]:
        phases = {p: dict(self.hists[p].snapshot(),
                          total_s=round(self.totals[p], 9))
                  for p in PHASES if self.hists[p].count}
        return {"enabled": self.enabled, "iterations": self.iterations,
                "wall_s": round(self.wall_s, 9), "phases": phases}

    def reset(self) -> None:
        for h in self.hists.values():
            h.reset()
        self.totals = {p: 0.0 for p in PHASES}
        self.iterations = 0
        self.wall_s = 0.0
        self._active = False
        self._win_spans = {p: 0.0 for p in PHASES}
        self._win_iters = 0


class CompileLedger:
    """Attributed record of every XLA compile the serving engine triggers.

    Always on (it adds no clock reads and no device work): each compile seam
    calls :meth:`record` instead of bumping ``ServeCounters.compiles``
    directly, and the ledger bumps the counter exactly once per record — the
    counter's values are unchanged, but every unit now carries a jit-site
    name, a bucket key, a class, and (for AOT prewarm, the only seam where
    the compile happens synchronously on the host) a wall time.  A ``warm``
    record — a key rebuilt after already being seen at its site — is the
    runtime event dslint's ``recompile-risk`` rule predicts statically; it
    lands in the flight recorder and the per-site warm counters behind
    ``serving_recompiles_total{site=...}``.

    ``compile_wall_s`` is the AOT seams' stopwatch and nothing else: trace +
    lower + compile of the programs built ahead, 0 for a lazily jitted one.
    What EVERY program cost, stage by stage, is the set-up account's
    (``monitor/compile_events.py``, handed in as ``events=``; this module still
    imports no jax): JAX's own trace / lower / load seconds under the program
    names this ledger stores.  :meth:`snapshot` joins the two by name, so
    ``health()["perf"]["compile_ledger"]`` answers for a replica that takes a
    minute to come up: which programs (``slowest``), which stage (``trace_s``,
    ``lower_s``, ``load_s``), cache or no cache (``cache_hits``: loads the
    persistent cache answered; ``cache_misses``: the other loads, each an XLA
    compile, whether the cache lacked the entry, takes no such program or has
    no directory); ``serving_compile_seconds_total{stage=...}`` and
    ``serving_compile_cache_{hits,misses}_total`` export the same totals.
    The join is by name alone and the account is the process's: two engines
    in one process that build programs of equal names (two replicas of one
    model) each read the seconds of both.
    """

    def __init__(self, counters=None, *, tracer=None, events=None):
        self._counters = counters
        self._tracer = tracer
        self._events = events  # the set-up account (by_program()), or None
        self._seen: Dict[Tuple[str, str], int] = {}
        self._programs: Dict[str, Tuple[str, str]] = {}  # name -> (site, first class)
        self.by_site: Dict[str, Dict[str, int]] = {}
        self.warm_by_site: Dict[str, int] = {}
        self.compile_wall_s = 0.0
        self.total = 0
        self.events: collections.deque = collections.deque(maxlen=256)

    @staticmethod
    def _key_str(key: Any) -> str:
        return key if isinstance(key, str) else repr(key)

    def record(self, site: str, key: Any, *, wall_s: float = 0.0,
               prewarmed: bool = False, name: Optional[str] = None,
               program: Any = None) -> str:
        """Record one compile at ``site`` for bucket ``key``; returns class.
        ``name`` is the program's jit name (the trace module is
        ``jit_<name>``); ``key`` alone decides ``warm``.  ``program``: see
        :meth:`built`; a lazily jitted program hands it in at its first call."""
        name = site if name is None else name
        if program is not None:
            self.built(name, program)
        k = (site, self._key_str(key))
        seen = self._seen.get(k, 0)
        self._seen[k] = seen + 1
        if seen:
            cls = CLASS_WARM
            self.warm_by_site[site] = self.warm_by_site.get(site, 0) + 1
            if self._tracer is not None:
                # the seconds every build of this program has cost so far (a
                # lazily jitted one compiles after this line: its newest
                # build is not in them yet)
                self._tracer.event("warm_recompile", site=site, key=k[1],
                                   program=name, builds=seen + 1,
                                   **self._stage_seconds([name]))
        else:
            cls = CLASS_PREWARMED if prewarmed else CLASS_COLD
        per_site = self.by_site.setdefault(site, {})
        per_site[cls] = per_site.get(cls, 0) + 1
        self._programs.setdefault(name, (site, cls))
        self.compile_wall_s += float(wall_s)
        self.total += 1
        self.events.append({"site": site, "key": k[1], "name": name,
                            "class": cls, "wall_s": round(float(wall_s), 6)})
        if self._counters is not None:
            self._counters.compiles += 1
        return cls

    def built(self, name: str, program: Any) -> None:
        """The executable behind a recorded name, for ``monitor/program_scopes.py``
        (which scope each of its operations belongs to): a held ``Compiled``,
        or a thunk that compiles the program again at the shapes it is
        dispatched at.  A dictionary store: nothing is read or compiled until
        :meth:`program_scopes` is asked.  This ledger is the registry's owner,
        so the programs leave the registry with the engine that holds it."""
        program_scopes.register(self, name, program)

    def program_scopes(self, name: Optional[str] = None) -> Dict[str, Dict[str, Tuple[str, ...]]]:
        """``{program: {instruction: scopes}}`` of this ledger's programs
        (``name``: that one alone): reads and parses their optimized text, once
        a program.  Never on a serve iteration."""
        return program_scopes.tables(None if name is None else [name], owner=self)

    @property
    def warm_total(self) -> int:
        return sum(self.warm_by_site.values())

    def _stage_seconds(self, names, by_program=None) -> Dict[str, Any]:
        """The account's totals over ``names``; nothing without an account."""
        if self._events is None:
            return {}
        by_program = self._events.by_program() if by_program is None else by_program
        found = [by_program[n] for n in names if n in by_program]
        return {key: round(sum(p[key] for p in found), 6) if key.endswith("_s")
                else sum(p[key] for p in found)
                for key in ("trace_s", "lower_s", "load_s", "cache_hits", "cache_misses")}

    def stage_totals(self) -> Dict[str, Any]:
        """``trace_s``, ``lower_s``, ``load_s``, ``cache_hits`` and
        ``cache_misses`` of the programs this ledger recorded (the exporter's
        and ``chip_smoke.py``'s numbers); empty without an account."""
        return self._stage_seconds(self._programs)

    def snapshot(self) -> Dict[str, Any]:
        snap = {"total": self.total,
                "warm_total": self.warm_total,
                "compile_wall_s": round(self.compile_wall_s, 6),
                "by_site": {s: dict(c) for s, c in sorted(self.by_site.items())},
                "recent": list(self.events)[-8:]}
        if self._events is not None:
            by_program = self._events.by_program()
            snap.update(self._stage_seconds(self._programs, by_program))
            cost = {n: by_program[n]["trace_s"] + by_program[n]["lower_s"] + by_program[n]["load_s"]
                    for n in self._programs if n in by_program}
            snap["slowest"] = [
                {"name": n, "site": self._programs[n][0], "class": self._programs[n][1],
                 "seconds": round(cost[n], 6), "inner_traces": by_program[n]["inner_traces"]}
                for n in sorted(cost, key=lambda n: (-cost[n], n))[:8]]
        return snap
