"""The program's set-up account cut at the window's start: the one place the
five ``setup.*`` readers take their numbers from.

The account (``deepspeed_tpu/monitor/compile_events.py``) holds a row for
every stage of every program JAX built in this process: ``trace``, ``lower``
and ``load`` (an XLA compile where the persistent cache missed, a read where it
hit) with start and end on ``time.perf_counter()``, and an ``engine_init`` row
around each engine's construction.  These are the first readers that import
the program: set-up precedes every object ``run`` holds, so there is nothing
on ``run`` to read it from.  A program without the account (the parent of the
PR that brought it) is nothing to read.

**The cut.**  Set-up ends where the window starts, ``T_START + run.setup_s`` on
the same clock.  ``run`` carries the duration and not the origin: it is taken
from ``run.t_start`` where a later harness puts one there, else from the
module that measured ``setup_s`` from it (``chipbench/run.py`` as ``__main__``,
or imported by a test), else there is nothing to read.  What the harness
builds after the cut (the float32 reference, the weights drawn again) is left
out; what ended inside the window is counted apart (``in_window``) and has to
be 0 where ``serve.compiles_in_window`` is.
"""

import sys

STAGES = ("trace", "lower", "load")


def origin(run):
    """``perf_counter`` at the process's start, as ``setup_s`` was measured."""
    found = getattr(run, "t_start", None)
    for module in ("__main__", "chipbench.run"):
        if found is None:
            found = getattr(sys.modules.get(module), "T_START", None)
    return found


def covered(spans) -> float:
    """Seconds the union of ``(start, end)`` spans covers."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(spans):
        total += max(0.0, end - max(start, reach))
        reach = max(reach, end)
    return total


def cut(run):
    """The account up to the window's start, or None with nothing to read:
    ``totals`` and ``programs`` (seconds and counts by stage, whole and by
    program name), ``engine_init_s`` (the engines' construction less JAX's
    stages inside it), ``other_s`` (``setup_s`` less everything accounted),
    ``in_window`` (loads that ended inside the window)."""
    try:
        from deepspeed_tpu.monitor import compile_events
    except ImportError:
        return None
    t_start = origin(run)
    if t_start is None:
        return None
    account = compile_events.ACCOUNT
    end = t_start + run.setup_s
    later = account.rows(until=end + run.window_s)
    rows = [r for r in later if r.end <= end]
    inits = [r for r in rows if r.stage == "engine_init"]
    inside = sum(r.end - r.start for r in rows if r.stage in STAGES and any(
        i.thread == r.thread and i.start <= r.start and r.end <= i.end for i in inits))
    totals = account.totals(until=end)
    init_s = covered((r.start, r.end) for r in inits) - inside
    return {"totals": totals, "programs": account.by_program(until=end),
            "engine_init_s": init_s,
            "other_s": run.setup_s - init_s - sum(totals[s + "_s"] for s in STAGES),
            "in_window": sum(r.stage == "load" for r in later[len(rows):])}


def cut_on_chip(run):
    """:func:`cut` for the four readers of seconds; None off the chip
    (``run.trace`` is the device trace's reduction, None in a rehearsal): a
    rehearsal's seconds are the CPU's and a time comes only from a chip run,
    and the harness's own test holds a traced rehearsal to the counters alone
    (``test_rehearsal_walks_the_cell_and_is_never_a_result``).  The count,
    ``setup.programs``, reads :func:`cut` there too."""
    return cut(run) if run.trace is not None else None


def seconds(run, stage, *also):
    """``(seconds, note)`` of one stage, the three programs with most named
    and the totals under ``also`` beside them; None off the chip."""
    found = cut_on_chip(run)
    if found is None:
        return None
    key, totals = stage + "_s", found["totals"]
    most = sorted(found["programs"].items(), key=lambda item: (-item[1][key], item[0]))[:3]
    note = {"rows": totals[stage + "s"],
            "most": ",".join(f"{name}:{program[key]:.3f}" for name, program in most)}
    note.update((k, round(totals[k], 4)) for k in also)
    return totals[key], note
