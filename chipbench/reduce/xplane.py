"""From a profiler trace to busy and idle time, time by operation, idle gaps
named by what the host was doing, and collective time.

The arithmetic works on plain lists of ``(name, start_ns, duration_ns)`` so
that it can be checked on a hand-built list; ``load`` turns the profiler's
``.xplane.pb`` into those lists with nothing but JAX."""

import glob
import os
import re

COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
                        r"|collective-broadcast|async-collective", re.I)
# operations that only hold other operations: their own time is their children's
CONTAINER = re.compile(r"^%?(while|conditional|call)([.\s(]|$)", re.I)


def union(intervals):
    """Sorted, merged ``(start, end)`` of possibly overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(intervals, holes):
    """Parts of merged ``intervals`` not covered by merged ``holes``."""
    out = []
    for s, e in intervals:
        for hs, he in holes:
            if he <= s or hs >= e:
                continue
            if hs > s:
                out.append((s, hs))
            s = max(s, he)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def self_times(events):
    """``[(name, self_ns)]``: each event's duration less what the events nested
    inside it cover, for one line on which events nest and do not cross."""
    out, stack = [], []  # stack of [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    out.extend((name, own) for name, _, own in stack)
    return out


def time_by_name(events, top=None):
    """Self time summed by name, longest first: ``[(name, ns)]``."""
    sums = {}
    for name, own in self_times(events):
        sums[name] = sums.get(name, 0) + own
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])
    return ranked[:top] if top else ranked


def busy_intervals(events):
    return union((s, s + d) for _, s, d in events if d > 0)


def idle_gaps(busy, lo, hi):
    """The gaps of the merged ``busy`` intervals inside the window [lo, hi]."""
    return subtract([(lo, hi)], clip(busy, lo, hi))


def label_gaps(gaps, host_spans, top=10, unlabelled="host_unannotated"):
    """Idle time by the host span that covers it: each gap is split among the
    host spans overlapping it (innermost, i.e. shortest, first); what no span
    covers is ``unlabelled``.  Returns ``[(name, ns)]``, longest first."""
    sums = {}
    spans = sorted(host_spans, key=lambda e: e[2])  # shortest first
    for gs, ge in gaps:
        left = [(gs, ge)]
        for name, s, d in spans:
            if not left:
                break
            if s >= ge or s + d <= gs:
                continue
            covered = clip(left, s, s + d)
            if covered:
                sums[name] = sums.get(name, 0) + total(covered)
                left = subtract(left, union(covered))
        rest = total(left)
        if rest:
            sums[unlabelled] = sums.get(unlabelled, 0) + rest
    return sorted(sums.items(), key=lambda kv: -kv[1])[:top]


def collective_times(events, async_events=()):
    """For one device's operation line (and its line of asynchronous
    operations, where the trace has one): ``(in_flight_ns, exposed_ns)``.  A
    collective is in flight from its ``-start`` to its ``-done``, for its own
    duration when it is synchronous, and for as long as the asynchronous line
    shows it; it is exposed while the core executes the collective operation
    itself and so no compute: the operations of a line run one after another,
    so that is the collective events' own time."""
    exposed = sum(own for name, own in self_times(events) if COLLECTIVE.search(name))
    flying = [(s, s + d) for name, s, d in async_events if COLLECTIVE.search(name)]
    open_starts = {}
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        if not COLLECTIVE.search(name):
            continue
        kind = COLLECTIVE.search(name).group(0).lower()
        if "-start" in name:
            open_starts.setdefault(kind, []).append(start)
        elif "-done" in name and open_starts.get(kind):
            flying.append((open_starts[kind].pop(0), start + dur))
        else:
            flying.append((start, start + dur))
    return total(union(flying)), exposed


def named(events, pattern: str):
    """Events whose name holds ``pattern`` (a kernel's ``name=``)."""
    return [e for e in events if pattern in e[0]]


# ------------------------------------------------------------------ the file
def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(text: str) -> str:
    """The trace names a device operation by its whole HLO line,
    ``%fusion.7 = bf16[32,256,4096]{...} fusion(...)``: keep the operation's
    own name and the shape it produces, ``%fusion.7 bf16[32,256,4096]``."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    return f"{name} {rest.split('{', 1)[0].split(' ', 1)[0]}"[:120]


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...], "async": [...]}},
    "host": [...]}`` from an ``.xplane.pb``: per accelerator plane the events of
    its operation line, its program (module) line and its asynchronous-operation
    line, and every host event, all as
    ``(name, start_ns, duration_ns)`` on one clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, lines_seen = {}, [], {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "TPU" in plane.name.upper()
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules", "Async XLA Ops": "async"}.get(
                line.name) if is_device and "SparseCore" not in plane.name else None
            if key is not None:
                events = [(short_name(e.name), int(e.start_ns), int(e.duration_ns))
                          for e in line.events]
                devices.setdefault(plane.name, {"ops": [], "modules": [], "async": []})[key] = events
                lines_seen[f"{plane.name} {line.name}"] = [len(events)] + sorted(
                    {n for n, _, _ in events})[:40]
            elif plane.name.startswith("/host:"):
                host.extend((e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events)
    return {"devices": devices, "host": host, "lines": lines_seen}


class Reduction:
    """What the per-layer readers ask of one traced window."""

    def __init__(self, loaded: dict, annotations=()):
        self.devices = {name: d for name, d in loaded["devices"].items() if d["ops"]}
        if not self.devices:
            raise ValueError(f"the trace holds no device operation: {loaded.get('lines')}")
        self.annotations = set(annotations)
        self.host = [e for e in loaded["host"] if e[0] in self.annotations]
        self.busy = {n: busy_intervals(d["ops"]) for n, d in self.devices.items()}
        # the traced window: first operation start to last operation end, any device
        self.lo = min(b[0][0] for b in self.busy.values())
        self.hi = max(b[-1][1] for b in self.busy.values())

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s_by_device(self) -> dict:
        return {n: total(b) / 1e9 for n, b in self.busy.items()}

    @property
    def busy_s(self) -> float:
        per = self.busy_s_by_device()
        return sum(per.values()) / len(per)

    def idle_share_by_device(self) -> dict:
        return {n: 1.0 - s / self.window_s for n, s in self.busy_s_by_device().items()}

    def all_ops(self):
        return [e for d in self.devices.values() for e in d["ops"]]

    def kernel_seconds(self, pattern: str) -> float:
        """Device time of the events named ``pattern``, averaged over devices."""
        return sum(d for _, _, d in named(self.all_ops(), pattern)) / 1e9 / len(self.devices)

    def module_events(self, pattern: str):
        first = next(iter(self.devices.values()))
        return named(first["modules"], pattern)

    def longest_module(self):
        """The program that takes most of the first device's time: its name,
        how often it ran, its mean duration, and its period (from one start to
        the next; the mean duration where it ran once).  None without a
        program line."""
        first = next(iter(self.devices.values()))
        by_name = {}
        for name, start, dur in first["modules"]:
            by_name.setdefault(name, []).append((start, dur))
        if not by_name:
            return None
        name, runs = max(by_name.items(), key=lambda kv: sum(d for _, d in kv[1]))
        runs.sort()
        mean = sum(d for _, d in runs) / len(runs)
        period = (runs[-1][0] - runs[0][0]) / (len(runs) - 1) if len(runs) > 1 else mean
        return {"name": name, "runs": len(runs), "mean_s": mean / 1e9, "period_s": period / 1e9}

    def collective_s_by_device(self) -> dict:
        return {n: tuple(x / 1e9 for x in collective_times(d["ops"], d.get("async", ())))
                for n, d in self.devices.items()}

    def breakdown(self, top=10) -> dict:
        first = next(iter(self.devices))
        ops = [(n, ns / 1e9) for n, ns in time_by_name(self.devices[first]["ops"])
               if not CONTAINER.search(n)][:top]
        gaps = idle_gaps(self.busy[first], self.lo, self.hi)
        idle = [(n, ns / 1e9) for n, ns in label_gaps(gaps, self.host, top=top)]
        return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": [[n, s] for n, s in idle]}
