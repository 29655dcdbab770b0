"""The share of the device's busy time spent in the expert FFN: device time of
its operations over the traced wave's busy time, listed by kind.

The trace gives an operation its HLO name and the shape it produces, no scope
(``ProfileData`` does not surface ``moe_route`` / ``moe_expert_ffn``), so the
operations are found from what is certain and outward:

- ``grouped_matmul``: the Pallas kernel by its name (``gmm``), or XLA's
  ``ragged-dot``.  Its result is ``[R, width]``: R, the routed rows a program
  computes, is read off these events for each program (a chunk program's
  2,048, a 32-row decode program's 256), never recomputed here.
- ``sort``: operations named ``sort`` in a program that holds a grouped
  matmul: the router's top-k over ``[slots, E]`` and the argsorts of the R
  rows by expert and back.
- ``group_metadata``: vectors as long as the stack's groups (layers x
  experts), one more, or groups + row tiles - 1: the group sizes and the
  kernel's tile schedule.
- ``router``: rank-2 results whose last dimension is the expert count.
- ``dispatch``: every other result of R rows by nothing, one, the hidden size
  or the expert width: the row indices, the gather of the sorted rows,
  ``silu(gate) * up``, the mask of the rows past the last group, the gather
  back into token order.

Left out, because nothing tells them from other operations: the weighted sum
over a token's k picks, whose result is ``[slots, hidden]`` like every dense
per-token operation's, and the ``[slots, k]`` picks, shaped like a block table
eight wide (together under 0.1% of the expert FFN's time; PERF.md, PR 27)."""

import bisect
import re

from chipbench.reduce import xplane

GROUPED_MATMUL = ("gmm", "ragged-dot")
RESULT = re.compile(r"\(?([a-z]+[0-9]*)\[([0-9,]*)\]")


def result_shape(name: str):
    """``%fusion.7 bf16[2048,1024]`` -> ("bf16", (2048, 1024)); a tuple's first element."""
    found = RESULT.search(name.split(" ", 1)[-1])
    if not found:
        return None, ()
    return found.group(1), tuple(int(d) for d in found.group(2).split(",") if d)


def is_grouped_matmul(name: str) -> bool:
    return any(k in name for k in GROUPED_MATMUL)


def by_program(device):
    """``{program name: [(operation name, ns)]}`` of the leaf operations, each
    under the program run that covers its start."""
    runs = sorted((start, start + dur, name) for name, start, dur in device["modules"])
    starts = [r[0] for r in runs]
    out = {}
    for name, start, dur in device["ops"]:
        if xplane.CONTAINER.search(name):
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < runs[i][1]:
            out.setdefault(runs[i][2], []).append((name, dur))
    return out


def kind_of(name: str, rows: int, groups: int, sizes):
    """The kind of one operation of a program whose grouped matmuls run over
    ``rows`` rows, or None where it is not the expert FFN's."""
    if is_grouped_matmul(name):
        return "grouped_matmul"
    if name.startswith("%sort"):
        return "sort"
    dtype, dims = result_shape(name)
    if not dims:
        return None
    tiles = max(1, rows // 128)
    if len(dims) == 1 and dims[0] in (groups, groups + 1, groups + tiles - 1):
        return "group_metadata"
    if len(dims) == 2 and dims[1] == sizes["num_experts"]:
        return "router"
    if dims[0] == rows and dims[1:] in ((), (1,), (sizes["hidden_size"],),
                                        (sizes["intermediate_size"],)):
        return "dispatch"
    return None


def operations(run):
    """``[(program, operation, ns, kind)]`` of the expert FFN's operations."""
    groups = run.sizes["num_hidden_layers"] * run.sizes["num_experts"]
    found = []
    for device in run.trace.devices.values():
        for program, ops in by_program(device).items():
            rows = {result_shape(n)[1][0] for n, _ in ops if is_grouped_matmul(n)}
            if len(rows) != 1:  # no expert FFN in this program (or not one this reader knows)
                continue
            for name, ns in ops:
                kind = kind_of(name, min(rows), groups, run.sizes)
                if kind:
                    found.append((program, name, ns, kind))
    return found


def read(run):
    if run.kind != "serve" or run.trace is None or run.trace.busy_s <= 0:
        return None
    if "num_experts" not in run.sizes:
        return None
    by_kind = {}
    for _, _, ns, kind in operations(run):
        by_kind[kind] = by_kind.get(kind, 0) + ns
    if not by_kind.get("grouped_matmul"):
        return None
    chips = len(run.trace.devices)
    seconds = sum(by_kind.values()) / 1e9 / chips
    return 100.0 * seconds / run.trace.busy_s, {
        "ffn_s": round(seconds, 4), "busy_s": round(run.trace.busy_s, 4),
        **{f"{kind}_s": round(ns / 1e9 / chips, 4) for kind, ns in sorted(by_kind.items())}}
