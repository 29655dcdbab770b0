"""The share of the device's busy time spent in the Gated DeltaNet layers'
operations that the trace lets one be certain of, listed by kind.

The trace gives an operation its HLO name and the shape it produces, no scope
(``ProfileData`` does not surface ``gdn_mixer`` / ``gdn_scan`` / ``gdn_state``),
so the operations are found by the kernel's name and by their results
(``chipbench/reduce/gdn_shapes.py``):

- ``scan``: the events named ``gdn_scan``, the chunked-scan kernel;
- ``in_proj``: results ``[.., q | k | v | z]``, the product ``u W_qkvz`` (three
  quarters of the layer's projection work);
- ``filter``: results ``[.., q | k | v]``: the 4-tap filter, its SiLU and the
  shift's rows;
- ``state``: results that end in ``(Hv, dk, dv)``: the carried matrices read
  from their slots, updated by a decode step, written back.

Left out, because nothing tells them from the step's other per-token
operations: the output projection ``[slots, hidden]`` and what the scan's
layout and the output norm do over ``[.., Hv, dv]``: the share is a floor of
the layers'."""

from chipbench.readers import moe_ffn_share
from chipbench.readers.gdn_scan_share import KERNEL
from chipbench.reduce import gdn_shapes, xplane


def operations(run):
    """``[(operation, ns, kind)]`` of the Gated DeltaNet layers' certain operations."""
    sizes = run.sizes
    _, hv, dk, dv, mixed, projected = gdn_shapes.widths(sizes)
    found = []
    for device in run.trace.devices.values():
        for name, ns in xplane.self_times(device["ops"]):
            if xplane.CONTAINER.search(name):
                continue
            dims = moe_ffn_share.result_shape(name)[1]
            if KERNEL in name:
                found.append((name, ns, "scan"))
            elif gdn_shapes.is_mixer_result(dims, sizes):
                kind = ("state" if dims[-3:] == (hv, dk, dv)
                        else "in_proj" if dims[-1] == projected else "filter")
                found.append((name, ns, kind))
    return found


def read(run):
    if (run.kind != "serve" or run.trace is None or run.trace.busy_s <= 0
            or not gdn_shapes.is_family(run.sizes)):
        return None
    by_kind = {}
    for _, ns, kind in operations(run):
        by_kind[kind] = by_kind.get(kind, 0) + ns
    if not by_kind.get("in_proj"):
        return None
    chips = len(run.trace.devices)
    seconds = sum(by_kind.values()) / 1e9 / chips
    return 100.0 * seconds / run.trace.busy_s, {
        "mixer_s": round(seconds, 4), "busy_s": round(run.trace.busy_s, 4),
        **{f"{kind}_s": round(ns / 1e9 / chips, 4) for kind, ns in sorted(by_kind.items())}}
