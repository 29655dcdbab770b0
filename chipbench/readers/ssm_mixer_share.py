"""The share of the device's busy time spent in the Mamba-2 layers' operations
that the trace lets one be certain of, listed by kind.

The trace gives an operation its HLO name and the shape it produces, no scope
(``ProfileData`` does not surface ``ssm_mixer`` / ``ssm_scan`` / ``ssm_update``
/ ``ssm_state``), so the operations are found by the kernels' names and by
their results (``chipbench/reduce/ssm_shapes.py``):

- ``scan`` / ``update``: the events named ``ssd_scan`` / ``ssd_update``;
- ``in_proj``: results ``[.., z | xBC | dt]``, the product ``u W_in`` (two
  thirds of the layer's projection work);
- ``filter``: results ``[.., xBC]``: the 4-tap filter, its SiLU and the shift's rows;
- ``state``: results that end in ``(H, P, Ns)``: the carried matrices read from
  their slots and written back.

Left out, because nothing tells them from the step's other per-token
operations: the output projection ``[slots, hidden]`` and what the gate, the
norm and the scan's layout do over ``[.., I]``: the share is a floor of the layers'."""

from chipbench.readers import moe_ffn_share
from chipbench.readers.ssm_scan_share import SCAN, UPDATE
from chipbench.reduce import ssm_shapes, xplane


def operations(run):
    """``[(operation, ns, kind)]`` of the Mamba-2 layers' certain operations."""
    sizes = run.sizes
    h, p, ns_, _, _, projected = ssm_shapes.widths(sizes)
    found = []
    for device in run.trace.devices.values():
        for name, ns in xplane.self_times(device["ops"]):
            if xplane.CONTAINER.search(name):
                continue
            dims = moe_ffn_share.result_shape(name)[1]
            if SCAN in name or UPDATE in name:
                found.append((name, ns, "scan" if SCAN in name else "update"))
            elif ssm_shapes.is_mixer_result(dims, sizes):
                kind = ("state" if dims[-3:] == (h, p, ns_)
                        else "in_proj" if dims[-1] == projected else "filter")
                found.append((name, ns, kind))
    return found


def read(run):
    if (run.kind != "serve" or run.trace is None or run.trace.busy_s <= 0
            or not ssm_shapes.is_family(run.sizes)):
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
