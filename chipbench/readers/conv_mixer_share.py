"""The share of the device's busy time spent in the conv operator's
operations that the trace lets one be certain of, listed by kind.

The trace gives an operation its HLO name and the shape it produces, no scope
(``ProfileData`` does not surface ``conv_mixer`` / ``conv_state``), so the
operations are found by their results (``chipbench/reduce/lfm2_shapes.py``):

- ``in_proj``: results ``[.., 3 x hidden]``, the product ``u W_in`` (three
  quarters of the operator's matmul work);
- ``state``: the whole state or a batch's rows of it (the slot read, the rows
  beside a step's new value, the write back).

Left out, because nothing tells them from the step's other per-token
operations: the gate ``B * X``, the taps' products, ``C * conv`` and the
output projection, all ``[slots, hidden]`` (a quarter of the matmul work and
the element-wise part): the share is a floor of the operator's."""

from chipbench.readers import moe_ffn_share
from chipbench.reduce import lfm2_shapes, xplane


def operations(run):
    """``[(operation, ns, kind)]`` of the conv operator's certain operations."""
    leaf = lfm2_shapes.state_leaf(getattr(run, "pool_shapes", ()))
    if leaf is None:
        return []
    found = []
    for device in run.trace.devices.values():
        for name, ns in xplane.self_times(device["ops"]):
            if xplane.CONTAINER.search(name):
                continue
            dims = moe_ffn_share.result_shape(name)[1]
            if lfm2_shapes.is_state_move(dims, leaf):
                found.append((name, ns, "state"))
            elif lfm2_shapes.is_mixer_result(dims, leaf, run.sizes["hidden_size"]):
                found.append((name, ns, "in_proj"))
    return found


def read(run):
    if run.kind != "serve" or run.trace is None or run.trace.busy_s <= 0:
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
