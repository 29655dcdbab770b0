"""The share of the device's busy time spent choosing what to attend: the
index-score kernel (events named ``dsa_index_scores``) and the operations that
are certainly the selection's by their results (``chipbench/reduce/
dsa_shapes.py is_selection_result``: the scores' gathers, their ordered image,
the bisection's counting passes, the masks, the layout for the kernel).  The
indexer's projections are per-token operations like any other and are not in
it: a floor."""

from chipbench.readers import moe_ffn_share
from chipbench.reduce import dsa_shapes, xplane

KERNEL = "dsa_index_scores"


def operations(run):
    """``[(operation, ns, kind)]``: ``scores`` the kernel's events, ``select`` the rest."""
    bs = dsa_shapes.block_size(getattr(run, "pool_shapes", ()))
    found = []
    for device in run.trace.devices.values():
        for name, ns in xplane.self_times(device["ops"]):
            if xplane.CONTAINER.search(name):
                continue
            if KERNEL in name:
                found.append((name, ns, "scores"))
                continue
            dtype, dims = moe_ffn_share.result_shape(name)
            if dsa_shapes.is_selection_result(dtype, dims, run.sizes, bs):
                found.append((name, ns, "select"))
    return found


def read(run):
    if (run.kind != "serve" or run.trace is None or run.trace.busy_s <= 0
            or not dsa_shapes.is_family(run.sizes)):
        return None
    by_kind = {}
    for _, ns, kind in operations(run):
        by_kind[kind] = by_kind.get(kind, 0) + ns
    if not by_kind.get("scores"):
        return None
    chips = len(run.trace.devices)
    seconds = sum(by_kind.values()) / 1e9 / chips
    return 100.0 * seconds / run.trace.busy_s, {
        "indexer_s": round(seconds, 4), "busy_s": round(run.trace.busy_s, 4),
        **{f"{kind}_s": round(ns / 1e9 / chips, 4) for kind, ns in sorted(by_kind.items())}}
