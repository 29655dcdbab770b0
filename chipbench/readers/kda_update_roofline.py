"""The one-token update kernel's share of its roofline: twice the state bytes of
the rows updated in the traced wave at the memory's speed
(``chipbench/reduce/kda_shapes.py``) over the device time of the events named
``kda_update``.  The rows updated are the live tokens that no scan walked (decode
steps, a burst's steps, the one-token rows of a mixed pass), each in every KDA
layer: ``live_tokens - scan_live_positions / layers`` of the engine's counters;
the rows a padded bucket holds beside them are the kernel's own cost."""

from chipbench.reduce import kda_shapes

UPDATE = "kda_update"


def read(run):
    counters = getattr(run, "counters", None) or {}
    leaves = kda_shapes.state_leaves(run.sizes, getattr(run, "pool_shapes", ())) \
        if run.kind == "serve" else None
    if run.trace is None or leaves is None or not counters.get("live_tokens"):
        return None
    spent = run.trace.kernel_seconds(UPDATE)
    if spent <= 0:
        return None
    layers = leaves[1][0]
    rows = counters["live_tokens"] - counters.get("scan_live_positions", 0) // layers
    least = kda_shapes.update_least_seconds(run.sizes, rows * layers, run.peaks)
    return 100.0 * least["seconds"] / spent, {"kernel_s": round(spent, 4), "row_updates": rows * layers,
                                              "least_s": round(least["seconds"], 5)}
