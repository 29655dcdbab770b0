"""The one-token update kernel's share of its roofline where B and C come a
GROUP of heads (Nemotron-H): twice the state bytes of the rows updated in the
traced wave, and their groups' B and C, at the memory's speed
(``chipbench/reduce/nemotron_h_shapes.py``) over the device time of the events
named ``ssd_update``.  The rows updated are the live tokens no scan walked
(decode steps, a burst's steps, the one-token rows of chunk passes), each in
every ``M`` layer: ``live_tokens - scan_live_positions / M layers`` of the
engine's counters; the rows a padded bucket holds beside them are the kernel's
own cost.  A program without the kernel, the leaves or these sizes gives nothing."""

from chipbench.reduce import nemotron_h_shapes as shapes

UPDATE = "ssd_update"


def read(run):
    counters = getattr(run, "counters", None) or {}
    leaves = shapes.state_leaves(run.sizes, getattr(run, "pool_shapes", ())) if run.kind == "serve" else None
    if getattr(run, "trace", None) is None or leaves is None or not counters.get("live_tokens"):
        return None
    spent = run.trace.kernel_seconds(UPDATE)
    if spent <= 0:
        return None
    layers = leaves[1][0]
    rows = counters["live_tokens"] - counters.get("scan_live_positions", 0) // layers
    least = shapes.update_least_seconds(run.sizes, rows * layers, run.peaks)
    return 100.0 * least["seconds"] / spent, {"kernel_s": round(spent, 4), "row_updates": rows * layers,
                                              "least_s": round(least["seconds"], 5)}
