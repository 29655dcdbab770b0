"""The ungated experts' grouped matmuls' share of their roofline over the traced
wave, with the TRUE held rows: the least time the chip could take for the rows
that picks on experts held here fill (``ServeCounters.moe_held_picks``, tallied
on the device; ``chipbench/reduce/nemotron_h_shapes.py``: two matmuls a row, two
matrices an expert), over the device time of the grouped-matmul events (``gmm``,
their rows read off each event's result).  Picks held elsewhere are no part of
the numerator: they multiply nothing and no matrix is read for them.  The bytes are
the two matrices of every held expert that a layer-pass's live picks NAMED
(``moe_experts_hit``, tallied on the device too: routing is skewed, and a count of
``min(experts, rows)`` a call read 109% on the chip, PR 62), each read at least
once a call, so no reading passes 100%.  A program without the tallies or these
sizes gives nothing."""

from chipbench.readers.moe_ffn_share import is_grouped_matmul, result_shape
from chipbench.reduce import nemotron_h_shapes as shapes
from chipbench.reduce import xplane


def read(run):
    counters = getattr(run, "counters", None) or {}
    held_rows, hit = counters.get("moe_held_picks"), counters.get("moe_experts_hit")
    if (run.kind != "serve" or getattr(run, "trace", None) is None or not held_rows or not hit
            or not shapes.is_family(run.sizes)):
        return None
    calls = [(result_shape(name)[1][0], ns) for name, _, ns in run.trace.all_ops()
             if is_grouped_matmul(name) and not xplane.CONTAINER.search(name) and result_shape(name)[1]]
    chips = len(run.trace.devices)
    spent = sum(ns for _, ns in calls) / 1e9 / chips
    if spent <= 0:
        return None
    least = shapes.expert_ffn_least_seconds(run.sizes, held_rows, hit, run.peaks)
    bound = max(("compute_s", "memory_s"), key=least.get)
    return 100.0 * least["seconds"] / spent, {
        "grouped_matmul_s": round(spent, 4), "calls": len(calls) // chips, "mostly": bound, "held_rows": held_rows,
        "experts_hit": hit,
        **{k: round(v, 6) for k, v in least.items()}}
