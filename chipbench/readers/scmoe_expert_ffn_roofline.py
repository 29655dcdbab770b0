"""The shortcut expert layers' grouped matmuls' share of their roofline over the
traced wave, with the TRUE held rows: the least time the chip could take for
the rows that picks on experts held here fill (``ServeCounters.moe_held_picks``,
tallied on the device; ``chipbench/reduce/scmoe_shapes.py``), over the device
time of the grouped-matmul events.  Identity picks and picks held elsewhere are
rows of those calls too (dead ones, behind the last group) and are no part of
the numerator: they multiply nothing and no matrix is read for them.  The
numerator is a floor (the fewest expert matrices the calls can have read), so
no reading passes 100%.  A program without the tally gives nothing."""

from chipbench.readers import moe_ffn_share, scmoe_ffn_share
from chipbench.reduce import scmoe_shapes


def read(run):
    held_rows = (getattr(run, "counters", None) or {}).get("moe_held_picks")
    if run.kind != "serve" or getattr(run, "trace", None) is None or not held_rows:
        return None
    if not scmoe_ffn_share.KEYS <= set(run.sizes):
        return None
    calls = [(moe_ffn_share.result_shape(name)[1][0], ns)
             for _, name, ns, kind in scmoe_ffn_share.operations(run) if kind == "grouped_matmul"]
    chips = len(run.trace.devices)
    spent = sum(ns for _, ns in calls) / 1e9 / chips
    if spent <= 0:
        return None
    capacities = sorted(rows for rows, _ in calls)[::chips]  # one device's calls
    least = scmoe_shapes.expert_ffn_least_seconds(run.sizes, held_rows, capacities, run.peaks)
    bound = max(("compute_s", "memory_s"), key=least.get)
    return 100.0 * least["seconds"] / spent, {
        "grouped_matmul_s": round(spent, 4), "calls": len(capacities), "mostly": bound,
        "held_rows": held_rows,
        **{k: round(v, 6) for k, v in least.items()}}
