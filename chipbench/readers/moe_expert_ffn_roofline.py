"""The expert FFN's grouped matmuls' share of their roofline over the traced
wave: the least time the chip could take for the wave's routed rows
(chipbench/reduce/moe_shapes.py), over the device time of the grouped-matmul
events.

Why the numerator is a floor, so that no reading passes 100%.  The operations
are exactly what the routed rows need: ``moe_routed_rows`` counts live tokens
x k x layers, no dead slot, no filling of a tile.  The bytes count each routed
row in and out once, and an expert's matrix once for a call that multiplies by
it; the program's kernel reads it again for every row tile the expert's group
crosses.  How many rows each call held is not in the counters, only how many
it could hold (the ``[R, width]`` its event produces), so the rows are laid
into the calls the way that reads fewest matrices (``fewest_matrix_reads``):
any true laying reads more.  What it rests on: a call of r rows uses
``min(E, r)`` experts.  Routing that concentrates on fewer would need fewer
reads; the program hands no expert loads out, so that cannot be read, and is
small where it matters: under uniform routing the 256 rows of a 32-token
decode pass use 62.9 of 64 experts, the 2,048 of a chunk pass all 64, and a
256-row call takes on the chip what uniformly random groups took in the
kernel's own trace (0.357 against 0.356 ms; PERF.md, PR 27)."""

from chipbench.reduce import moe_shapes
from chipbench.readers import moe_ffn_share


def read(run):
    if run.kind != "serve" or run.trace is None or not run.counters.get("moe_routed_rows"):
        return None
    calls = [(moe_ffn_share.result_shape(name)[1][0], ns)
             for _, name, ns, kind in moe_ffn_share.operations(run) if kind == "grouped_matmul"]
    chips = len(run.trace.devices)
    spent = sum(ns for _, ns in calls) / 1e9 / chips
    if spent <= 0:
        return None
    capacities = sorted(rows for rows, _ in calls)[::chips]  # one device's calls
    least = moe_shapes.expert_ffn_least_seconds(run.sizes, run.counters["moe_routed_rows"],
                                                capacities, run.peaks)
    bound = max(("compute_s", "memory_s"), key=least.get)
    return 100.0 * least["seconds"] / spent, {
        "grouped_matmul_s": round(spent, 4), "calls": len(capacities), "mostly": bound,
        **{k: round(v, 5) for k, v in least.items()}}
