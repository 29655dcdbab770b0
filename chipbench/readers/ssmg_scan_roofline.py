"""The chunked-scan kernel's share of its roofline where B and C come a GROUP of
heads (Nemotron-H): the least time the chip could take for the recurrence over
the token positions the scan was given in the traced wave
(``chipbench/reduce/nemotron_h_shapes.py``, from the engine's
``scan_live_positions``: the recurrence's operations and bytes with every group's
B and C counted) over the device time of the events named ``ssd_scan``."""

from chipbench.reduce import nemotron_h_shapes as shapes

SCAN = "ssd_scan"


def read(run):
    counters = getattr(run, "counters", None) or {}
    if (run.kind != "serve" or getattr(run, "trace", None) is None or not shapes.is_family(run.sizes)
            or not counters.get("scan_live_positions")):
        return None
    spent = run.trace.kernel_seconds(SCAN)
    if spent <= 0:
        return None
    least = shapes.scan_least_seconds(run.sizes, counters["scan_live_positions"], run.peaks)
    bound = max((k for k in least if k != "seconds"), key=least.get)
    return 100.0 * least["seconds"] / spent, {"kernel_s": round(spent, 4), "mostly": bound,
                                              **{k: round(v, 5) for k, v in least.items()}}
