"""The chunked-scan kernel's share of its roofline: the least time the chip could
take for the delta rule over the token positions the scan was given in the traced
wave (``chipbench/reduce/kda_shapes.py``, from the engine's
``scan_live_positions``: the recurrence's operations and bytes, whatever implements
it) over the device time of the events named ``kda_scan``."""

from chipbench.reduce import kda_shapes

SCAN = "kda_scan"


def read(run):
    counters = getattr(run, "counters", None) or {}
    if (run.kind != "serve" or run.trace is None or not kda_shapes.is_family(run.sizes)
            or not counters.get("scan_live_positions")):
        return None
    spent = run.trace.kernel_seconds(SCAN)
    if spent <= 0:
        return None
    least = kda_shapes.scan_least_seconds(run.sizes, counters["scan_live_positions"], run.peaks)
    bound = max((k for k in least if k != "seconds"), key=least.get)
    return 100.0 * least["seconds"] / spent, {"kernel_s": round(spent, 4), "mostly": bound,
                                              **{k: round(v, 5) for k, v in least.items()}}
