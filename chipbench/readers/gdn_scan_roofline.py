"""The chunked-scan kernel's share of its roofline: the least time the chip
could take for the gated delta rule over the token positions the scan was given
in the traced wave (``chipbench/reduce/gdn_shapes.py``, from the engine's
``scan_live_positions``: the algorithm's operations and bytes, whatever
implements it) over the device time of the events named ``gdn_scan``."""

from chipbench.reduce import gdn_shapes
from chipbench.readers.gdn_scan_share import KERNEL


def read(run):
    counters = getattr(run, "counters", None) or {}
    if (run.kind != "serve" or run.trace is None or not gdn_shapes.is_family(run.sizes)
            or not counters.get("scan_live_positions")):
        return None
    spent = run.trace.kernel_seconds(KERNEL)
    if spent <= 0:
        return None
    least = gdn_shapes.scan_least_seconds(run.sizes, counters["scan_live_positions"], run.peaks)
    bound = max((k for k in least if k != "seconds"), key=least.get)
    return 100.0 * least["seconds"] / spent, {"kernel_s": round(spent, 4), "mostly": bound,
                                              **{k: round(v, 5) for k, v in least.items()}}
