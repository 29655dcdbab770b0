"""The chunked-scan kernel's share of the device's busy time in the traced
wave: the events named ``gdn_scan`` (the kernel's own name in the device
trace) over busy time.  A trace without such events gives nothing."""

KERNEL = "gdn_scan"


def read(run):
    if run.kind != "serve" or run.trace is None or run.trace.busy_s <= 0:
        return None
    spent = run.trace.kernel_seconds(KERNEL)
    if spent <= 0:
        return None
    return 100.0 * spent / run.trace.busy_s, {"kernel_s": round(spent, 4),
                                              "busy_s": round(run.trace.busy_s, 4)}
