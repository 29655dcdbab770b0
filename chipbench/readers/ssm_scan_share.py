"""The two state-space kernels' share of the device's busy time in the traced
wave: the events named ``ssd_scan`` (the chunked scan) and ``ssd_update`` (a
decode row's one-token update), the kernels' own names in the device trace,
over busy time.  A trace without such events gives nothing."""

SCAN, UPDATE = "ssd_scan", "ssd_update"


def read(run):
    if run.kind != "serve" or run.trace is None or run.trace.busy_s <= 0:
        return None
    scan, update = run.trace.kernel_seconds(SCAN), run.trace.kernel_seconds(UPDATE)
    if scan + update <= 0:
        return None
    return 100.0 * (scan + update) / run.trace.busy_s, {
        "scan_s": round(scan, 4), "update_s": round(update, 4), "busy_s": round(run.trace.busy_s, 4)}
