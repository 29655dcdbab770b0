"""The KV writer's share of the device's busy time: what a step's new K and V
rows cost to put into the pool, and the check that the Pallas writer (and not
a scatter, which has no such event) is in the program."""

from chipbench.reduce import xplane

KERNEL = "kv_write"


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    calls = len(xplane.named(run.trace.all_ops(), KERNEL)) // len(run.trace.devices)
    spent = run.trace.kernel_seconds(KERNEL)
    if calls == 0 or spent <= 0 or run.trace.busy_s <= 0:
        return None
    return 100.0 * spent / run.trace.busy_s, {
        "write_s": round(spent, 4), "busy_s": round(run.trace.busy_s, 4), "calls": calls,
        "us_per_call": round(1e6 * spent / calls, 3)}
