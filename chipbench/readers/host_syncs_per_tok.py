"""Device-to-host materializations the serve loop made per generated token."""


def read(run):
    if run.kind != "serve" or not run.generated_ok:
        return None
    return run.counters["host_syncs"] / run.generated_ok, {"host_syncs": run.counters["host_syncs"]}
