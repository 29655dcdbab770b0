"""Time a device's core spends inside collective operations, when no compute
runs on it, over the traced window; the worst device."""


def read(run):
    if run.kind != "train" or run.trace is None:
        return None
    per = run.trace.collective_s_by_device()
    worst = max(exposed for _, exposed in per.values())
    in_flight = max(flying for flying, _ in per.values())
    return 100.0 * worst / run.trace.window_s, {
        "exposed_ms_per_step": round(1e3 * worst / run.steps, 3),
        "collective_in_flight_ms_per_step": round(1e3 * in_flight / run.steps, 3)}
