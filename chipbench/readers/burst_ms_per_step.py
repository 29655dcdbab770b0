"""Device time of the fused decode-burst programs per decode step they
cover.  The burst is told by its program's name in the trace; where no
program is named so, there is nothing to read."""


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    events = run.trace.module_events("burst")
    # steps inside bursts: every forward pass that was not a stepwise dispatch
    steps = run.forwards - run.stepwise_forwards
    if not events or steps <= 0:
        return None
    seconds = sum(d for _, _, d in events) / 1e9
    return 1e3 * seconds / steps, {"burst_programs_run": len(events), "steps": steps}
