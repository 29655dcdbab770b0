"""Model FLOP/s utilization of the traced steps: operations the forward and
backward passes require per token (6 per matmul parameter plus causal
attention, no recomputation counted), times the tokens of a step, over the
step's time on the device, chips and the peak.  The step's time is read from
the trace: the program that takes most of the device's time is the train
step, and its period is the distance from one start of it to the next (the
gap the host leaves between steps included, the profiler's start-up not)."""

from chipbench.reduce import shapes


def read(run):
    if run.kind != "train" or run.trace is None:
        return None
    step = run.trace.longest_module()
    if step is None:
        return None
    per_token = shapes.train_flops_per_token(run.sizes, run.seq)
    rate = run.tokens_per_step / step["period_s"]
    return (100.0 * per_token * rate / (run.chips * run.peaks["bf16_flops_per_s"]),
            {"flops_per_token": f"{per_token:.4g}", "module": step["name"], "runs": step["runs"],
             "step_period_ms": round(1e3 * step["period_s"], 3),
             "step_on_device_ms": round(1e3 * step["mean_s"], 3),
             "tokens_per_s": round(rate, 1)})
