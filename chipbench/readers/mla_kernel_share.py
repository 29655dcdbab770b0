"""The paged kernel's share of the device's busy time in a cell whose pool is
latent (MLA): the check that latent attention is what the cell measures."""

KERNEL = "paged_attention"


def read(run):
    if run.kind != "serve" or run.trace is None or "kv_lora_rank" not in run.sizes:
        return None
    spent = run.trace.kernel_seconds(KERNEL)
    if spent <= 0 or run.trace.busy_s <= 0:
        return None
    return 100.0 * spent / run.trace.busy_s, {"kernel_s": round(spent, 4),
                                              "busy_s": round(run.trace.busy_s, 4)}
