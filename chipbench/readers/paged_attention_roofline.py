"""The paged-attention kernel's share of its roofline over the traced wave:
the least time the chip could take for the wave's attention, counted from the
wave's own lengths (chipbench/reduce/shapes.py), over the device time of the
kernel's events."""

from chipbench.reduce import shapes

KERNEL = "paged_attention"


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    spent = run.trace.kernel_seconds(KERNEL)
    if spent <= 0:
        return None
    least = shapes.paged_attention_least_seconds(run.sizes, run.lengths, run.max_new_tokens,
                                                 run.peaks)
    bound = max(("decode_memory_s", "prefill_compute_s", "prefill_memory_s"), key=least.get)
    return 100.0 * least["seconds"] / spent, {"kernel_s": round(spent, 4), "mostly": bound,
                                              **{k: round(v, 5) for k, v in least.items()}}
