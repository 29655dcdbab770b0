"""The paged kernel's share of its roofline where layers differ in their window:
the least time the chip could take for the traced wave's attention, a windowed
layer counted over the keys inside its window and a full layer over all
(``chipbench/reduce/swa_shapes.py``, from the wave's own lengths), over the device
time of the events named ``paged_attention``.  A walk that fetched and multiplied
the blocks behind a window reads low by as much."""

from chipbench.reduce import swa_shapes

KERNEL = "paged_attention"


def read(run):
    if run.kind != "serve" or run.trace is None or not swa_shapes.is_family(run.sizes):
        return None
    spent = run.trace.kernel_seconds(KERNEL)
    if spent <= 0:
        return None
    least = swa_shapes.attention_least_seconds(run.sizes, run.lengths, run.max_new_tokens, run.peaks)
    bound = max(("decode_memory_s", "prefill_compute_s", "prefill_memory_s"), key=least.get)
    return 100.0 * least["seconds"] / spent, {"kernel_s": round(spent, 4), "mostly": bound,
                                              **{k: round(v, 5) for k, v in least.items()}}
