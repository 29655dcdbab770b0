"""The paged kernel's share of its roofline where the pool is latent (MLA):
the least time the chip could take for the traced wave's latent attention,
counted from the wave's own lengths (chipbench/reduce/mla_shapes.py), over the
device time of the kernel's events.  A configuration without a latent (no
``kv_lora_rank`` among its sizes) gives nothing: ``paged_attention_roofline``
is its reader."""

from chipbench.reduce import mla_shapes

KERNEL = "paged_attention"


def read(run):
    if run.kind != "serve" or run.trace is None or "kv_lora_rank" not in run.sizes:
        return None
    spent = run.trace.kernel_seconds(KERNEL)
    if spent <= 0:
        return None
    least = mla_shapes.attention_least_seconds(run.sizes, run.lengths, run.max_new_tokens,
                                               run.peaks)
    bound = max((k for k in least if k != "seconds"), key=least.get)
    return 100.0 * least["seconds"] / spent, {"kernel_s": round(spent, 4), "mostly": bound,
                                              **{k: round(v, 5) for k, v in least.items()}}
