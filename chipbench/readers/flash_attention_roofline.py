"""The flash-attention kernels' share of their roofline over the traced
steps: shape-counted operations and bytes of forward and backward over the
device time of flash_attention_fwd, _bwd_dkv and _bwd_dq events."""

from chipbench.reduce import shapes

KERNEL = "flash_attention"


def read(run):
    if run.kind != "train" or run.trace is None:
        return None
    spent = run.trace.kernel_seconds(KERNEL)  # averaged over the devices
    if spent <= 0:
        return None
    rows = run.global_batch // run.chips  # sequences one device works on
    flops = shapes.flash_attention_flops(run.sizes, rows, run.seq) * run.steps
    moved = shapes.flash_attention_bytes(run.sizes, rows, run.seq) * run.steps
    by_flops = flops / run.peaks["bf16_flops_per_s"]
    by_bytes = moved / run.peaks["hbm_bytes_per_s"]
    return 100.0 * max(by_flops, by_bytes) / spent, {
        "kernel_s": round(spent, 4), "bound": "compute" if by_flops >= by_bytes else "memory"}
