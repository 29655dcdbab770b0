"""``swa_kernel_share`` for the layers that attend their whole past (``attn_full``)."""

from chipbench.readers.swa_kernel_share import kind_share


def read(run):
    return kind_share(run, "attn_full")
