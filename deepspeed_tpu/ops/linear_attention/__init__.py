"""Linear-attention kernels: the gated delta rule's chunked scan and its one-token update."""
from .gated_delta import CHUNK, gated_delta_scan, gated_delta_step, scan_chunks
