"""Linear-attention kernels: the gated delta rule's, Kimi Delta Attention's and the state-space
duality's chunked scans and their one-token updates (``CHUNK`` and ``scan_chunks`` here are the
gated delta rule's; ``ssd.py`` has its own, which ``kda.py`` shares)."""
from .gated_delta import CHUNK, gated_delta_scan, gated_delta_step, scan_chunks
from .kda import kda_chunks, kda_scan, kda_step
from .ssd import ssd_chunks, ssd_scan, ssd_update
