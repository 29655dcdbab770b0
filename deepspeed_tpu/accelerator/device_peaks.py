"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

The table ``monitor/telemetry.py`` reads (``chipbench/reduce/peaks.json`` holds
the benchmark's copy).  A device that is not in it has no peak: ``device_peaks``
raises, and a utilization against an unknown device is ``null`` or an error,
never another chip's figure.

Source: Google Cloud TPU documentation, the "System architecture" page of each
version (v5e: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600
Gbit/s of chip-to-chip interconnect).  Only ``TPU v5 lite`` has been seen by
this repo on a chip; the other kinds are as JAX names those generations.
"""

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float  # FLOP/s per chip
    hbm_bytes_per_s: float


DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v4": DevicePeaks(bf16_flops=275e12, hbm_bytes_per_s=1200e9),
    "TPU v5 lite": DevicePeaks(bf16_flops=197e12, hbm_bytes_per_s=819e9),
    "TPU v5": DevicePeaks(bf16_flops=459e12, hbm_bytes_per_s=2765e9),
    "TPU v6 lite": DevicePeaks(bf16_flops=918e12, hbm_bytes_per_s=1640e9),
}


class UnknownDeviceError(LookupError):
    """The device's kind is not in ``DEVICE_PEAKS``."""


def device_peaks(device_kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{', '.join(sorted(DEVICE_PEAKS))}. Add a row with its source to "
            f"deepspeed_tpu/accelerator/device_peaks.py") from None
