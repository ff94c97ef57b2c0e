"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A device that is not listed is an error: no
number is ever computed against a guessed peak.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s). JAX names a v5e "TPU v5 lite".
The compute peak is the bf16 one; the float32 work this benchmark runs at
``Precision.HIGHEST`` takes several MXU passes, so its shares of it read low
by that factor.
"""
from __future__ import annotations

from dataclasses import dataclass

SOURCE = 'Google Cloud documentation, "TPU v5e"'


@dataclass(frozen=True)
class Peaks:
    flops: float      # FLOP/s, bf16 MXU peak
    hbm_bw: float     # bytes/s
    hbm_bytes: float  # bytes of device memory


PEAKS = {"TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9)}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(f"no published peaks for device kind "
                          f"{device_kind!r}; known: {sorted(PEAKS)}") from None
