"""Published per-chip peaks, keyed by JAX's ``device_kind``.

The benchmark keeps its own table so that no change to the program can
move the yardstick.  A kind that is not listed is an error, never a
default: a share of a wrong peak is worse than no share.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float        # FLOP/s
    hbm_bandwidth: float     # bytes/s
    hbm_bytes: float         # bytes of device memory
    source: str

    @property
    def ridge(self) -> float:
        """FLOP per byte at which compute and bandwidth bound alike."""
        return self.flops_bf16 / self.hbm_bandwidth


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bandwidth=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
