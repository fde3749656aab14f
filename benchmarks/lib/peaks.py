"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` string JAX reports.  A device that is not in the table is an
error, never a default: no CPU fall-back ever prints a device metric.  With
them, the roofline's bound: the least time the chip could take for a given
count of operations and bytes (the count itself is the family's ``work``)."""

from __future__ import annotations

from typing import Dict, Tuple

# Google Cloud documentation, "TPU v5e" (system architecture page): 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; the "
            f"benchmark runs only on {sorted(PEAKS)}") from None


def least_seconds(w: Dict[str, float], peaks: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The least time the chip could take for ``w`` and which peak bounds
    it."""
    t_c = w["flops"] / peaks["flops_bf16"]
    t_m = w["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
