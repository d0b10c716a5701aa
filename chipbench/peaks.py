"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s bfloat16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 819e9},
}
SOURCE = "Google Cloud documentation, TPU v5e"


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_time(ops: float, nbytes: float, device_kind: str,
               precision: str) -> float:
    """Least seconds the chip could take for ``ops`` operations at the peak
    of ``precision`` (``bf16`` or ``int8``) and ``nbytes`` of HBM traffic:
    the larger of the two bounds."""
    pk = peaks(device_kind)
    rate = {"bf16": pk["bf16_flops"], "int8": pk["int8_ops"]}[precision]
    return max(ops / rate, nbytes / pk["hbm_bytes"])


def share(least_s: float, measured_s: float) -> float:
    """Percent of the roofline: least time over measured time.  A share
    above 100 means the work was counted too high or the time too low;
    it is reported as measured, never clipped."""
    if measured_s <= 0:
        raise ValueError(f"measured time {measured_s} is not positive")
    return 100.0 * least_s / measured_s
