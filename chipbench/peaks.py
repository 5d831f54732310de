"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.
JAX reports a v5e chip as ``TPU v5 lite``. A device that is not in the
table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    """One chip's peaks: operations per second and HBM bytes per second."""

    flops: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


_V5E = Peak(flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
            source="Google Cloud documentation, TPU v5e")

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peak(device_kind: str) -> Peak:
    """The peaks of ``device_kind``; raises KeyError for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to chipbench/peaks.py "
                       "with their source") from None


def roofline_pct(ops: float, bytes_moved: float, seconds: float,
                 device_kind: str) -> float:
    """Least time the chip could take, over the time taken, in percent.

    The least time is the larger of ``ops`` over the peak rate and
    ``bytes_moved`` over the peak bandwidth. A share above 100% means the
    work is counted too high or the time misses part of it: it raises.
    """
    if seconds <= 0:
        raise ValueError(f"kernel time {seconds} s is not positive")
    p = peak(device_kind)
    least = max(ops / p.flops, bytes_moved / p.hbm_bytes_per_s)
    share = 100.0 * least / seconds
    if share > 100.0:
        raise ValueError(
            f"roofline share {share:.3f}% is above 100%: the work "
            f"({ops:.4g} ops, {bytes_moved:.4g} bytes) or the time "
            f"({seconds:.4g} s) is wrong")
    return share
