"""Published peaks of one chip, keyed by JAX's ``Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  (The program's ``launch/hlo_analysis.PEAKS`` holds the same
numbers; this copy is the benchmark's own.)  A kind that is not here is an
error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bytes_per_s": 200e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


def roofline_seconds(flops: float, nbytes: float, device_kind: str):
    """The least time the chip could take for the work: (seconds, bound),
    bound being "compute" or "memory"."""
    p = peak(device_kind)
    t_flops, t_bytes = flops / p["flops"], nbytes / p["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
