"""The one table of hardware peaks, keyed by ``device_kind`` as JAX reports
it. A device that is not here is an error, never a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device_kind {device_kind!r}; "
                       f"add it to benchmark/peaks.py with its source") from None


def least_seconds(ops: float, nbytes: float, device_kind: str,
                  chips: int = 1) -> tuple[float, str]:
    """The least time ``chips`` such chips could take for that much work,
    and which bound binds ("flops" or "bytes")."""
    p = peak(device_kind)
    t_ops = ops / (p["flops_per_s"] * chips)
    t_bytes = nbytes / (p["bytes_per_s"] * chips)
    return (t_ops, "flops") if t_ops >= t_bytes else (t_bytes, "bytes")
