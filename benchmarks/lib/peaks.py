"""Published peaks of the chips the benchmark knows, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: an MFU against another chip's peak is a wrong number."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def mfu_pct(flops_per_sample: float, samples_per_s: float,
            device_kind: str, chips: int = 1) -> float:
    """Model FLOP/s utilisation: required FLOPs (no recomputation) times
    the measured rate over chips times the bf16 peak, in percent."""
    return 100.0 * flops_per_sample * samples_per_s / (
        chips * peak(device_kind)["bf16_flops"])


def roofline_pct(flops: float, bytes_moved: float, seconds: float,
                 device_kind: str) -> tuple:
    """(share of the roofline in percent, which bound it): the least time
    the chip could take over the time it took."""
    p = peak(device_kind)
    t_flops = flops / p["bf16_flops"]
    t_bytes = bytes_moved / p["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
