"""Peaks of the chips the benchmark runs on, and the least time of a product.

A device that is not in ``PEAKS`` is an error, never a default: a roofline
share against a guessed peak is no measurement.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peaks_for", "plain_matmul_work", "least_time_s"]

PEAKS = {
    # jax.devices()[0].device_kind of a TPU v5e chip
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': per-chip peaks",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def plain_matmul_work(v: int, r: int, t: int) -> tuple[int, int]:
    """(operations, bytes) of the plain exact C = A^T B, A (v, r), B (v, t).

    The plain product reads A and B as int8 and writes C as int32, and makes
    one multiply and one add per term.  This is the work of the product
    itself, whatever computes it, so a change to the coded pipeline cannot
    move it.
    """
    return 2 * v * r * t, v * r + v * t + 4 * r * t


def least_time_s(ops: float, nbytes: float, peaks: dict,
                 rate: str = "int8_ops_per_s") -> tuple[float, str]:
    """(least seconds, bound) for ``ops`` at the ``rate`` peak and ``nbytes``
    at peak HBM bandwidth; the bound is ``"compute"`` or ``"memory"``."""
    compute = ops / peaks[rate]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
