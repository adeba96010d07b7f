"""Peaks of the chips the benchmark runs on, and the bytes and operations
the WARP algorithm needs for a query, whatever implements it.

A query's stage-2 work is its real candidates: over its active query
tokens and their probed clusters, the sum of the clusters' true sizes.
Each candidate token costs its packed residual codes (``dim * nbits / 8``
bytes) and its 4-byte doc id read from HBM, and ``2 * dim`` operations
(one multiply-add per dimension of the implicit decompression).
"""

from __future__ import annotations

# One chip of each kind. Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
}

DOC_ID_BYTES = 4


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown chip is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add a row with its source") from None


def candidate_bytes(tokens: int, dim: int, nbits: int) -> int:
    return tokens * (dim * nbits // 8 + DOC_ID_BYTES)


def candidate_ops(tokens: int, dim: int) -> int:
    return tokens * 2 * dim


def least_time(n_bytes: float, n_ops: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_mem = n_bytes / peak["hbm_bytes_per_s"]
    t_ops = n_ops / peak["flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
