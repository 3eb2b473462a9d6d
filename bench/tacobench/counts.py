"""The work the algorithm must do in each pass, from shapes alone.

Counted the same whatever implements it: real queries, real rows and the
real width, never the padded shapes or the one-hot matmuls a kernel uses.

* A cell code (one half of one subspace's IMI cell) needs
  ``ceil(log2(sqrt_k) / 8)`` bytes: 1 byte for up to 256 centroids.
* Pass 1 (``schist``): every point's ``2 * n_sub`` cell codes are read once
  per batch, with the per-query centroid-distance tables
  (``2 * n_sub * q * sqrt_k`` float32) and thresholds. Its arithmetic is
  compares and counts on the vector unit, for which the chip publishes no
  peak, so its least time is the bytes over the HBM peak.
* Pass 2 (``masked_rerank``): every point's codes again, its float32 row
  and its float32 squared norm, read once per batch; ``2 * q * n * d``
  flops for the distances of every (query, point) pair.
"""
from __future__ import annotations

import math

F32 = 4


def code_bytes(sqrt_k: int) -> int:
    return max(1, math.ceil(math.log2(max(2, sqrt_k)) / 8))


def schist_bytes(q: float, n: int, n_sub: int, sqrt_k: int) -> float:
    """Least HBM bytes of one pass-1 batch of ``q`` queries."""
    codes = 2 * n_sub * n * code_bytes(sqrt_k)
    tables = 2 * n_sub * q * sqrt_k * F32 + n_sub * q * F32
    return codes + tables


def rerank_flops(q: float, n: int, d: int) -> float:
    return 2.0 * q * n * d


def rerank_bytes(q: float, n: int, d: int, n_sub: int, sqrt_k: int) -> float:
    """Least HBM bytes of one pass-2 batch of ``q`` queries."""
    return (n * d * F32 + n * F32 + 2 * n_sub * n * code_bytes(sqrt_k)
            + q * d * F32)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The larger of flops over the flop peak and bytes over the HBM peak."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
