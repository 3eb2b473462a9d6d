"""Configuration for subspace-collision methods (TaCo, SuCo and ablations).

The framework is composable: TaCo, SuCo, and the paper's three ablations are
all points in the same config space (paper §5.1 "Benchmark Methods"):

  method      transform   activation   selection
  ---------   ---------   ----------   -----------
  TaCo        entropy     sort (SDA)   query_aware
  SuCo        none        linear (DA)  fixed
  SuCo-DT     entropy     linear (DA)  fixed
  SuCo-CS     none        linear (DA)  query_aware
  SuCo-QS     none        sort (SDA)   query_aware
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class SCConfig:
    """Parameters of the subspace-collision framework (paper Table 1)."""

    n_subspaces: int = 6  # N_s
    subspace_dim: int = 8  # s
    n_clusters: int = 1024  # K (total IMI cells; sqrt(K) per half)
    kmeans_iters: int = 10  # t
    alpha: float = 0.05  # collision ratio
    beta: float = 0.005  # re-rank ratio
    k: int = 50  # result count
    transform: str = "entropy"  # 'entropy' (TaCo) | 'none' (SuCo)
    activation: str = "sort"  # 'sort' | 'heap' | 'linear'
    selection: str = "query_aware"  # 'query_aware' | 'fixed'
    kmeans_init: str = "random"  # 'random' | 'kmeans++'
    candidate_cap: int | None = None  # None → auto from beta & k
    seed: int = 0
    #: Pallas l2dist centroid distances and the gather path's scscore; the
    #: masked_full passes pick Pallas by platform regardless
    use_kernels: bool = False
    #: candidate re-rank strategy:
    #:   'gather'      — Alg. 5 compaction into `cap` static slots + a
    #:                   (Q, cap, d) gather (may truncate beyond cap);
    #:   'masked_full' — two-pass streaming pipeline: blockwise SC-score +
    #:                   histogram (pass 1), then a masked full-matmul
    #:                   re-rank with a running per-query top-k (pass 2).
    #:                   No candidate cap, so `truncated` is structurally
    #:                   impossible; no (Q, n) or (Q, cap, d) intermediate.
    #:   'auto'        — masked_full for single-device queries, gather for
    #:                   corpus-sharded local queries (billion-scale shards
    #:                   keep the gather path, see ROADMAP).
    rerank: str = "gather"
    #: numeric precision of the streamed data/centroid tiles on the query
    #: path (kernels + jnp-stream twins accumulate in f32 either way):
    #:   'f32'  — default; every bitwise-determinism gate holds.
    #:   'bf16' — round centroid-distance inputs and the re-rank matmul
    #:            operands through bfloat16, halving HBM traffic for the
    #:            dominant contractions. Candidate *selection* may differ
    #:            from f32 (gated by a recall-parity sweep,
    #:            tests/test_precision.py); returned distances stay exact
    #:            f32 because finalize_topk recomputes them from the
    #:            original vectors.
    precision: str = "f32"

    def __post_init__(self):
        if self.precision not in ("f32", "bf16"):
            raise ValueError(
                f"precision must be 'f32' or 'bf16', got {self.precision!r}"
            )

    @property
    def sqrt_k(self) -> int:
        r = math.isqrt(self.n_clusters)
        if r * r != self.n_clusters:
            raise ValueError(f"n_clusters={self.n_clusters} must be a perfect square")
        return r

    def cap_for(self, n: int) -> int:
        if self.candidate_cap is not None:
            return min(self.candidate_cap, n)
        # Alg. 5 can include up to one over-budget level; 4x beta*n + headroom
        # keeps truncation (which tests assert against) out of normal operation.
        return int(min(n, max(4 * self.k, math.ceil(4 * self.beta * n))))


def resolve_rerank(cfg: SCConfig, *, distributed: bool = False) -> str:
    """Resolve ``cfg.rerank`` to a concrete strategy for one call site.

    ``auto`` picks the streaming masked-full pipeline for single-device
    queries and keeps the gather path for corpus-sharded local queries
    (billion-scale shards re-rank ~beta*n_local points, where the full
    n_local-column matmul would dominate).
    """
    mode = cfg.rerank
    if mode == "auto":
        return "gather" if distributed else "masked_full"
    if mode not in ("gather", "masked_full"):
        raise ValueError(f"unknown rerank mode {mode!r}")
    return mode


def taco_config(**kw) -> SCConfig:
    return SCConfig(**{**dict(transform="entropy", activation="sort", selection="query_aware"), **kw})


def suco_config(**kw) -> SCConfig:
    return SCConfig(**{**dict(transform="none", activation="linear", selection="fixed"), **kw})


def suco_dt_config(**kw) -> SCConfig:
    return SCConfig(**{**dict(transform="entropy", activation="linear", selection="fixed"), **kw})


def suco_cs_config(**kw) -> SCConfig:
    return SCConfig(**{**dict(transform="none", activation="linear", selection="query_aware"), **kw})


def suco_qs_config(**kw) -> SCConfig:
    return SCConfig(**{**dict(transform="none", activation="sort", selection="query_aware"), **kw})


ABLATIONS = {
    "taco": taco_config,
    "suco": suco_config,
    "suco-dt": suco_dt_config,
    "suco-cs": suco_cs_config,
    "suco-qs": suco_qs_config,
}
