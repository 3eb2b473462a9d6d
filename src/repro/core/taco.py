"""TaCo — end-to-end index build (paper Alg. 3) and k-ANNS query (Alg. 6).

Because TaCo, SuCo and the paper's ablations differ only in which transform /
activation / selection they plug in (see repro.core.config), this module
implements the whole subspace-collision family; ``build``/``query`` read the
choice from ``SCConfig``.

This is the functional core; the lifecycle facade :class:`repro.ann.AnnIndex`
(build / save / load / searcher / engine) fronts it and is the preferred
entry point — the free functions here remain supported wrappers over the
same machinery.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import transform as T
from repro.core.activation import activation_taus
from repro.core.config import SCConfig, resolve_rerank
from repro.core.imi import IMISubspace, build_imi_subspace, split_halves
from repro.core.scoring import sc_scores
from repro.core.selection import (
    fixed_threshold_from_hist,
    query_aware_threshold,
    select_candidates,
)
from repro.obs import trace as obst
from repro.utils import (
    pairwise_sq_dists,
    register_pytree_dataclass,
    static_field,
    topk_smallest,
    tree_size_bytes,
)


@register_pytree_dataclass
@dataclasses.dataclass(frozen=True)
class SCIndex:
    """A built subspace-collision index (TaCo or SuCo family)."""

    transform: T.SubspaceTransform | None  # entropy-averaging transform (TaCo)
    dim_perm: jax.Array | None  # raw-dim permutation (SuCo, Def. 4)
    subspaces: tuple[IMISubspace, ...]
    data: jax.Array  # (n, d) original data, used for re-ranking
    sub_dims: tuple[int, ...] = static_field(default=())
    #: (n,) float32 ``||x||^2`` per point, precomputed at build() time so
    #: re-ranking can use the MXU-shaped ``||q||^2 - 2 q.x + ||x||^2`` form
    #: without a per-query norm pass (None on indexes built before this
    #: field existed — re-rank falls back to the diff-square form).
    data_norms: jax.Array | None = None

    @property
    def n(self) -> int:
        return self.data.shape[0]

    @property
    def index_bytes(self) -> int:
        """Index memory footprint (excludes the dataset itself, as in the
        paper's protocol)."""
        size = tree_size_bytes(self.subspaces)
        if self.transform is not None:
            size += tree_size_bytes(self.transform)
        if self.dim_perm is not None:
            size += int(self.dim_perm.size * self.dim_perm.dtype.itemsize)
        if self.data_norms is not None:
            size += int(self.data_norms.size * self.data_norms.dtype.itemsize)
        return size


def _project(index: SCIndex, x: jax.Array) -> jax.Array:
    if index.transform is not None:
        return T.apply_transform(index.transform, x)
    return jnp.asarray(x, jnp.float32)[:, index.dim_perm]


def _sub_slices(sub_dims: tuple[int, ...]) -> list[tuple[int, int]]:
    offs, out = 0, []
    for d in sub_dims:
        out.append((offs, offs + d))
        offs += d
    return out


def suco_dim_partition(d: int, n_subspaces: int, rng: np.random.Generator):
    """Paper Def. 4 subspace sampling: random dims without replacement,
    N_s-1 subspaces of s = floor(d/N_s) dims, the last takes the rest."""
    s = d // n_subspaces
    perm = rng.permutation(d)
    sub_dims = tuple([s] * (n_subspaces - 1) + [d - s * (n_subspaces - 1)])
    return perm.astype(np.int32), sub_dims


def _end_phase(stage: obst.Stage, outputs):
    """Wait for a build phase's outputs when its stage is recorded (a
    profiler session or a sampled ring span), so that the span holds the
    phase's device time. Unrecorded, the host runs ahead and the device's
    queue stays full: a wait per phase costs 50-90 ms of a 23 s build on a
    TPU v5e."""
    if stage.recorded:
        jax.block_until_ready(outputs)
    return outputs


def build(data: jax.Array, cfg: SCConfig) -> SCIndex:
    """Paper Algorithm 3 (plus Alg. 1/2 when cfg.transform == 'entropy').

    Traced as the stage ``taco.build`` with one child per phase:
    ``taco.build.transform``, ``taco.build.subspace`` (attribute ``i``)
    once per subspace, and ``taco.build.norms``."""
    stage = obst.default_tracer().stage
    data = jnp.asarray(data, jnp.float32)
    n, d = data.shape
    rng = jax.random.PRNGKey(cfg.seed)

    with stage("taco.build", n=n, d=d):
        with stage("taco.build.transform") as phase:
            if cfg.transform == "entropy":
                tr = T.fit_transform(data, cfg.n_subspaces, cfg.subspace_dim)
                projected = T.apply_transform(tr, data)
                perm = None
                sub_dims = (cfg.subspace_dim,) * cfg.n_subspaces
            elif cfg.transform == "none":
                tr = None
                np_rng = np.random.default_rng(cfg.seed)
                perm_np, sub_dims = suco_dim_partition(d, cfg.n_subspaces, np_rng)
                perm = jnp.asarray(perm_np)
                projected = data[:, perm]
            else:
                raise ValueError(f"unknown transform {cfg.transform!r}")
            _end_phase(phase, projected)

        subspaces = []
        for i, (lo, hi) in enumerate(_sub_slices(sub_dims)):
            with stage("taco.build.subspace", i=i) as phase:
                subspaces.append(_end_phase(phase, build_imi_subspace(
                    jax.random.fold_in(rng, i),
                    projected[:, lo:hi],
                    cfg.sqrt_k,
                    cfg.kmeans_iters,
                    cfg.kmeans_init,
                )))
        with stage("taco.build.norms") as phase:
            index = SCIndex(
                transform=tr,
                dim_perm=perm,
                subspaces=tuple(subspaces),
                data=data,
                sub_dims=sub_dims,
                data_norms=jnp.sum(data * data, axis=1),
            )
            _end_phase(phase, index.data_norms)
    return index


def _round_bf16(x: jax.Array) -> jax.Array:
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _centroid_distances(index: SCIndex, queries: jax.Array, use_kernels: bool,
                        precision: str = "f32"):
    """Per-subspace distances to both centroid halves: stacked (N_s, Q, sqrt_k).

    ``precision="bf16"`` rounds the projected queries and centroids through
    bfloat16 before the (f32-accumulated) distance computation. Rounding
    here — rather than inside each downstream op — means pass 1 (schist)
    and pass 2 (masked_rerank) consume identically derived d1s/d2s/taus, so
    their SC masks can never diverge."""
    if use_kernels:
        from repro.kernels.ops import l2dist as dist_fn
    else:
        dist_fn = pairwise_sq_dists
    pq = _project(index, queries)
    if precision == "bf16":
        pq = _round_bf16(pq)
    d1s, d2s = [], []
    for (lo, hi), sub in zip(_sub_slices(index.sub_dims), index.subspaces):
        q_sub = pq[:, lo:hi]
        s1, _ = split_halves(hi - lo)
        c1, c2 = sub.centroids1, sub.centroids2
        if precision == "bf16":
            c1, c2 = _round_bf16(c1), _round_bf16(c2)
        d1s.append(dist_fn(q_sub[:, :s1], c1))
        d2s.append(dist_fn(q_sub[:, s1:], c2))
    return jnp.stack(d1s), jnp.stack(d2s)


#: id(SCIndex) -> (weakref to the index, stacked (a1s, a2s)). Keyed by id
#: with a liveness check because SCIndex is an (unhashable) pytree
#: dataclass; the weakref callback evicts the entry when the index dies, so
#: the cache can never pin a retired snapshot's assignment arrays.
_COLLISION_CACHE: dict[int, tuple] = {}


def collision_constants(index: SCIndex):
    """Stacked (N_s, n) cell-assignment tensors (a1s, a2s) for ``index``,
    cached per index snapshot.

    The stack is query-independent: restacking it on every batch is pure
    per-batch overhead on the eager path (the jit path constant-folds it,
    but serving's stage decomposition and any non-jit caller pay it in
    full). Under tracing the cache is bypassed and the stack happens
    inline, exactly as before — detected on the RESULT, because even
    concrete closure-captured assignment arrays stack into a tracer
    inside a jit/shard_map trace, and caching a tracer would leak it."""
    key = id(index)
    hit = _COLLISION_CACHE.get(key)
    if hit is not None and hit[0]() is index:
        return hit[1]
    stacked = (
        jnp.stack([s.assign1 for s in index.subspaces]),
        jnp.stack([s.assign2 for s in index.subspaces]),
    )
    if isinstance(stacked[0], jax.core.Tracer):
        return stacked
    import weakref

    _COLLISION_CACHE[key] = (
        weakref.ref(index, lambda _r, _k=key: _COLLISION_CACHE.pop(_k, None)),
        stacked,
    )
    return stacked


def _collision_inputs(index: SCIndex, queries: jax.Array, cfg: SCConfig, *,
                      hoist: bool = True):
    """Alg. 6 lines 3-5 without the SC matrix: the per-subspace centroid
    distances, activation thresholds and stacked cell assignments that both
    the gather and the streaming masked-full pipelines consume.

    ``hoist=False`` restacks the assignment tensors inline (the
    pre-collision_constants behaviour) — kept for the before/after
    benchmark row and equivalence tests."""
    d1s, d2s = _centroid_distances(
        index, queries, cfg.use_kernels, cfg.precision
    )
    alpha_n = cfg.alpha * index.n
    taus, retrieved = [], []
    for i, sub in enumerate(index.subspaces):
        tau_i, ret_i = activation_taus(
            d1s[i], d2s[i], sub.cell_sizes, alpha_n, method=cfg.activation
        )
        taus.append(tau_i)
        retrieved.append(ret_i)
    taus = jnp.stack(taus)  # (N_s, Q)
    if hoist:
        a1s, a2s = collision_constants(index)
    else:
        a1s = jnp.stack([s.assign1 for s in index.subspaces])
        a2s = jnp.stack([s.assign2 for s in index.subspaces])
    return d1s, d2s, a1s, a2s, taus, jnp.stack(retrieved)


def compute_sc_scores(index: SCIndex, queries: jax.Array, cfg: SCConfig):
    """Collision counting (Alg. 6 lines 3-7): SC-scores (Q, n) + diagnostics."""
    d1s, d2s, a1s, a2s, taus, retrieved = _collision_inputs(index, queries, cfg)
    if cfg.use_kernels:
        from repro.kernels.ops import scscore

        sc = scscore(d1s, d2s, a1s, a2s, taus)
    else:
        sc = sc_scores(d1s, d2s, a1s, a2s, taus)
    return sc, {"taus": taus, "retrieved": retrieved}


def data_norms_of(index: SCIndex) -> jax.Array:
    """``||x||^2`` per point — precomputed at build() time, derived on the
    fly for indexes predating the ``data_norms`` field."""
    if index.data_norms is not None:
        return index.data_norms
    return jnp.sum(index.data * index.data, axis=1)


def rerank(
    data: jax.Array,
    queries: jax.Array,
    cand_ids: jax.Array,
    valid: jax.Array,
    k: int,
    data_norms: jax.Array | None = None,
):
    """Result refinement: exact distances over candidates, masked top-k.

    With ``data_norms`` (precomputed ``||x||^2``) the distances use the
    ``||q||^2 - 2 q.x + ||x||^2`` form — one fused multiply-reduce over the
    gathered candidates instead of materializing the (Q, cap, d) diff
    tensor twice (subtract + square)."""
    cand_vecs = jnp.take(data, cand_ids, axis=0)  # (Q, cap, d)
    if data_norms is None:
        diff = cand_vecs - queries[:, None, :]
        dists = jnp.sum(diff * diff, axis=-1)
    else:
        q_norms = jnp.sum(queries * queries, axis=1)  # (Q,)
        cross = jnp.einsum("qcd,qd->qc", cand_vecs, queries,
                           precision=jax.lax.Precision.HIGHEST)
        dists = jnp.maximum(
            q_norms[:, None] - 2.0 * cross + jnp.take(data_norms, cand_ids), 0.0
        )
    dists = jnp.where(valid, dists, jnp.inf)
    top_d, pos = topk_smallest(dists, k)
    top_ids = jnp.take_along_axis(cand_ids, pos, axis=1)
    # invalid slots (fewer candidates than k) → id -1
    top_valid = jnp.isfinite(top_d)
    return jnp.where(top_valid, top_ids, -1), jnp.where(top_valid, top_d, jnp.inf)


def query(index: SCIndex, queries: jax.Array, cfg: SCConfig, *, k: int | None = None):
    """Paper Algorithm 6: returns (ids (Q, k), sq_dists (Q, k))."""
    ids, dists, _stats = query_with_stats(index, queries, cfg, k=k)
    return ids, dists


def query_with_stats(
    index: SCIndex, queries: jax.Array, cfg: SCConfig, *, k: int | None = None
):
    """Alg. 6 with diagnostics. ``k`` overrides ``cfg.k`` per call without
    rebuilding the config (it stays a Python int — static under jit — so
    callers serving many result counts key their jit cache on it instead of
    recompiling per request; see repro.serving.ann_engine)."""
    k = cfg.k if k is None else int(k)
    queries = jnp.asarray(queries, jnp.float32)
    if resolve_rerank(cfg) == "masked_full":
        return _query_masked_full(index, queries, cfg, k)
    sc, stats = compute_sc_scores(index, queries, cfg)
    # floor the cap at the runtime k so large-k overrides stay servable
    cap = min(index.n, max(cfg.cap_for(index.n), k))
    cand_ids, valid, thresh, count = select_candidates(
        sc, float(cfg.beta * index.n), cfg.n_subspaces, cap, mode=cfg.selection
    )
    ids, dists = rerank(index.data, queries, cand_ids, valid, k, data_norms_of(index))
    stats = dict(
        stats,
        sc_threshold=thresh,
        candidate_count=jnp.minimum(count, cap),  # actually re-ranked
        candidate_demand=count,  # pre-clamp Alg. 5 demand (may exceed cap)
        truncated=count > cap,  # strictly: count == cap drops nothing
        sc=sc,
    )
    return ids, dists, stats


def _query_masked_full(index: SCIndex, queries: jax.Array, cfg: SCConfig, k: int):
    """Streaming two-pass query (Alg. 6 with Alg. 5 in histogram space).

    Pass 1 fuses SC-score computation with per-query histogram accumulation
    (``kernels.schist``): the (Q, n) SC matrix never materializes — only the
    (Q, N_s+1) histogram leaves the blockwise loop. The Alg. 5 threshold is
    read off the histogram (query-aware mode) or its top-down cumsum (fixed
    mode). Pass 2 (``kernels.masked_rerank``) recomputes SC per block,
    computes exact squared distances by matmul against the precomputed
    ``||x||^2`` norms, masks by ``SC >= thresh`` and merges each block into a
    running per-query top-k — no candidate gather, no static cap, so
    ``truncated`` is structurally impossible and the results carry the true
    dynamic-shape Alg. 5 semantics even where the gather path truncates.

    Stats parity with the gather path except ``sc`` (whose absence is the
    point) and ``candidate_count`` == ``candidate_demand`` (nothing is ever
    clamped). The Pallas pass 2 adds ``rerank_blocks``, its int32
    ``(merged, run)`` grid-step counts (see ``kernels.masked_rerank``).

    Both passes pick their implementation by platform (``impl="auto"``):
    the Pallas kernels on a TPU, their streaming jnp twins elsewhere.
    """
    from repro.kernels import ops

    d1s, d2s, a1s, a2s, taus, retrieved = _collision_inputs(index, queries, cfg)
    hist = ops.schist(d1s, d2s, a1s, a2s, taus)
    beta_n = float(cfg.beta * index.n)
    if cfg.selection == "query_aware":
        thresh, demand = query_aware_threshold(hist, beta_n, cfg.n_subspaces)
    elif cfg.selection == "fixed":
        thresh, demand = fixed_threshold_from_hist(hist, beta_n, index.n)
    else:
        raise ValueError(f"unknown selection mode {cfg.selection!r}")
    ids, dists, rerank_blocks = ops.masked_rerank(
        d1s, d2s, a1s, a2s, taus, thresh,
        index.data, data_norms_of(index), queries, k,
        precision=cfg.precision, counts=True,
    )
    stats = {
        "taus": taus,
        "retrieved": retrieved,
        "sc_threshold": thresh,
        "candidate_count": demand,
        "candidate_demand": demand,
        "truncated": jnp.zeros(queries.shape[0], bool),
    }
    if rerank_blocks is not None:
        stats["rerank_blocks"] = rerank_blocks
    return ids, dists, stats


def make_query_fn(index: SCIndex, cfg: SCConfig, *, k: int | None = None):
    """A jit-compiled ``fn(queries)`` over ``index``. The index is an
    argument of the executable, not a constant baked into it."""
    fn = jax.jit(functools.partial(query, cfg=cfg, k=k))
    return functools.partial(fn, index)
