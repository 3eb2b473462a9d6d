"""Mesh-distributed TaCo — corpus-sharded index build and query (shard_map).

Scale story (DESIGN.md §3): the corpus is sharded along the mesh's data axes
(n_local = n / n_data_shards points per device); queries are sharded along the
model axis. Per device:

  build:  covariance  -> psum of local (sum, outer-sum) stats
          K-means     -> local segment sums + psum (centroids replicated)
          cell sizes  -> psum of local bincounts (activation needs GLOBAL
                         cell populations so tau has the paper's semantics)
  query:  activation thresholds tau are computed redundantly on every device
          (inputs are replicated and tiny: (Q, sqrt_k) distances; alpha*n
          stays GLOBAL so tau has the paper's semantics);
          SC-scores run on LOCAL points only; the per-query SC-score
          histograms are psummed over the data axes so every shard applies
          the SAME Algorithm-5 threshold against the GLOBAL beta*n budget —
          the total re-ranked candidate count therefore equals the
          single-device count (<= ~beta*n_global) no matter the shard
          count, and each shard re-ranks exactly its share of the global
          candidate set (per-shard static cap: 4*beta*n_local — the
          budget-derived cap over the shard's share — floored at k);
          each device emits its local top-k, one all-gather over the data
          axes (k * n_shards (id, dist) pairs — bytes, not vectors), then a
          global top-k. Exact: re-rank distances are exact per shard, so
          sharded results are identical to single-device results whenever
          no shard truncates (surfaced via the per-shard stats).

Communication per query batch: one psum of (Q_local, N_s+1) int32 histograms
plus one all-gather of (Q_local, shards*k) pairs. There is NO all-to-all and
no point-vector movement — this is what makes the subspace-collision family
a good fit for 1000+ node serving.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.activation import activation_taus
from repro.core.config import SCConfig, resolve_rerank
from repro.core.imi import split_halves
from repro.core.scoring import sc_scores
from repro.core.selection import (
    compact_above_threshold,
    fixed_threshold_from_hist,
    query_aware_threshold,
    sc_histogram,
    select_candidates,
)
from repro.core.taco import (
    SCIndex,
    _project,
    _sub_slices,
    collision_constants,
    data_norms_of,
    rerank,
)
from repro.utils import pairwise_sq_dists, topk_smallest


def index_pspecs(index: SCIndex, data_axes) -> SCIndex:
    """PartitionSpec pytree matching SCIndex: corpus-dependent leaves sharded
    over the data axes, everything else replicated."""
    da = data_axes

    def sub_spec(sub):
        return type(sub)(
            centroids1=P(),
            centroids2=P(),
            assign1=P(da),
            assign2=P(da),
            cell_sizes=P(),  # GLOBAL cell sizes, replicated
        )

    tr_spec = None
    if index.transform is not None:
        tr_spec = type(index.transform)(
            mean=P(),
            basis=P(),
            eigvals=P(),
            n_subspaces=index.transform.n_subspaces,
            subspace_dim=index.transform.subspace_dim,
        )
    return SCIndex(
        transform=tr_spec,
        dim_perm=None if index.dim_perm is None else P(),
        subspaces=tuple(sub_spec(s) for s in index.subspaces),
        data=P(da, None),
        sub_dims=index.sub_dims,
        data_norms=None if index.data_norms is None else P(da),
    )


def per_shard_cap(cfg: SCConfig, n_local: int, k: int) -> int:
    """Static per-shard candidate cap for the gather re-rank: the shard's
    share of the global budget (4*beta*n_local, the same 4x headroom as
    ``cfg.cap_for``) floored at the runtime k each shard needs to emit its
    local top-k; an explicit ``candidate_cap`` is a per-shard cap (as in
    the billion-scale dry-run config). One definition shared by the
    shard_map query below and host-side stats consumers
    (:class:`repro.ann.searcher.ShardedSearcher`)."""
    base = (
        cfg.candidate_cap
        if cfg.candidate_cap is not None
        else math.ceil(4 * cfg.beta * n_local)
    )
    return min(n_local, max(base, k))


def make_distributed_query_with_stats(
    mesh,
    cfg: SCConfig,
    index: SCIndex,
    n_global: int,
    data_axes=("data",),
    query_axes=("model",),
    k: int | None = None,
):
    """Returns a jit-able ``fn(index, queries) -> (ids, sq_dists, stats)``
    where the index is sharded per :func:`index_pspecs` and queries over
    query_axes. ``k`` overrides ``cfg.k`` per closure (static Python int —
    mirrors :func:`repro.core.taco.query_with_stats`, so the serving engine
    keys its jit cache on it).

    ``stats`` (all shapes (Q, S) for S data shards, shard-major in
    all-gather order):

      * ``shard_candidates`` — pre-clamp per-shard candidate demand; sums
        over shards to the single-device global demand for query-aware
        selection (the histogram psum makes every shard cut at the global
        Algorithm-5 threshold).
      * ``shard_truncated``  — per-shard demand exceeded the shard's static
        cap (``max(4*beta*n_local, k)``, or ``candidate_cap`` per shard);
        any truncation voids the sharded == single-device exactness
        guarantee. With ``cfg.rerank == "masked_full"`` each shard runs the
        streaming masked re-rank over ALL its above-threshold points
        (kernels/masked_rerank.py) — no per-shard cap exists and this stat
        is always False. Note ``resolve_rerank``: ``"auto"`` keeps the
        gather path for sharded local queries.

    Billion-scale configuration: shard the corpus over ALL mesh axes
    (``data_axes=("data", "model")``, 256/512-way — 1B x 128d = 2 GB/device)
    and replicate the query batch (``query_axes=()``); the combine all-gather
    then runs over every axis but still moves only (Q, shards*k) id/dist
    pairs."""
    k = cfg.k if k is None else int(k)
    query_axes = tuple(query_axes)
    data_axes = tuple(data_axes)
    specs = index_pspecs(index, data_axes)
    alpha_n = cfg.alpha * n_global
    beta_n = float(cfg.beta * n_global)
    n_shards = math.prod(mesh.shape[ax] for ax in data_axes)
    if k > n_global // n_shards:
        raise ValueError(
            f"k={k} exceeds the {n_global // n_shards}-point shard: every "
            f"shard must hold at least k points to emit its local top-k"
        )

    rerank_mode = resolve_rerank(cfg, distributed=True)

    def local_query(idx: SCIndex, queries: jax.Array):
        n_local = idx.data.shape[0]
        pq = _project(idx, queries)
        d1s, d2s, taus = [], [], []
        for (lo, hi), sub in zip(_sub_slices(idx.sub_dims), idx.subspaces):
            s1, _ = split_halves(hi - lo)
            d1 = pairwise_sq_dists(pq[:, lo:hi][:, :s1], sub.centroids1)
            d2 = pairwise_sq_dists(pq[:, lo:hi][:, s1:], sub.centroids2)
            tau, _ = activation_taus(d1, d2, sub.cell_sizes, alpha_n, method=cfg.activation)
            d1s.append(d1)
            d2s.append(d2)
            taus.append(tau)
        d1s, d2s, taus = jnp.stack(d1s), jnp.stack(d2s), jnp.stack(taus)
        # collision_constants bypasses its cache for tracers (shard_map'd
        # assignment arrays), so this stays an inline stack under the mesh
        # while sharing the hoisted-constant code path with core/taco.py.
        a1s, a2s = collision_constants(idx)

        if rerank_mode == "masked_full":
            # Streaming masked-full per shard: local SC histograms are
            # psummed (same global-threshold discipline as the gather
            # branch), then every shard re-ranks ALL its above-threshold
            # points with the blockwise masked matmul — no per-shard cap,
            # so per-shard truncation is structurally impossible. For
            # fixed selection this IS the global rank cut the gather
            # branch only approximates by an even budget split (ties at
            # the threshold level are all re-ranked). Both passes pick
            # Pallas or the jnp twins by platform, as on one device.
            from repro.kernels import ops

            local_hist = ops.schist(d1s, d2s, a1s, a2s, taus)
            hist = jax.lax.psum(local_hist, data_axes)
            if cfg.selection == "query_aware":
                thresh, _ = query_aware_threshold(hist, beta_n, cfg.n_subspaces)
            elif cfg.selection == "fixed":
                thresh, _ = fixed_threshold_from_hist(hist, beta_n, n_global)
            else:
                raise ValueError(f"unknown selection mode {cfg.selection!r}")
            levels = jnp.arange(cfg.n_subspaces + 1)[None, :]
            count = jnp.sum(
                jnp.where(levels >= thresh[:, None], local_hist, 0), axis=1
            ).astype(jnp.int32)
            ids_local, dists_local = ops.masked_rerank(
                d1s, d2s, a1s, a2s, taus, thresh,
                idx.data, data_norms_of(idx), queries, k,
            )
            truncated = jnp.zeros_like(count, dtype=bool)
        else:
            sc = sc_scores(d1s, d2s, a1s, a2s, taus)
            # NOT floored at cap_for's 4*cfg.k, which would scale total
            # static re-rank work as S*4k in the many-shard regime.
            cap = per_shard_cap(cfg, n_local, k)
            if cfg.selection == "query_aware":
                # The budget is GLOBAL: psum the local SC-score histograms so
                # every shard walks Algorithm 5 on the global histogram against
                # the global beta*n budget and cuts at the same threshold.
                # Total selected across shards == the single-device count —
                # NOT S * beta * n as the old per-shard-budget code did.
                hist = jax.lax.psum(sc_histogram(sc, cfg.n_subspaces), data_axes)
                thresh, _ = query_aware_threshold(hist, beta_n, cfg.n_subspaces)
                cand_ids, valid, count = compact_above_threshold(sc, thresh, cap)
            else:
                # fixed selection ranks by LOCAL score order, so the global
                # rank cut is approximated by an even split of the budget.
                cand_ids, valid, _t, count = select_candidates(
                    sc, beta_n / n_shards, cfg.n_subspaces, cap, mode=cfg.selection
                )
            ids_local, dists_local = rerank(
                idx.data, queries, cand_ids, valid, k, data_norms_of(idx)
            )
            truncated = count > cap

        # globalize ids and combine across data shards
        shard_off = jnp.int32(0)
        for ax in data_axes:
            shard_off = shard_off * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        ids_global = jnp.where(ids_local >= 0, ids_local + shard_off * n_local, -1)
        all_ids = jax.lax.all_gather(ids_global, data_axes, axis=1, tiled=True)
        all_d = jax.lax.all_gather(dists_local, data_axes, axis=1, tiled=True)
        top_d, pos = topk_smallest(all_d, k)
        stats = {
            "shard_candidates": jax.lax.all_gather(
                count[:, None], data_axes, axis=1, tiled=True
            ),
            "shard_truncated": jax.lax.all_gather(
                truncated[:, None], data_axes, axis=1, tiled=True
            ),
        }
        return jnp.take_along_axis(all_ids, pos, axis=1), top_d, stats

    q_spec = P(query_axes, None)
    fn = shard_map(
        local_query,
        mesh=mesh,
        in_specs=(specs, q_spec),
        out_specs=(q_spec, q_spec, {"shard_candidates": q_spec, "shard_truncated": q_spec}),
        check_vma=False,
    )
    return jax.jit(fn)


def make_distributed_query(
    mesh,
    cfg: SCConfig,
    index: SCIndex,
    n_global: int,
    data_axes=("data",),
    query_axes=("model",),
):
    """Stats-free ``fn(index, queries) -> (ids, sq_dists)`` — see
    :func:`make_distributed_query_with_stats` (XLA dead-code-eliminates the
    stat gathers from this variant)."""
    stats_fn = make_distributed_query_with_stats(
        mesh, cfg, index, n_global, data_axes=data_axes, query_axes=query_axes
    )

    @jax.jit
    def fn(idx: SCIndex, queries: jax.Array):
        ids, dists, _stats = stats_fn(idx, queries)
        return ids, dists

    return fn


# ---------------------------------------------------------------------------
# Distributed index build pieces (each one a compile unit for the dry-run)
# ---------------------------------------------------------------------------


def make_distributed_cov(mesh, n_global: int, data_axes=("data",)):
    """Global mean/covariance from sharded data: psum of local moments."""

    def local_cov(x):
        s = jnp.sum(x, axis=0)
        outer = jnp.matmul(x.T, x, precision=jax.lax.Precision.HIGHEST)
        s = jax.lax.psum(s, data_axes)
        outer = jax.lax.psum(outer, data_axes)
        mean = s / n_global
        cov = (outer - n_global * jnp.outer(mean, mean)) / (n_global - 1)
        return mean, cov

    fn = shard_map(
        local_cov,
        mesh=mesh,
        in_specs=(P(data_axes, None),),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def make_distributed_lloyd(mesh, data_axes=("data",)):
    """One Lloyd super-step over sharded (projected) data; centroids replicated."""

    def local_step(x, centroids):
        d = pairwise_sq_dists(x, centroids)
        assign = jnp.argmin(d, axis=1)
        k = centroids.shape[0]
        sums = jax.ops.segment_sum(x, assign, num_segments=k)
        counts = jax.ops.segment_sum(jnp.ones(x.shape[0], jnp.float32), assign, num_segments=k)
        sums = jax.lax.psum(sums, data_axes)
        counts = jax.lax.psum(counts, data_axes)
        new_c = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centroids)
        return new_c, assign.astype(jnp.int32)

    fn = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(data_axes, None), P()),
        out_specs=(P(), P(data_axes)),
        check_vma=False,
    )
    return jax.jit(fn)


def make_distributed_cell_sizes(mesh, sqrt_k: int, data_axes=("data",)):
    """Global IMI cell populations from sharded assignments."""

    def local_sizes(a1, a2):
        cell = a1.astype(jnp.int32) * sqrt_k + a2.astype(jnp.int32)
        local = jnp.zeros((sqrt_k * sqrt_k,), jnp.int32).at[cell].add(1)
        return jax.lax.psum(local, data_axes).reshape(sqrt_k, sqrt_k)

    fn = shard_map(
        local_sizes,
        mesh=mesh,
        in_specs=(P(data_axes), P(data_axes)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)
