"""Searchers — placement + the ``(bucket, k, cfg)`` executable cache.

A :class:`Searcher` is the one place that turns a built :class:`SCIndex`
into compiled query executables. It owns

  * **placement** — where the index lives: on the default device
    (:class:`SingleDeviceSearcher`) or corpus-sharded over a mesh
    (:class:`ShardedSearcher`, via :mod:`repro.core.distributed`);
  * **the executable LRU** — one cache keyed ``(bucket, k, cfg)``; ``k``
    and per-call ``beta``/``rerank`` overrides become new keys, steady-state
    traffic with stable parameters never recompiles. ``(bucket, k, cfg)``
    is caller-controlled, so without eviction a stream of novel beta values
    would grow executable memory without bound;
  * **bucketing** — direct ``search()`` calls are padded up the
    :data:`~repro.serving.batching.ANN_BATCH_BUCKETS` ladder so repeated
    ad-hoc batch sizes share executables (padding cannot change real-row
    results: every row of the TaCo query path is independent).

Both the :class:`repro.serving.ann_engine.AnnServingEngine` backends and
direct :meth:`search` / :meth:`search_with_stats` calls run through the same
:meth:`run_padded`, so the engine and the ad-hoc path share executables
bucket-for-bucket. Construct searchers via :meth:`repro.ann.AnnIndex.searcher`
or :func:`make_searcher`.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import SCConfig
from repro.core.taco import SCIndex, query_with_stats
from repro.batching import ANN_BATCH_BUCKETS, bucket_size, pad_rows
from repro.obs import metrics as obsm
from repro.obs import trace as obst

# Process-wide searcher metric families (repro.obs registry): executable
# LRU behaviour and autotune warm-loads, across every searcher instance.
_M_COMPILES = obsm.counter(
    "taco_searcher_compiles_total",
    "Query executables compiled (one per new (bucket, k, cfg) key)",
)
_M_FN_HITS = obsm.counter(
    "taco_searcher_fn_cache_hits_total",
    "Executable-cache hits (batch reused a compiled query fn)",
)
_M_AUTOTUNE = obsm.gauge(
    "taco_searcher_autotune_entries_loaded",
    "Autotune (bq, bn) winners warm-loaded at searcher construction",
)


@dataclasses.dataclass
class AnnBatchResult:
    """What :meth:`Searcher.run_padded` returns for one padded batch
    (one row per slot, including pad slots)."""

    ids: np.ndarray  # (B, k) int32
    dists: np.ndarray  # (B, k) float32
    truncated: np.ndarray  # (B,) bool
    candidate_count: np.ndarray | None = None  # (B,) int32 re-ranked per query
    shard_candidates: np.ndarray | None = None  # (B, S) int32
    shard_truncated: np.ndarray | None = None  # (B, S) bool
    #: pass 2's (grid steps that merged a point block, grid steps run)
    rerank_blocks: tuple[int, int] | None = None


def effective_query_params(
    cfg: SCConfig, k=None, beta=None, rerank=None
) -> tuple[int, SCConfig]:
    """Resolve per-call ``k``/``beta``/``rerank`` overrides to the concrete
    ``(k, cfg)`` pair that keys the executable cache. One definition shared
    by :meth:`Searcher.search` and the serving engine's request grouping, so
    the 'same' request always lands on the same executable."""
    if beta is not None and float(beta) != cfg.beta:
        cfg = dataclasses.replace(cfg, beta=float(beta))
    if rerank is not None and rerank != cfg.rerank:
        cfg = dataclasses.replace(cfg, rerank=rerank)
    return cfg.k if k is None else int(k), cfg


class Searcher:
    """Compiled-query front end over one placement of an :class:`SCIndex`."""

    #: data shards the corpus is split over (1 = no sharding)
    shards: int = 1

    def __init__(
        self,
        index: SCIndex,
        cfg: SCConfig | None = None,
        *,
        max_cached_fns: int = 64,
        buckets=ANN_BATCH_BUCKETS,
        autotune_cache: str | None = None,
    ):
        self.index = index
        self.cfg = cfg
        self.max_cached_fns = int(max_cached_fns)
        # Warm the kernel autotune cache once so the first compile picks up
        # pre-tuned (bq, bn) winners instead of searching or defaulting.
        self.autotune_entries_loaded = 0
        if autotune_cache is not None:
            from repro.kernels.autotune import load_cache as _load_autotune

            self.autotune_entries_loaded = _load_autotune(autotune_cache)
            _M_AUTOTUNE.set(self.autotune_entries_loaded)
        self.buckets = tuple(buckets)
        self._fns: OrderedDict = OrderedDict()  # (bucket, k, cfg) -> callable
        self.compile_counts: dict = {}  # same key -> #times compiled

    # ------------------------------------------------------------- cache --
    def fn_for(self, bucket: int, k: int, cfg: SCConfig):
        """The compiled executable for one ``(bucket, k, cfg)`` key (LRU)."""
        key = (bucket, k, cfg)
        if key not in self._fns:
            self._fns[key] = self._compile(bucket, k, cfg)
            self.compile_counts[key] = self.compile_counts.get(key, 0) + 1
            _M_COMPILES.inc()
            while len(self._fns) > self.max_cached_fns:
                self._fns.popitem(last=False)
        else:
            self._fns.move_to_end(key)
            _M_FN_HITS.inc()
        return self._fns[key]

    def _compile(self, bucket: int, k: int, cfg: SCConfig):
        raise NotImplementedError

    def _run_executable(self, bucket: int, k: int, cfg: SCConfig, index,
                        queries):
        """Run the ``(bucket, k, cfg)`` executable on ``index`` and copy its
        outputs to the host, as three stages: ``taco.searcher.dispatch``
        (``taco.searcher.compile`` on a key new to this searcher), then
        ``taco.searcher.device`` until the outputs are ready, then
        ``taco.searcher.fetch``."""
        stage = obst.default_tracer().stage
        new_key = (bucket, k, cfg) not in self._fns
        with stage("taco.searcher.compile" if new_key else "taco.searcher.dispatch"):
            out = self.fn_for(bucket, k, cfg)(index, jnp.asarray(queries))
        with stage("taco.searcher.device"):
            jax.block_until_ready(out)
        with stage("taco.searcher.fetch"):
            return jax.tree.map(np.asarray, out)

    def run_padded(
        self, bucket: int, k: int, cfg: SCConfig, queries: np.ndarray
    ) -> AnnBatchResult:
        """Execute one already-padded ``(bucket, d)`` query batch."""
        raise NotImplementedError

    # ------------------------------------------------------------- limits --
    # Request-validation surface for the serving engine: the engine asks the
    # searcher (not the index it happened to be constructed with) because a
    # mutable searcher's corpus grows and shrinks under it.
    @property
    def dim(self) -> int:
        """Query dimensionality this searcher accepts."""
        return self.index.data.shape[1]

    @property
    def max_k(self) -> int:
        """Largest servable per-request ``k``."""
        return self.index.n

    def extra_telemetry(self) -> dict:
        """Searcher-specific keys merged into the engine's telemetry()."""
        return {}

    def probe_corpus(self):
        """(vectors, ids) the engine's recall probes score against — the
        corpus THIS searcher currently serves, so probes stay truthful
        across engine index swaps."""
        data = np.asarray(self.index.data)
        return data, np.arange(data.shape[0], dtype=np.int64)

    # ------------------------------------------------------------ search --
    def _effective(self, k, beta, rerank) -> tuple[int, SCConfig]:
        if self.cfg is None:
            raise ValueError(
                "this Searcher was built without a default SCConfig; "
                "construct it with cfg=... (AnnIndex.searcher does)"
            )
        return effective_query_params(self.cfg, k, beta, rerank)

    def search_with_stats(self, queries, *, k=None, beta=None, rerank=None):
        """``(ids (Q, k), sq_dists (Q, k), stats)`` — uniform across
        placements. ``stats`` always carries ``truncated`` (Q,) and
        ``candidate_count`` (Q,); sharded placement adds the per-shard
        ``shard_candidates`` / ``shard_truncated`` splits (Q, S).

        A single (d,) query vector is accepted and returns (k,) results.
        """
        k, cfg = self._effective(k, beta, rerank)
        q = np.asarray(queries, np.float32)
        single = q.ndim == 1
        if single:
            q = q[None]
        n_rows = q.shape[0]
        bucket = bucket_size(n_rows, self.buckets)
        res = self.run_padded(bucket, k, cfg, pad_rows(q, bucket))
        stats = {"truncated": res.truncated[:n_rows]}
        if res.candidate_count is not None:
            stats["candidate_count"] = res.candidate_count[:n_rows]
        if res.shard_candidates is not None:
            stats["shard_candidates"] = res.shard_candidates[:n_rows]
            stats["shard_truncated"] = res.shard_truncated[:n_rows]
        ids, dists = res.ids[:n_rows], res.dists[:n_rows]
        if single:
            ids, dists = ids[0], dists[0]
            stats = {name: s[0] for name, s in stats.items()}
        return ids, dists, stats

    def search(self, queries, *, k=None, beta=None, rerank=None):
        """``(ids (Q, k), sq_dists (Q, k))`` — see :meth:`search_with_stats`."""
        ids, dists, _stats = self.search_with_stats(
            queries, k=k, beta=beta, rerank=rerank
        )
        return ids, dists


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def single_device_query(index: SCIndex, queries, *, cfg: SCConfig, k: int):
    """The single-device query executable: ``(ids, dists, truncated,
    candidate_count, rerank_blocks)``, the last None where pass 2 reports
    no counts. The index is an argument, not a constant, so the corpus
    never enters the HLO and every searcher over an index of the same
    shapes shares one executable per ``(bucket, k, cfg)``."""
    ids, dists, stats = query_with_stats(index, queries, cfg, k=k)
    # only the O(Q) stats leave the device; the (Q, n) SC matrix stays
    # internal to the executable
    return (ids, dists, stats["truncated"], stats["candidate_count"],
            stats.get("rerank_blocks"))


class SingleDeviceSearcher(Searcher):
    """Default-device execution of :func:`single_device_query`."""

    def _compile(self, bucket: int, k: int, cfg: SCConfig):
        return functools.partial(single_device_query, cfg=cfg, k=k)

    def run_padded(self, bucket, k, cfg, queries) -> AnnBatchResult:
        ids, dists, truncated, count, blocks = self._run_executable(
            bucket, k, cfg, self.index, queries)
        return AnnBatchResult(
            ids=ids, dists=dists, truncated=truncated, candidate_count=count,
            rerank_blocks=None if blocks is None else
            (int(blocks[0]), int(blocks[1])))


class ShardedSearcher(Searcher):
    """Corpus-sharded execution through :mod:`repro.core.distributed`.

    The built index is placed ONCE, sharded over the mesh's data axes per
    :func:`repro.core.distributed.index_pspecs`; each ``(bucket, k, cfg)``
    key compiles a :func:`make_distributed_query_with_stats` executable.
    Queries are replicated by default (``query_axes=()``) so every bucket
    size runs on every mesh, and the combine all-gather moves only
    (Q, shards*k) id/dist pairs per batch.
    """

    def __init__(
        self,
        index: SCIndex,
        cfg: SCConfig | None = None,
        *,
        mesh=None,
        shards: int | None = None,
        data_axes=None,
        query_axes=(),
        max_cached_fns: int = 64,
        buckets=ANN_BATCH_BUCKETS,
        autotune_cache: str | None = None,
    ):
        super().__init__(index, cfg, max_cached_fns=max_cached_fns,
                         buckets=buckets, autotune_cache=autotune_cache)
        from jax.sharding import NamedSharding

        from repro.core.distributed import index_pspecs

        if mesh is None:
            n_dev = len(jax.devices())
            shards = n_dev if shards is None else int(shards)
            if not 1 <= shards <= n_dev:
                raise ValueError(f"shards={shards} out of range [1, {n_dev} devices]")
            mesh = jax.make_mesh((shards,), ("data",))
            data_axes = ("data",)
        elif shards is not None:
            raise ValueError(
                "pass either mesh or shards, not both — with an explicit "
                "mesh the shard count is the product of its data axes"
            )
        self.mesh = mesh
        self.data_axes = tuple(data_axes if data_axes is not None else ("data",))
        self.query_axes = tuple(query_axes)
        self.shards = math.prod(mesh.shape[ax] for ax in self.data_axes)
        if index.n % self.shards:
            raise ValueError(
                f"corpus size {index.n} not divisible by {self.shards} shards"
            )
        specs = index_pspecs(index, self.data_axes)
        #: the index as placed: corpus leaves sharded over the data axes
        self.placed_index = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)) if s is not None else x,
            index,
            specs,
            is_leaf=lambda x: x is None,
        )

    def _compile(self, bucket: int, k: int, cfg: SCConfig):
        from repro.core.distributed import make_distributed_query_with_stats

        return make_distributed_query_with_stats(
            self.mesh,
            cfg,
            self.index,
            self.index.n,
            data_axes=self.data_axes,
            query_axes=self.query_axes,
            k=k,
        )

    def run_padded(self, bucket, k, cfg, queries) -> AnnBatchResult:
        from repro.core.config import resolve_rerank
        from repro.core.distributed import per_shard_cap

        ids, dists, stats = self._run_executable(
            bucket, k, cfg, self.placed_index, queries)
        shard_candidates = stats["shard_candidates"]
        shard_truncated = stats["shard_truncated"]
        # shard_candidates is the pre-clamp per-shard DEMAND; clamp each
        # shard at its static gather cap so candidate_count keeps the
        # single-device semantics ('actually re-ranked') uniformly across
        # placements. The masked-full pipeline has no cap (count == demand).
        if resolve_rerank(cfg, distributed=True) == "gather":
            cap = per_shard_cap(cfg, self.index.n // self.shards, k)
            count = np.minimum(shard_candidates, cap).sum(axis=1)
        else:
            count = shard_candidates.sum(axis=1)
        return AnnBatchResult(
            ids=ids,
            dists=dists,
            truncated=shard_truncated.any(axis=1),
            candidate_count=count.astype(np.int32),
            shard_candidates=shard_candidates,
            shard_truncated=shard_truncated,
        )


def make_searcher(
    index: SCIndex,
    cfg: SCConfig | None = None,
    placement: str = "auto",
    *,
    mesh=None,
    shards: int | None = None,
    data_axes=None,
    query_axes=(),
    max_cached_fns: int = 64,
    autotune_cache: str | None = None,
) -> Searcher:
    """Placement-resolving :class:`Searcher` factory.

    ``placement``:
      * ``"single"``  — default-device execution; ``mesh``/``shards`` rejected.
      * ``"sharded"`` — corpus-sharded over ``mesh`` (or an N-way data mesh
        from ``shards``; all devices when neither is given).
      * ``"auto"``    — ``"sharded"`` when a mesh/shard count is requested,
        or when several devices are visible and the corpus splits evenly
        over all of them; ``"single"`` otherwise.
    """
    if placement == "auto":
        if mesh is not None or (shards is not None and shards > 1):
            placement = "sharded"
        else:
            n_dev = len(jax.devices())
            placement = (
                "sharded" if n_dev > 1 and index.n % n_dev == 0 and shards is None
                else "single"
            )
    if placement == "single":
        if mesh is not None or (shards is not None and shards > 1):
            raise ValueError(
                f"mesh/shards are only consumed by placement='sharded', got "
                f"placement='single' with mesh={mesh!r} shards={shards!r}"
            )
        return SingleDeviceSearcher(
            index, cfg, max_cached_fns=max_cached_fns,
            autotune_cache=autotune_cache,
        )
    if placement == "sharded":
        return ShardedSearcher(
            index,
            cfg,
            mesh=mesh,
            shards=shards,
            data_axes=data_axes,
            query_axes=query_axes,
            max_cached_fns=max_cached_fns,
            autotune_cache=autotune_cache,
        )
    raise ValueError(
        f"unknown placement {placement!r} (want 'single', 'sharded' or 'auto')"
    )
