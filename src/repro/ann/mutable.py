"""Mutable ANN index — a log-structured delta segment over an immutable base.

TaCo's index is built once over a static corpus; production corpora churn.
The paper's headline result — indexing up to 8x cheaper than SuCo — is what
makes the classic LSM recipe affordable here: serve mutations from a small
append-only **delta segment** (brute-force-scanned per query, which is
*exact*) plus a **tombstone bitmap** over the immutable base, and fold both
back into a fresh :class:`~repro.ann.AnnIndex` build whenever a
:class:`~repro.ann.compaction.CompactionPolicy` says the churn has earned a
rebuild.

Search semantics
----------------
``search()`` fans out to the base :class:`~repro.ann.searcher.Searcher`
(over-fetching ``k + next_pow2(#tombstones)`` so tombstoned rows can be
masked without ever coming up short) and an exact top-k scan of the live
delta rows, then merges the two streams distance-major / id-minor — the
same canonical order both re-rank pipelines and ``lax.top_k`` produce. The
delta scan and the tombstone mask are exact, so a mutable search differs
from a from-scratch rebuild over the live corpus only through the base
segment's subspace-collision approximation:

  * immediately after :meth:`compact` the results are **bitwise-identical**
    to ``AnnIndex.build(live_corpus)`` by construction (compaction IS that
    build, modulo the stable-external-id translation);
  * before compaction they are bitwise-identical whenever candidate
    selection is exhaustive (e.g. ``selection="fixed", beta=1.0`` — pinned
    in tests for both re-rank pipelines), and otherwise carry the same
    approximation the immutable index has.

External ids are stable and never reused: base rows keep their build-time
row ids, inserts are numbered monotonically from there, and compaction
re-maps the fresh build's rows back to the surviving external ids.

Concurrency: every mutation replaces ``self._state`` (an immutable
snapshot) under a lock, so a concurrent search sees either the old or the
new state, never a torn one. Background compaction builds from a snapshot
while a mutation log accumulates, then replays the log onto the fresh
state at install time (see :mod:`repro.ann.compaction`).

Serving: :meth:`engine` wraps a :class:`MutableSearcher` in an
:class:`~repro.serving.ann_engine.AnnServingEngine` wired for churn —
every mutation bumps the engine's ``index_generation`` and drops its
result cache, and the engine's recall probes sample the live corpus.

Durability (:mod:`repro.ann.wal`): with ``durability="sync"`` or
``"async"`` every mutation appends a checksummed record to a durable
write-ahead log *before* the state snapshot is installed — the append is
memory-only under the lock, the fsync happens on the caller's path
(sync) or via a group-commit flusher task on the shared WorkerPool
(async), never under the index lock. A kill -9 mid-churn replays the
log past the last snapshot's watermark back to the pre-crash state (see
:func:`repro.ann.persistence.load_mutable_index`).
"""
from __future__ import annotations

import threading
import weakref
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann.index import AnnIndex
from repro.ann.compaction import CompactionPolicy, CompactionReport  # noqa: F401
from repro.ann.searcher import AnnBatchResult, Searcher
from repro.batching import ANN_BATCH_BUCKETS
from repro.core.config import SCConfig
from repro.core.taco import rerank as _exact_rerank
from repro.obs import metrics as obsm
from repro.obs import trace as obst

# Process-wide mutation metric families (repro.obs registry).
_M_MUTATIONS = obsm.counter(
    "taco_mutable_rows_total", "Rows mutated on any mutable index, by kind",
    labelnames=("kind",),
)
_M_ROWS_INSERTED = _M_MUTATIONS.labels(kind="insert")
_M_ROWS_DELETED = _M_MUTATIONS.labels(kind="delete")
_M_LIVE_ROWS = obsm.gauge(
    "taco_mutable_live_rows", "Live rows (base - tombstones + delta live)"
)


def _pow2ceil(x: int) -> int:
    """0 -> 0, else the next power of two >= x (buckets the tombstone
    over-fetch and the delta pad so executable keys change O(log) times
    between compactions, not per mutation)."""
    return 0 if x <= 0 else 1 << (int(x) - 1).bit_length()


class _State:
    """One immutable snapshot of the mutable index.

    Mutations never modify a snapshot's arrays in place — they build a new
    snapshot and atomically replace the owner's ``_state`` reference, so a
    search that grabbed a snapshot keeps computing against a consistent
    view. ``base_ids`` is sorted ascending (build order, preserved by
    compaction) which both makes id lookup a searchsorted and means the
    live corpus enumerated base-then-delta is in external-id order — the
    property the bitwise tie-break parity with a rebuilt oracle rests on.
    """

    __slots__ = (
        "base", "base_ids", "tombstones", "n_tombstones",
        "delta", "delta_ids", "delta_live", "_delta_pad", "_base_data_np",
    )

    def __init__(self, base, base_ids, tombstones, delta, delta_ids, delta_live):
        self.base: AnnIndex | None = base
        self.base_ids: np.ndarray = base_ids  # (n_base,) int32, ascending
        self.tombstones: np.ndarray = tombstones  # (n_base,) bool
        self.n_tombstones = int(tombstones.sum())
        self.delta: np.ndarray = delta  # (m, d) float32, insertion order
        self.delta_ids: np.ndarray = delta_ids  # (m,) int32, ascending
        self.delta_live: np.ndarray = delta_live  # (m,) bool
        self._delta_pad = None
        self._base_data_np = None

    # ------------------------------------------------------------- views --
    @property
    def n_base(self) -> int:
        return int(self.base_ids.shape[0])

    @property
    def n_delta_rows(self) -> int:
        return int(self.delta.shape[0])

    @property
    def n_delta_live(self) -> int:
        return int(self.delta_live.sum())

    @property
    def n_live(self) -> int:
        return self.n_base - self.n_tombstones + self.n_delta_live

    def base_data(self) -> np.ndarray:
        """Host copy of the base corpus (cached per snapshot)."""
        if self._base_data_np is None:
            self._base_data_np = np.asarray(self.base.sc_index.data)
        return self._base_data_np

    def live_corpus(self) -> tuple[np.ndarray, np.ndarray]:
        """(vectors (L, d), external ids (L,)) in external-id order."""
        parts_v, parts_i = [], []
        if self.base is not None and self.n_base:
            alive = ~self.tombstones
            parts_v.append(self.base_data()[alive])
            parts_i.append(self.base_ids[alive])
        if self.n_delta_rows:
            parts_v.append(self.delta[self.delta_live])
            parts_i.append(self.delta_ids[self.delta_live])
        if not parts_v:
            d = self.delta.shape[1]
            return np.empty((0, d), np.float32), np.empty((0,), np.int32)
        return (
            np.ascontiguousarray(np.concatenate(parts_v)),
            np.concatenate(parts_i),
        )

    def delta_padded(self):
        """Delta rows padded up a power-of-two ladder: (rows (m_pad, d),
        ``||x||^2`` norms (m_pad,), live mask (m_pad,), ids (m_pad,)) —
        cached per snapshot so repeated queries share one pad + norm pass.
        Pad rows are zero vectors with ``live=False``: the exact re-rank
        masks them to +inf, so they can never enter a top-k."""
        if self._delta_pad is None:
            m = self.n_delta_rows
            m_pad = max(8, _pow2ceil(m))
            rows = np.zeros((m_pad, self.delta.shape[1]), np.float32)
            rows[:m] = self.delta
            live = np.zeros((m_pad,), bool)
            live[:m] = self.delta_live
            ids = np.full((m_pad,), -1, np.int32)
            ids[:m] = self.delta_ids
            norms = np.einsum("md,md->m", rows, rows).astype(np.float32)
            self._delta_pad = (rows, norms, live, ids)
        return self._delta_pad

    def replace(self, **kw) -> "_State":
        fields = dict(
            base=self.base, base_ids=self.base_ids, tombstones=self.tombstones,
            delta=self.delta, delta_ids=self.delta_ids, delta_live=self.delta_live,
        )
        fields.update(kw)
        st = _State(**fields)
        if kw.get("base", self.base) is self.base:
            st._base_data_np = self._base_data_np  # host copy survives
        return st


def _state_insert(st: _State, vectors: np.ndarray, ids: np.ndarray) -> _State:
    return st.replace(
        delta=np.concatenate([st.delta, vectors]),
        delta_ids=np.concatenate([st.delta_ids, ids]),
        delta_live=np.concatenate([st.delta_live, np.ones(len(ids), bool)]),
    )


def _state_delete(st: _State, ids: np.ndarray) -> _State:
    """Tombstone each id (base row or delta row); KeyError on a dead or
    unknown id — a delete must name a live vector."""
    tomb = st.tombstones.copy()
    dlive = st.delta_live.copy()
    for i in np.asarray(ids, np.int64).ravel():
        pos = int(np.searchsorted(st.base_ids, i))
        if pos < st.n_base and st.base_ids[pos] == i:
            if tomb[pos]:
                raise KeyError(f"id {int(i)} was already deleted")
            tomb[pos] = True
            continue
        hits = np.flatnonzero(st.delta_ids == i)
        if hits.size and dlive[hits[-1]]:
            dlive[hits[-1]] = False
            continue
        raise KeyError(
            f"id {int(i)} is not a live vector (already deleted or never "
            f"inserted)"
        )
    return st.replace(tombstones=tomb, delta_live=dlive)


@partial(jax.jit, static_argnames=("k",))
def _delta_topk(queries, rows, norms, live, k: int):
    """Exact top-k over the (padded) delta segment.

    Runs the SAME exact re-rank the base pipelines use
    (:func:`repro.core.taco.rerank`, ``||q||^2 - 2 q.x + ||x||^2`` against
    precomputed norms) so a delta hit's squared distance is the number a
    rebuilt index would report for that row. Returns (row ids (Q, k) into
    the padded delta, dists (Q, k)); dead/pad rows are masked to -1/inf.
    """
    q = queries.shape[0]
    m = rows.shape[0]
    cand = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[None, :], (q, m))
    valid = jnp.broadcast_to(live[None, :], (q, m))
    return _exact_rerank(rows, queries, cand, valid, k, norms)


def _merge_topk(streams, k: int, bucket: int):
    """Merge per-query (ids, dists) streams into one canonical top-k.

    Two stable argsorts (id-minor, then distance-major) — the exact order
    :func:`repro.kernels.masked_rerank.finalize_topk` and the gather
    pipeline's ``lax.top_k`` over id-ordered candidates produce, so the
    merged stream breaks distance ties the same way a from-scratch rebuild
    over the id-ordered live corpus would. Dead entries ride in as
    (id -1, dist inf) and sink. Returns (ids (bucket, k) int32,
    dists (bucket, k) float32).
    """
    if not streams:
        return (
            np.full((bucket, k), -1, np.int32),
            np.full((bucket, k), np.inf, np.float32),
        )
    all_i = np.concatenate([s[0] for s in streams], axis=1)
    all_d = np.concatenate([s[1] for s in streams], axis=1)
    if all_i.shape[1] < k:  # fewer total slots than k: pad before selecting
        pad = k - all_i.shape[1]
        all_i = np.pad(all_i, ((0, 0), (0, pad)), constant_values=-1)
        all_d = np.pad(all_d, ((0, 0), (0, pad)), constant_values=np.inf)
    o1 = np.argsort(all_i, axis=1, kind="stable")
    i1 = np.take_along_axis(all_i, o1, axis=1)
    d1 = np.take_along_axis(all_d, o1, axis=1)
    o2 = np.argsort(d1, axis=1, kind="stable")
    ids = np.take_along_axis(i1, o2, axis=1)[:, :k]
    dists = np.take_along_axis(d1, o2, axis=1)[:, :k]
    dead = ~np.isfinite(dists)
    ids = np.where(dead, -1, ids)
    return ids.astype(np.int32), dists.astype(np.float32)


class MutableSearcher(Searcher):
    """Fan-out searcher over (base − tombstones) ∪ delta.

    Reads the owning :class:`MutableAnnIndex`'s current state snapshot per
    padded batch, so one searcher (and the engine built on it) stays valid
    across mutations AND compactions — the base executables live on each
    base index's own single-device searcher and survive for as long as
    that base does. Single-device placement only (sharded delta segments
    are a ROADMAP follow-on).
    """

    shards = 1

    def __init__(self, mutable: "MutableAnnIndex", *, buckets=ANN_BATCH_BUCKETS):
        # deliberately NOT calling Searcher.__init__: there is no single
        # immutable index to bind; everything routes through `mutable`
        self.mutable = mutable
        self.cfg = mutable.cfg
        self.buckets = tuple(buckets)

    # ------------------------------------------------------------- shims --
    @property
    def index(self):
        """The CURRENT base SCIndex (None while running delta-only)."""
        st = self.mutable._state
        return None if st.base is None else st.base.sc_index

    def _base_searcher(self, st: _State):
        return None if st.base is None else st.base._default_searcher()

    @property
    def _fns(self):
        s = self._base_searcher(self.mutable._state)
        return s._fns if s is not None else {}

    @property
    def compile_counts(self):
        s = self._base_searcher(self.mutable._state)
        return s.compile_counts if s is not None else {}

    @property
    def dim(self) -> int:
        return self.mutable.d

    @property
    def max_k(self) -> int:
        return max(1, self.mutable._state.n_live)

    def extra_telemetry(self) -> dict:
        return {"mutable": self.mutable.stats()}

    def probe_corpus(self):
        return self.mutable.live_corpus()

    # -------------------------------------------------------------- run --
    def run_padded(self, bucket, k, cfg: SCConfig, queries) -> AnnBatchResult:
        st = self.mutable._state  # one atomic snapshot for the whole batch
        streams = []
        truncated = np.zeros((bucket,), bool)
        count = np.zeros((bucket,), np.int32)
        rerank_blocks = None

        if st.base is not None and st.n_base:
            # over-fetch so that even if every tombstone outranked the k-th
            # live row, k live rows remain; pow2-bucketed so the (bucket,
            # base_k, cfg) executable key moves O(log) times per epoch
            base_k = min(st.n_base, k + _pow2ceil(st.n_tombstones))
            res = st.base._default_searcher().run_padded(
                bucket, base_k, cfg, queries
            )
            rows = np.asarray(res.ids)
            safe = np.maximum(rows, 0)
            dead = (rows < 0) | st.tombstones[safe]
            streams.append((
                np.where(dead, -1, st.base_ids[safe]),
                np.where(dead, np.float32(np.inf), np.asarray(res.dists)),
            ))
            truncated = np.asarray(res.truncated)
            if res.candidate_count is not None:
                count = count + np.asarray(res.candidate_count)
            rerank_blocks = res.rerank_blocks

        if st.n_delta_rows:
            rows, norms, live, ids = st.delta_padded()
            k_delta = min(k, rows.shape[0])
            d_rows, d_dists = jax.block_until_ready(
                _delta_topk(jnp.asarray(queries), jnp.asarray(rows),
                            jnp.asarray(norms), jnp.asarray(live), k_delta)
            )
            d_rows = np.asarray(d_rows)
            safe = np.maximum(d_rows, 0)
            dead = d_rows < 0
            streams.append((
                np.where(dead, -1, ids[safe]),
                np.where(dead, np.float32(np.inf), np.asarray(d_dists)),
            ))
            count = count + np.int32(st.n_delta_live)  # exact scan, per query

        ids, dists = _merge_topk(streams, k, bucket)
        return AnnBatchResult(
            ids=ids, dists=dists, truncated=truncated, candidate_count=count,
            rerank_blocks=rerank_blocks,
        )


def churn_wave(mutable, rng, live_ids, n_inserts: int, *, engine=None,
               background: bool = False):
    """One synthetic mutation wave for churn drivers and benchmarks
    (``serve_ann --churn`` / ``bench_serving --churn`` share this, so both
    measure the same workload): insert ``n_inserts`` Gaussian rows, delete
    ``n_inserts // 2`` random earlier inserts (tracked in ``live_ids``,
    mutated in place), then let the policy decide on compaction. Returns
    the :class:`~repro.ann.compaction.CompactionReport` (or, with
    ``background=True``, the in-flight
    :class:`~repro.ann.compaction.CompactionHandle` — the rebuild runs as
    a shared-WorkerPool task while the caller keeps serving) or None."""
    fresh = rng.standard_normal((n_inserts, mutable.d)).astype(np.float32)
    live_ids.extend(int(i) for i in mutable.insert(fresh))
    kill = [live_ids.pop(rng.integers(len(live_ids)))
            for _ in range(min(n_inserts // 2, len(live_ids)))]
    if kill:
        mutable.delete(kill)
    return mutable.maybe_compact(engine=engine, background=background)


class MutableAnnIndex:
    """An :class:`AnnIndex` that accepts inserts and deletes.

    See the module docstring for semantics. Typical use::

        mutable = AnnIndex.build(data, cfg).mutable()
        new_ids = mutable.insert(fresh_vectors)
        mutable.delete([3, 17])
        ids, dists = mutable.search(queries)
        report = mutable.maybe_compact()        # policy-driven rebuild
        mutable.save(path); MutableAnnIndex.load(path)  # mid-churn restart
    """

    def __init__(
        self,
        base: AnnIndex | None = None,
        *,
        cfg: SCConfig | None = None,
        dim: int | None = None,
        policy: CompactionPolicy | None = None,
        durability: str = "none",
        wal_dir: str | None = None,
        wal=None,
        wal_segment_bytes: int | None = None,
    ):
        if base is not None:
            cfg = base.cfg if cfg is None else cfg
            dim = base.d
        if cfg is None:
            raise ValueError("cfg is required when no base index is given")
        if dim is None:
            raise ValueError("dim is required when no base index is given")
        from repro.ann.wal import DURABILITY_MODES, WriteAheadLog

        if durability not in DURABILITY_MODES:
            raise ValueError(
                f"durability={durability!r} (want one of {DURABILITY_MODES})"
            )
        if durability != "none" and wal is None and wal_dir is None:
            raise ValueError(f"durability={durability!r} requires wal_dir")
        if durability == "none" and (wal is not None or wal_dir is not None):
            raise ValueError("a WAL was given but durability='none'")
        self.durability = durability
        if wal is not None:
            self._wal = wal
        elif wal_dir is not None:
            kw = {} if wal_segment_bytes is None else {
                "segment_bytes": wal_segment_bytes
            }
            self._wal = WriteAheadLog(wal_dir, **kw)
        else:
            self._wal = None
        self._checkpoint_path: str | None = None
        self.cfg = cfg
        self.d = int(dim)
        self.policy = CompactionPolicy() if policy is None else policy
        n_base = base.n if base is not None else 0
        self._lock = threading.RLock()
        self._state = _State(
            base=base,
            base_ids=np.arange(n_base, dtype=np.int32),
            tombstones=np.zeros(n_base, bool),
            delta=np.empty((0, self.d), np.float32),
            delta_ids=np.empty((0,), np.int32),
            delta_live=np.empty((0,), bool),
        )
        self._next_id = n_base
        self.generation = 0  # bumps on every mutation and compaction install
        self._mutations = 0
        self._compactions = 0
        self._last_compaction_s: float | None = None
        self._log: list | None = None  # mutation log while compacting
        self._engines: list = []  # weakrefs to attached serving engines
        self._searcher: MutableSearcher | None = None

    # -------------------------------------------------------- construction --
    @classmethod
    def build(cls, data, cfg: SCConfig, *, policy=None) -> "MutableAnnIndex":
        """Build the immutable base over ``data`` and wrap it mutable."""
        return cls(AnnIndex.build(data, cfg), policy=policy)

    # ------------------------------------------------------------ mutation --
    def insert(self, vectors) -> np.ndarray:
        """Append vectors to the delta segment; returns their new external
        ids (monotonic, never reused — a deleted-then-reinserted vector
        gets a fresh id). Accepts one (d,) vector or a (m, d) batch."""
        v = np.ascontiguousarray(np.asarray(vectors, np.float32))
        if v.ndim == 1:
            v = v[None]
        if v.ndim != 2 or v.shape[1] != self.d:
            raise ValueError(f"vectors shape {v.shape} != (m, {self.d})")
        span = obst.default_tracer().start_trace("insert", rows=int(v.shape[0]))
        with self._lock:
            ids = np.arange(self._next_id, self._next_id + v.shape[0],
                            dtype=np.int32)
            self._next_id += v.shape[0]
            lsn = None
            if self._wal is not None:
                # append BEFORE apply (memory only under the lock) so the
                # log order is exactly the apply order
                with span.child("wal-append"):
                    lsn = self._wal.append_insert(
                        ids, v, generation=self.generation + 1
                    )
            if self._log is not None:
                self._log.append(("insert", v, ids))
            engines = self._install(_state_insert(self._state, v, ids))
        _M_ROWS_INSERTED.inc(v.shape[0])
        with span.child("wal-commit", durability=self.durability):
            self._wal_commit(lsn)
        self._notify_engines(engines)
        span.finish()
        return ids

    def delete(self, ids) -> int:
        """Tombstone live vectors by external id; returns the count.
        Raises KeyError (mutating nothing) if any id is unknown or already
        deleted."""
        arr = np.atleast_1d(np.asarray(ids, np.int64))
        span = obst.default_tracer().start_trace("delete", rows=int(arr.size))
        with self._lock:
            new = _state_delete(self._state, arr)  # raises before any change
            lsn = None
            if self._wal is not None:
                with span.child("wal-append"):
                    lsn = self._wal.append_delete(
                        arr, generation=self.generation + 1
                    )
            if self._log is not None:
                self._log.append(("delete", arr.copy()))
            engines = self._install(new)
        _M_ROWS_DELETED.inc(arr.size)
        with span.child("wal-commit", durability=self.durability):
            self._wal_commit(lsn)
        self._notify_engines(engines)
        span.finish()
        return int(arr.size)

    def _wal_commit(self, lsn) -> None:
        """Durability step for one appended record, run AFTER the index
        lock is released: ``sync`` flushes + fsyncs on this (the caller's)
        thread, ``async`` schedules a coalesced group commit on the shared
        WorkerPool. File I/O never happens under ``self._lock``."""
        if lsn is None or self._wal is None:
            return
        if self.durability == "sync":
            self._wal.flush(lsn)
        else:
            self._wal.kick()

    def _install(self, st: _State) -> list:
        """Atomically publish a new state snapshot (callers hold the lock)
        and return the attached live engines; the CALLER must pass them to
        :meth:`_notify_engines` after releasing the lock.

        Notifying outside the lock keeps the lock order one-way (mutable
        lock -> engine lock would otherwise nest here, while the engine's
        drain worker holds its own lock for batch formation). The cost is
        a tiny window where a request can observe the new state before the
        engine's result cache is invalidated — such a hit serves a
        pre-install answer stamped with its (old) ``index_generation``, so
        the consumer can tell; the engine's own generation guard still
        prevents a result computed against the old state from entering the
        cache after the notify lands."""
        self._state = st
        self.generation += 1
        self._mutations += 1
        _M_LIVE_ROWS.set(st.n_live)
        alive, engines = [], []
        for ref in self._engines:
            eng = ref()
            if eng is None:
                continue
            alive.append(ref)
            engines.append(eng)
        self._engines = alive
        return engines

    @staticmethod
    def _notify_engines(engines: list) -> None:
        """Invalidate attached engines (generation bump + cache drop);
        called WITHOUT the mutable index's lock held."""
        for eng in engines:
            eng.notify_index_mutated()

    # -------------------------------------------------------------- query --
    def searcher(self, placement: str = "single") -> MutableSearcher:
        """The fan-out searcher (cached). Only single-device placement is
        supported; sharded delta segments are a ROADMAP follow-on."""
        if placement != "single":
            raise ValueError(
                f"MutableAnnIndex only supports placement='single' "
                f"(got {placement!r}); compact first to serve sharded"
            )
        if self._searcher is None:
            self._searcher = MutableSearcher(self)
        return self._searcher

    def search(self, queries, *, k=None, beta=None, rerank=None):
        return self.searcher().search(queries, k=k, beta=beta, rerank=rerank)

    def search_with_stats(self, queries, *, k=None, beta=None, rerank=None):
        return self.searcher().search_with_stats(
            queries, k=k, beta=beta, rerank=rerank
        )

    def engine(self, **engine_kwargs):
        """An :class:`~repro.serving.ann_engine.AnnServingEngine` serving
        this mutable index. Mutations and compactions bump the engine's
        ``index_generation`` and drop its result cache; recall probes
        (``recall_probe_every=N``) run against the live corpus."""
        from repro.serving.ann_engine import AnnServingEngine

        st = self._state
        eng = AnnServingEngine(
            None if st.base is None else st.base.sc_index,
            self.cfg,
            backend=self.searcher(),
            **engine_kwargs,
        )
        self._engines.append(weakref.ref(eng))
        return eng

    # ----------------------------------------------------------- lifecycle --
    def live_corpus(self) -> tuple[np.ndarray, np.ndarray]:
        """(vectors (L, d), external ids (L,)) — the corpus a from-scratch
        rebuild would index, in external-id order."""
        return self._state.live_corpus()

    def rebuild_oracle(self) -> tuple[AnnIndex, np.ndarray]:
        """A from-scratch ``AnnIndex.build`` over the live corpus plus the
        row -> external-id map; the parity oracle tests and examples assert
        against (compaction installs exactly this build)."""
        vecs, ids = self.live_corpus()
        return AnnIndex.build(vecs, self.cfg), ids

    def compact(self, *, engine=None, reason: str = "manual"):
        """Rebuild base+delta−tombstones into a fresh index and install it
        atomically; see :func:`repro.ann.compaction.compact`."""
        from repro.ann import compaction

        return compaction.compact(self, engine=engine, reason=reason)

    def compact_async(self, *, engine=None, reason: str = "background"):
        """:func:`repro.ann.compaction.compact` on a background thread;
        returns a :class:`~repro.ann.compaction.CompactionHandle`."""
        from repro.ann import compaction

        return compaction.compact_async(self, engine=engine, reason=reason)

    def maybe_compact(self, *, engine=None, background: bool = False):
        """Compact iff the policy's thresholds say the churn earned it.
        Returns the report (or handle when ``background``), else None."""
        reason = self.policy.reason(self.stats())
        if reason is None:
            return None
        if background:
            return self.compact_async(engine=engine, reason=reason)
        return self.compact(engine=engine, reason=reason)

    # Private compaction hooks (driven by repro.ann.compaction) ------------
    def _begin_compaction(self):
        with self._lock:
            if self._log is not None:
                raise RuntimeError("a compaction is already in progress")
            self._log = []
            st = self._state
        vecs, ids = st.live_corpus()
        return st, vecs, ids

    def _abort_compaction(self):
        with self._lock:
            self._log = None

    def _finish_compaction(self, base, vecs, ids, *, engine=None, snapshot=None):
        """Install the freshly built base (None => delta-only state: the
        live corpus was too small to cluster), replaying any mutations
        logged while the build ran. Returns (rows reclaimed, ops replayed)."""
        with self._lock:
            # reclaimed counts what the rebuild dropped from the SNAPSHOT it
            # was built over — rows inserted mid-build are replayed into the
            # fresh delta, not reclaimed
            snap = self._state if snapshot is None else snapshot
            reclaimed = (snap.n_base + snap.n_delta_rows) - len(ids)
            if base is not None:
                st = _State(
                    base=base,
                    base_ids=np.asarray(ids, np.int32),
                    tombstones=np.zeros(len(ids), bool),
                    delta=np.empty((0, self.d), np.float32),
                    delta_ids=np.empty((0,), np.int32),
                    delta_live=np.empty((0,), bool),
                )
            else:
                st = _State(
                    base=None,
                    base_ids=np.empty((0,), np.int32),
                    tombstones=np.empty((0,), bool),
                    delta=np.asarray(vecs, np.float32),
                    delta_ids=np.asarray(ids, np.int32),
                    delta_live=np.ones(len(ids), bool),
                )
            replayed = len(self._log)
            for op in self._log:
                if op[0] == "insert":
                    st = _state_insert(st, op[1], op[2])
                else:
                    st = _state_delete(st, op[1])
            self._log = None
            self._compactions += 1
            engines = self._install(st)
            lsn = None
            if self._wal is not None:
                # the marker records that the live corpus up to this LSN is
                # now base layout — replay treats it as a no-op, checkpoint
                # uses it to bound the log
                lsn = self._wal.append_compact(
                    generation=self.generation, n_live=st.n_live,
                    next_id=self._next_id,
                )
        # outside the lock: engine invalidation takes each engine's own
        # lock (see _install); swap_index below additionally records the
        # swap and re-binds an engine that was serving a DIFFERENT backend.
        self._wal_commit(lsn)
        self._notify_engines(engines)
        if engine is not None:
            engine.swap_index(self.searcher(), cfg=self.cfg)
        return reclaimed, replayed

    # -------------------------------------------------------- persistence --
    def save(self, path: str) -> str:
        """Persist base + delta + tombstones in ONE atomic manifest commit
        (:func:`repro.ann.persistence.save_mutable_index`) — a restart
        mid-churn resumes without replaying mutations. With a WAL
        attached the manifest records the (segment, LSN) watermark and the
        log checkpoints (rotate + retire covered segments) afterwards."""
        from repro.ann.persistence import save_mutable_index

        return save_mutable_index(self, path)

    def checkpoint(self, path: str | None = None) -> str:
        """Snapshot to ``path`` (default: the last save/load directory)
        and bound the WAL there; compaction calls this when a checkpoint
        directory is known so the log never outgrows one churn epoch."""
        path = self._checkpoint_path if path is None else path
        if path is None:
            raise ValueError(
                "no checkpoint path: pass one or save()/load() first"
            )
        return self.save(path)

    @classmethod
    def load(cls, path: str, *, policy=None, wal_dir=None,
             durability=None) -> "MutableAnnIndex":
        """Load a snapshot; with ``wal_dir`` also replay records past the
        snapshot's watermark (crash recovery) and keep logging there."""
        from repro.ann.persistence import load_mutable_index

        return load_mutable_index(
            path, policy=policy, wal_dir=wal_dir, durability=durability
        )

    def close(self) -> None:
        """Flush and close the WAL (if any); the index stays queryable
        but further mutations in a durable mode will fail."""
        if self._wal is not None:
            self._wal.close()

    # --------------------------------------------------------------- info --
    @property
    def n_live(self) -> int:
        return self._state.n_live

    @property
    def dirty(self) -> bool:
        """True when the state diverged from the last built base (a
        compaction would change the on-disk/base layout)."""
        st = self._state
        return bool(st.n_delta_rows or st.n_tombstones)

    def stats(self) -> dict:
        st = self._state
        out = {
            "n_base": st.n_base,
            "n_tombstones": st.n_tombstones,
            "n_delta_live": st.n_delta_live,
            "n_delta_dead": st.n_delta_rows - st.n_delta_live,
            "n_live": st.n_live,
            "generation": self.generation,
            "mutations": self._mutations,
            "compactions": self._compactions,
            "last_compaction_s": self._last_compaction_s,
            "next_id": self._next_id,
            "dirty": self.dirty,
            "durability": self.durability,
        }
        if self._wal is not None:
            out["wal"] = self._wal.stats()
        return out

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        s = self.stats()
        return (
            f"MutableAnnIndex(live={s['n_live']}, base={s['n_base']}, "
            f"tombstones={s['n_tombstones']}, delta={s['n_delta_live']}, "
            f"generation={s['generation']})"
        )
