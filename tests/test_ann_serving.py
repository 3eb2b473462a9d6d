"""AnnServingEngine correctness: engine == direct query, padding-proof,
jit-cache reuse, telemetry consistency."""
import dataclasses
import time

import numpy as np
import pytest

from repro.core import build, query, taco_config
from repro.serving import AnnRequest, AnnServingEngine
from repro.serving.batching import bucket_size, pad_rows


@pytest.fixture(scope="module")
def served_index(small_dataset):
    data, queries, _gt_i, _gt_d = small_dataset
    cfg = taco_config(n_subspaces=4, subspace_dim=8, n_clusters=256,
                      alpha=0.05, beta=0.02, k=10)
    index = build(data, cfg)
    return index, cfg, np.asarray(queries)


def _fresh_engine(index, cfg, **kw):
    return AnnServingEngine(index, cfg, **kw)


def test_bucket_size_ladder():
    assert bucket_size(1, (1, 2, 4, 8)) == 1
    assert bucket_size(3, (1, 2, 4, 8)) == 4
    assert bucket_size(8, (1, 2, 4, 8)) == 8
    assert bucket_size(9, (1, 2, 4, 8)) == 16  # past the top rung
    with pytest.raises(ValueError):
        bucket_size(0, (1, 2))


def test_even_shard_total():
    from repro.data import even_shard_total

    assert even_shard_total(10000, 32, 1) == 10000  # no sharding: no-op
    n = even_shard_total(10000, 32, 4)
    assert n <= 10000 and (n - 32) % 4 == 0
    assert even_shard_total(8192, 16, 8) == (8192 - 16) // 8 * 8 + 16


def test_pad_rows():
    x = np.arange(6, dtype=np.float32).reshape(3, 2)
    padded = pad_rows(x, 5)
    assert padded.shape == (5, 2)
    np.testing.assert_array_equal(padded[:3], x)
    np.testing.assert_array_equal(padded[3], x[-1])
    assert pad_rows(x, 3) is x
    with pytest.raises(ValueError):
        pad_rows(x, 2)


def test_engine_matches_direct_query(served_index):
    """(a) engine results identical to direct taco.query, per request."""
    index, cfg, queries = served_index
    want_ids, want_dists = query(index, queries, cfg)
    engine = _fresh_engine(index, cfg, max_batch=queries.shape[0])
    results = engine.search([AnnRequest(query=q) for q in queries])
    got_ids = np.stack([r.ids for r in results])
    got_dists = np.stack([r.dists for r in results])
    np.testing.assert_array_equal(got_ids, np.asarray(want_ids))
    np.testing.assert_array_equal(got_dists, np.asarray(want_dists))


def test_engine_matches_direct_query_with_k_override(served_index):
    index, cfg, queries = served_index
    want_ids, want_dists = query(index, queries[:4], cfg, k=5)
    engine = _fresh_engine(index, cfg, max_batch=4)
    results = engine.search([AnnRequest(query=q, k=5) for q in queries[:4]])
    got_ids = np.stack([r.ids for r in results])
    assert got_ids.shape == (4, 5)
    np.testing.assert_array_equal(got_ids, np.asarray(want_ids))
    np.testing.assert_array_equal(
        np.stack([r.dists for r in results]), np.asarray(want_dists)
    )


def test_engine_beta_override_matches_replaced_cfg(served_index):
    index, cfg, queries = served_index
    beta = cfg.beta * 2
    want_ids, _ = query(index, queries[:4], dataclasses.replace(cfg, beta=beta))
    engine = _fresh_engine(index, cfg, max_batch=4)
    results = engine.search([AnnRequest(query=q, beta=beta) for q in queries[:4]])
    np.testing.assert_array_equal(
        np.stack([r.ids for r in results]), np.asarray(want_ids)
    )


def test_bucket_padding_does_not_change_results(served_index):
    """(b) a 5-request batch runs padded to bucket 8; results must equal
    the unpadded direct query of exactly those 5 rows."""
    index, cfg, queries = served_index
    want_ids, want_dists = query(index, queries[:5], cfg)
    engine = _fresh_engine(index, cfg, max_batch=16)
    results = engine.search([AnnRequest(query=q) for q in queries[:5]])
    assert engine.telemetry()["compiles_per_bucket"] == {8: 1}  # padded shape
    np.testing.assert_array_equal(
        np.stack([r.ids for r in results]), np.asarray(want_ids)
    )
    np.testing.assert_array_equal(
        np.stack([r.dists for r in results]), np.asarray(want_dists)
    )


def test_mixed_stream_demuxes_per_request(served_index):
    """Interleaved default / k-override / beta-override requests come back
    in submission order, each matching its own direct query."""
    index, cfg, queries = served_index
    beta = cfg.beta * 2
    reqs = [
        AnnRequest(query=queries[0]),
        AnnRequest(query=queries[1], k=3),
        AnnRequest(query=queries[2], beta=beta),
        AnnRequest(query=queries[3]),
    ]
    engine = _fresh_engine(index, cfg, max_batch=8)
    results = engine.search(reqs)
    np.testing.assert_array_equal(
        results[0].ids, np.asarray(query(index, queries[:1], cfg)[0])[0]
    )
    np.testing.assert_array_equal(
        results[1].ids, np.asarray(query(index, queries[1:2], cfg, k=3)[0])[0]
    )
    np.testing.assert_array_equal(
        results[2].ids,
        np.asarray(
            query(index, queries[2:3], dataclasses.replace(cfg, beta=beta))[0]
        )[0],
    )
    np.testing.assert_array_equal(
        results[3].ids, np.asarray(query(index, queries[3:4], cfg)[0])[0]
    )
    # three distinct (k, cfg) groups -> three batches
    assert engine.telemetry()["batches"] == 3


def test_jit_cache_hit_no_recompile(served_index):
    """(c) repeated waves at the same bucket size reuse the executable."""
    index, cfg, queries = served_index
    engine = _fresh_engine(index, cfg, max_batch=8)
    engine.search([AnnRequest(query=q) for q in queries[:8]])
    t1 = engine.telemetry()
    assert t1["compiles_total"] == 1
    for _ in range(3):
        engine.search([AnnRequest(query=q) for q in queries[8:16]])
    t2 = engine.telemetry()
    assert t2["compiles_total"] == 1  # no recompiles for repeated bucket
    assert t2["batches"] == 4
    # a new bucket size compiles exactly once more
    engine.search([AnnRequest(query=q) for q in queries[:2]])
    t3 = engine.telemetry()
    assert t3["compiles_total"] == 2
    assert t3["compiles_per_bucket"] == {8: 1, 2: 1}


def test_submit_rejects_malformed_requests(served_index):
    """Validation happens at submit() so a bad request can't crash a drain
    batch carrying other callers' requests."""
    index, cfg, queries = served_index
    engine = _fresh_engine(index, cfg, max_batch=4)
    good = engine.submit(AnnRequest(query=queries[0]))
    with pytest.raises(ValueError):
        engine.submit(AnnRequest(query=queries[0][:-1]))  # wrong dim
    with pytest.raises(ValueError):
        engine.submit(AnnRequest(query=queries[0], k=0))
    with pytest.raises(ValueError):
        engine.submit(AnnRequest(query=queries[0], k=index.n + 1))
    with pytest.raises(ValueError):
        engine.submit(AnnRequest(query=queries[0], beta=0.0))
    out = engine.drain()
    assert set(out) == {good}  # earlier valid request unaffected


def test_engine_rejects_unused_shard_kwargs(served_index):
    """mesh/shards only apply to backend='sharded'; silently ignoring them
    would let a forgotten backend= degrade to single-device serving."""
    index, cfg, _queries = served_index
    with pytest.raises(ValueError):
        AnnServingEngine(index, cfg, shards=4)
    with pytest.raises(ValueError):
        AnnServingEngine(index, cfg, backend="bogus")


def test_jit_cache_is_bounded(served_index):
    index, cfg, queries = served_index
    engine = _fresh_engine(index, cfg, max_batch=1, max_cached_fns=2)
    for i in range(4):  # 4 distinct beta groups -> 4 compiles, 2 retained
        engine.search([AnnRequest(query=queries[0], beta=0.01 + 0.001 * i)])
    assert engine.telemetry()["compiles_total"] == 4
    assert len(engine._fns) == 2


def test_telemetry_counters_consistent(served_index):
    """(d) counters line up with the actual request/batch traffic."""
    index, cfg, queries = served_index
    engine = _fresh_engine(index, cfg, max_batch=4)
    n = queries.shape[0]  # 16 requests in waves of max_batch=4 -> 4 batches
    results = engine.search([AnnRequest(query=q) for q in queries])
    t = engine.telemetry()
    assert len(results) == n
    assert t["requests_served"] == n
    assert t["batches"] == 4
    assert t["compiles_total"] == sum(t["compiles_per_bucket"].values()) == 1
    assert 0.0 <= t["truncation_rate"] <= 1.0
    assert t["latency_p50_s"] <= t["latency_p99_s"]
    assert t["queries_per_sec"] > 0
    assert engine.pending() == 0
    # per-request latency is the wall time of its batch
    assert all(r.latency_s > 0 for r in results)


def test_queries_per_sec_counts_idle_wall_time(served_index):
    """Answers over wall seconds since the last reset, idle time included:
    a pause after serving lowers the rate."""
    index, cfg, queries = served_index
    engine = _fresh_engine(index, cfg, max_batch=4)
    engine.search([AnnRequest(query=q) for q in queries[:4]])  # compiles
    engine.reset_telemetry()
    assert engine.telemetry()["queries_per_sec"] == 0.0
    engine.search([AnnRequest(query=q) for q in queries[:4]])
    time.sleep(0.2)
    assert 0.0 < engine.telemetry()["queries_per_sec"] <= 4 / 0.2


def test_telemetry_surfaces_lockcheck_counters(served_index):
    """Lock-discipline counters (runtime checker, analysis/lockcheck) ride
    along in telemetry(): present, well-typed, and consistent — dispatch
    count zero implies zero seconds under lock."""
    index, cfg, queries = served_index
    engine = _fresh_engine(index, cfg, max_batch=4)
    engine.search([AnnRequest(query=q) for q in queries[:4]])
    t = engine.telemetry()
    assert isinstance(t["jax_dispatch_under_lock"], int)
    assert isinstance(t["jax_seconds_under_lock"], float)
    assert t["jax_dispatch_under_lock"] >= 0
    assert t["jax_seconds_under_lock"] >= 0.0
    if t["jax_dispatch_under_lock"] == 0:
        assert t["jax_seconds_under_lock"] == 0.0


# ------------------------------------------------------- index lifecycle --
def test_swap_index_on_live_engine(served_index, small_dataset):
    """swap_index: atomic between drains, monotonic generation, cache
    dropped (stale-generation results never served), new index serves."""
    from repro.ann import AnnIndex

    index, cfg, queries = served_index
    data, _q, _gt_i, _gt_d = small_dataset
    engine = _fresh_engine(index, cfg, max_batch=8, result_cache_size=16)
    r0 = engine.search([AnnRequest(query=q) for q in queries[:4]])
    assert all(r.index_generation == 0 for r in r0)
    assert all(r.cached for r in
               engine.search([AnnRequest(query=q) for q in queries[:4]]))

    # rebuild over a shifted corpus (drop the first 32 rows): results differ
    new = AnnIndex.build(np.asarray(data)[32:], cfg)
    gen = engine.swap_index(new)
    assert gen == 1 and engine.telemetry()["index_swaps"] == 1
    r1 = engine.search([AnnRequest(query=q) for q in queries[:4]])
    assert not any(r.cached for r in r1), "stale cache served across swap"
    assert all(r.index_generation == 1 for r in r1)
    want_ids, want_d = new.search(queries[:4])
    np.testing.assert_array_equal(np.stack([r.ids for r in r1]),
                                  np.asarray(want_ids))
    np.testing.assert_array_equal(np.stack([r.dists for r in r1]),
                                  np.asarray(want_d))
    # queued-but-undrained requests are served by the NEW index
    rid = engine.submit(AnnRequest(query=queries[5]))
    engine.swap_index(AnnIndex(sc_index=index, cfg=cfg))
    res = engine.drain()[rid]
    np.testing.assert_array_equal(res.ids, np.asarray(query(index, queries[5:6], cfg)[0])[0])
    assert res.index_generation == 2


def test_swap_index_rejects_garbage(served_index):
    index, cfg, _queries = served_index
    engine = _fresh_engine(index, cfg)
    with pytest.raises(TypeError):
        engine.swap_index(42)


def test_notify_index_mutated_bumps_generation(served_index):
    index, cfg, queries = served_index
    engine = _fresh_engine(index, cfg, max_batch=4, result_cache_size=8)
    engine.search([AnnRequest(query=queries[0])])
    assert engine.search([AnnRequest(query=queries[0])])[0].cached
    engine.notify_index_mutated()
    r = engine.search([AnnRequest(query=queries[0])])[0]
    assert not r.cached and r.index_generation == 1
    assert engine.telemetry()["result_cache_invalidations"] == 1


def test_recall_probes_report_live_recall(served_index, small_dataset):
    """recall_probe_every=N: every Nth executed request is re-answered by
    exact kNN; telemetry reports the running mean recall@k."""
    index, cfg, queries = served_index
    _data, _q, gt_i, _gt_d = small_dataset
    engine = _fresh_engine(index, cfg, max_batch=8, recall_probe_every=2,
                           result_cache_size=32)
    engine.search([AnnRequest(query=q) for q in queries])
    t = engine.telemetry()
    assert t["recall_probe_count"] == len(queries) // 2
    assert 0.0 < t["live_recall_at_k"] <= 1.0
    # cache hits never reach the backend, so they are never probed
    engine.search([AnnRequest(query=q) for q in queries])
    assert engine.telemetry()["recall_probe_count"] == len(queries) // 2
    engine.reset_telemetry()
    assert engine.telemetry()["recall_probe_count"] == 0


def test_engine_counts_pass2_blocks_from_its_backend(served_index):
    """The engine adds a backend's pass 2 grid-step counts to
    ``taco_rerank_blocks_merged_total`` / ``taco_rerank_blocks_total``, and
    nothing where the backend reports none."""
    from repro.ann.searcher import AnnBatchResult, Searcher
    from repro.obs import default_registry

    index, cfg, queries = served_index

    class Stub(Searcher):
        def __init__(self, blocks):
            super().__init__(index, cfg)
            self.blocks = blocks

        def run_padded(self, bucket, k, cfg, queries):
            return AnnBatchResult(
                ids=np.zeros((bucket, k), np.int32),
                dists=np.zeros((bucket, k), np.float32),
                truncated=np.zeros((bucket,), bool),
                rerank_blocks=self.blocks)

    names = ("taco_rerank_blocks_merged_total", "taco_rerank_blocks_total")

    def counts():
        snap = default_registry().snapshot()
        return np.array([snap.get(n, 0.0) for n in names])

    for blocks, want in (((3, 40), [6, 80]), (None, [0, 0])):
        engine = _fresh_engine(index, cfg, max_batch=4, backend=Stub(blocks))
        before = counts()
        engine.search([AnnRequest(query=q) for q in queries[:8]])
        np.testing.assert_array_equal(counts() - before, want)
        assert engine.telemetry()["batches"] == 2
