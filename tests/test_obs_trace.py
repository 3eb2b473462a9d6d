"""Tracing analyzed: sampling, the bounded ring, Chrome export, and the
acceptance gates that need a live engine — span parenting across the
async submit -> drain-worker -> WorkerPool boundaries (every sampled
request forms ONE rooted tree even though its stages run on different
threads), WAL group-commit spans, and the 10k-request soak proving
latency accounting is flat-memory (the unbounded per-request latency
list is gone)."""
import numpy as np
import pytest

from repro.core import build, taco_config
from repro.obs import metrics as obsm
from repro.obs import trace as obst
from repro.obs.metrics import NBUCKETS
from repro.serving import AnnRequest, AnnServingEngine

D = 32
K = 5


@pytest.fixture(scope="module")
def tiny_index():
    rng = np.random.default_rng(0)
    data = rng.integers(0, 30, (512, D)).astype(np.float32)
    cfg = taco_config(n_subspaces=3, subspace_dim=8, n_clusters=64,
                      kmeans_iters=3, alpha=0.1, beta=0.2, k=K)
    return build(data, cfg), cfg, data


# ------------------------------------------------------------ sampling --
def test_sample_rate_zero_returns_null_span():
    tr = obst.Tracer(sample_rate=0.0)
    span = tr.start_trace("x")
    assert span is obst.NULL_SPAN
    assert not span  # falsy: call sites can skip optional work
    assert span.child("y") is span  # children are itself
    span.annotate(a=1)
    span.finish()  # no-op, records nothing
    assert tr.spans() == []
    assert tr.dropped == 1


def test_sample_rate_one_records():
    tr = obst.Tracer(sample_rate=1.0)
    with tr.start_trace("root") as root:
        assert root  # truthy
        root.child("stage").finish(ok=True)
    spans = tr.spans()
    assert [s["name"] for s in spans] == ["stage", "root"]
    stage, rootrec = spans
    assert stage["trace_id"] == rootrec["trace_id"]
    assert stage["parent_id"] == rootrec["span_id"]
    assert rootrec["parent_id"] is None
    assert stage["attrs"] == {"ok": True}


def test_sampling_is_seed_deterministic():
    a = obst.Tracer(sample_rate=0.5, seed=42)
    b = obst.Tracer(sample_rate=0.5, seed=42)
    kept_a = [bool(a.start_trace("x")) for _ in range(64)]
    kept_b = [bool(b.start_trace("x")) for _ in range(64)]
    assert kept_a == kept_b
    assert 0 < sum(kept_a) < 64  # genuinely probabilistic, not all/none


def test_bad_sample_rate_raises():
    with pytest.raises(ValueError):
        obst.Tracer(sample_rate=1.5)


def test_ring_is_bounded():
    tr = obst.Tracer(sample_rate=1.0, capacity=8)
    for i in range(50):
        tr.start_trace("t", i=i).finish()
    spans = tr.spans()
    assert len(spans) == 8
    assert [s["attrs"]["i"] for s in spans] == list(range(42, 50))
    tr.clear()
    assert tr.spans() == []


# ------------------------------------------------------ chrome export --
def test_to_chrome_structure(tmp_path):
    tr = obst.Tracer(sample_rate=1.0)
    with tr.start_trace("root"):
        pass
    doc = tr.to_chrome()
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 1 and xs[0]["name"] == "root"
    for field in ("ts", "dur", "pid", "tid", "args"):
        assert field in xs[0]
    assert ms and ms[0]["name"] == "thread_name"
    out = tmp_path / "trace.json"
    assert tr.dump_chrome(str(out)) == 1
    assert out.exists()


def test_set_default_tracer_roundtrip():
    mine = obst.Tracer(sample_rate=1.0)
    prev = obst.set_default_tracer(mine)
    try:
        assert obst.default_tracer() is mine
    finally:
        obst.set_default_tracer(prev)
    assert obst.default_tracer() is prev


# ------------------------------------- async pipeline span parenting --
def test_async_request_spans_form_one_rooted_tree(tiny_index):
    """Satellite acceptance: a traced request crossing submit() ->
    AnnFuture -> drain worker -> WorkerPool recall probe still yields
    ONE rooted span tree — propagation is explicit (the span rides the
    pending record / task kwargs), not thread-local."""
    index, cfg, _data = tiny_index
    tracer = obst.Tracer(sample_rate=1.0, capacity=4096)
    engine = AnnServingEngine(index, cfg, async_mode=True, tracer=tracer,
                              recall_probe_every=2, max_batch=8)
    rng = np.random.default_rng(1)
    try:
        futures = [
            engine.submit(AnnRequest(
                rng.integers(0, 30, D).astype(np.float32), k=K))
            for _ in range(24)
        ]
        for f in futures:
            f.result(timeout=60.0)
    finally:
        engine.close()
    # probes are pool tasks; give them a beat to finish their spans
    from repro.serving.scheduler import get_shared_pool

    get_shared_pool().join(timeout=30.0)

    spans = tracer.spans()
    names = {s["name"] for s in spans}
    assert {"ann-request", "queue-wait", "batch-form", "kernel"} <= names
    assert "recall-probe" in names

    by_trace: dict[int, list[dict]] = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
    roots = [s for s in spans
             if s["parent_id"] is None and s["name"] == "ann-request"]
    assert len(roots) == 24
    for tid, group in by_trace.items():
        ids = {s["span_id"] for s in group}
        n_roots = sum(1 for s in group if s["parent_id"] is None)
        assert n_roots == 1, f"trace {tid} has {n_roots} roots"
        for s in group:
            if s["parent_id"] is not None:
                assert s["parent_id"] in ids, (
                    f"orphan span {s['name']} in trace {tid}"
                )
    # the tree genuinely crossed threads: submitters, the drain worker
    # and the probe pool all contributed spans
    assert len({s["tid"] for s in spans}) >= 2


def test_wal_group_commit_spans(tmp_path, tiny_index):
    """Durability path: WAL flushes trace as their own roots with an
    fsync child; mutations trace wal-append under the insert span."""
    _index, cfg, data = tiny_index
    from repro.ann import MutableAnnIndex

    tracer = obst.Tracer(sample_rate=1.0)
    prev = obst.set_default_tracer(tracer)
    try:
        from repro.ann import AnnIndex

        m = MutableAnnIndex(
            AnnIndex.build(data[:256], cfg),
            wal_dir=str(tmp_path / "wal"), durability="sync",
        )
        rng = np.random.default_rng(2)
        m.insert(rng.integers(0, 30, (4, D)).astype(np.float32))
        m.delete([0, 1])
        m.close()
    finally:
        obst.set_default_tracer(prev)
    spans = tracer.spans()
    names = {s["name"] for s in spans}
    assert {"insert", "wal-append", "wal-commit", "wal-flush",
            "fsync"} <= names
    flushes = [s for s in spans if s["name"] == "wal-flush"]
    fsyncs = [s for s in spans if s["name"] == "fsync"]
    assert flushes and fsyncs
    flush_ids = {s["span_id"] for s in flushes}
    assert all(s["parent_id"] in flush_ids for s in fsyncs)


# ------------------------------------------------------------- soak --
def test_latency_accounting_is_flat_memory_over_10k_requests(tiny_index):
    """Satellite acceptance: the engine used to append every latency to
    an unbounded list; 10k requests must now leave only fixed-size
    histogram shards behind (and telemetry percentiles keep working)."""
    index, cfg, _data = tiny_index
    engine = AnnServingEngine(index, cfg, result_cache_size=8, max_batch=8)
    rng = np.random.default_rng(3)
    q = rng.integers(0, 30, D).astype(np.float32)
    reqs = [AnnRequest(q, k=K)] * 100
    try:
        for _ in range(100):  # 10_000 requests, cache-hit dominated
            engine.search(reqs)
        assert not hasattr(engine, "_latencies")
        # bounded accounting: one fixed-size shard per observing thread
        shards = engine._lat_hist._shards
        assert len(shards) <= 4
        assert all(len(sh.counts) == NBUCKETS for sh in shards)
        t = engine.telemetry()
        assert t["requests_served"] == 10_000
        assert 0.0 <= t["latency_p50_s"] <= t["latency_p99_s"]
    finally:
        engine.close()


def test_cache_hit_latency_reports_exact_zero(tiny_index):
    """The bounded histogram must not cost the old behavior: pure
    cache-hit traffic reported p50 == 0.0 exactly (zeros are counted
    outside the log buckets), so it still does."""
    index, cfg, _data = tiny_index
    engine = AnnServingEngine(index, cfg, result_cache_size=8)
    rng = np.random.default_rng(4)
    q = rng.integers(0, 30, D).astype(np.float32)
    try:
        engine.search([AnnRequest(q, k=K)])  # miss: executes
        engine.reset_telemetry()
        for _ in range(50):
            engine.search([AnnRequest(q, k=K)])  # all hits
        assert engine.telemetry()["latency_p50_s"] == 0.0
    finally:
        engine.close()


# ------------------------------------------- scoped stages, profiler clock --
def test_stage_names_must_start_with_taco():
    with pytest.raises(ValueError):
        obst.Tracer().stage("build")


def test_stages_nest_in_the_ring_when_sampled():
    tr = obst.Tracer(sample_rate=1.0)
    with tr.stage("taco.outer", i=1) as outer:
        assert outer.span
        with tr.stage("taco.inner") as inner:
            inner.annotate(rows=3)
            inner.span.child("request-stage").finish()
    by_name = {s["name"]: s for s in tr.spans()}
    assert set(by_name) == {"taco.outer", "taco.inner", "request-stage"}
    assert by_name["taco.outer"]["parent_id"] is None
    assert by_name["taco.outer"]["attrs"] == {"i": 1}
    assert by_name["taco.inner"]["parent_id"] == by_name["taco.outer"]["span_id"]
    assert by_name["taco.inner"]["attrs"] == {"rows": 3}
    assert by_name["request-stage"]["parent_id"] == by_name["taco.inner"]["span_id"]
    chrome = [e["name"] for e in tr.to_chrome()["traceEvents"] if e["ph"] == "X"]
    assert sorted(chrome) == ["request-stage", "taco.inner", "taco.outer"]


def test_unsampled_stage_records_nothing_and_nests_nothing():
    tr = obst.Tracer(sample_rate=0.0)
    with tr.stage("taco.outer") as outer:
        assert outer.span is obst.NULL_SPAN
        with tr.stage("taco.inner") as inner:
            assert inner.span is obst.NULL_SPAN
            inner.annotate(rows=3)
    assert tr.spans() == []
    assert tr.dropped == 1  # one root coin; the inner stage followed it


def test_stage_is_recorded_by_the_ring_or_a_profiler_session(tmp_path):
    import jax

    with obst.Tracer(sample_rate=0.0).stage("taco.off") as off:
        assert not off.recorded
    with obst.Tracer(sample_rate=1.0).stage("taco.ring") as ring:
        assert ring.recorded
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obst.Tracer(sample_rate=0.0).stage("taco.profiled") as profiled:
            assert profiled.recorded
    finally:
        jax.profiler.stop_trace()


def test_stage_nests_under_another_tracers_stage():
    """The innermost open stage decides the ring a stage lands in, whatever
    tracer opened it: an engine's own tracer receives the searcher's
    stages, which the process default tracer opens."""
    mine = obst.Tracer(sample_rate=1.0)
    other = obst.Tracer(sample_rate=0.0)
    with mine.stage("taco.a"):
        with other.stage("taco.b"):
            pass
    with other.stage("taco.c"):
        pass
    assert other.spans() == []
    assert [s["name"] for s in mine.spans()] == ["taco.b", "taco.a"]


def _host_stages(log_dir):
    """``[(name, start_ns, end_ns, stats, line)]`` of every ``taco.*`` event
    on a host plane of the trace under ``log_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("taco."):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats), (plane.name, line_no)))
    return out


def _parent_of(stage, stages):
    """The innermost other stage on the same line that holds ``stage``."""
    name, s, e, _stats, line = stage
    holders = [o for o in stages if o is not stage and o[4] == line
               and o[1] <= s and e <= o[2]]
    return min(holders, key=lambda o: o[2] - o[1]) if holders else None


def test_build_and_batch_stages_land_on_the_profilers_host_plane(tmp_path):
    """Under a profiler session the build phases and the engine's batch
    cycle are host-plane events on the device trace's clock, each nested
    in its parent; a batch of a new bucket names its compile; with a
    sampling tracer the same stages land in the ring too."""
    import jax

    from repro.ann import AnnIndex

    rng = np.random.default_rng(5)
    data = rng.integers(0, 30, (512, D)).astype(np.float32)
    cfg = taco_config(n_subspaces=3, subspace_dim=8, n_clusters=64,
                      kmeans_iters=3, alpha=0.1, beta=0.2, k=K)
    tracer = obst.Tracer(sample_rate=1.0)
    prev = obst.set_default_tracer(tracer)
    jax.profiler.start_trace(str(tmp_path))
    try:
        index = AnnIndex.build(data, cfg)
        engine = index.engine("single", cfg=cfg, max_batch=8)
        engine.search([AnnRequest(q, k=K) for q in data[:3]])   # bucket 4
        engine.search([AnnRequest(q, k=K) for q in data[:7]])   # bucket 8
        engine.search([AnnRequest(q, k=K) for q in data[3:10]])  # bucket 8 again
        engine.close()
    finally:
        jax.profiler.stop_trace()
        obst.set_default_tracer(prev)

    stages = _host_stages(tmp_path)
    names = [st[0] for st in stages]
    parents = {"taco.build": None}
    for phase in ("transform", "subspace", "norms"):
        parents[f"taco.build.{phase}"] = "taco.build"
    parents["taco.engine.batch"] = None
    for child in ("engine.form", "engine.stage", "engine.resolve",
                  "searcher.compile", "searcher.dispatch", "searcher.device",
                  "searcher.fetch"):
        parents[f"taco.{child}"] = "taco.engine.batch"
    assert set(names) == set(parents)
    for st in stages:
        parent = _parent_of(st, stages)
        assert (parent[0] if parent else None) == parents[st[0]], st
    subs = sorted(st[3]["i"] for st in stages if st[0] == "taco.build.subspace")
    assert subs == list(range(cfg.n_subspaces))
    assert names.count("taco.build.transform") == names.count("taco.build.norms") == 1

    batches = sorted((st for st in stages if st[0] == "taco.engine.batch"),
                     key=lambda st: st[1])
    assert [(b[3]["bucket"], b[3]["rows"], b[3]["k"]) for b in batches] == [
        (4, 3, K), (8, 7, K), (8, 7, K)]
    assert [b[3]["batch"] for b in batches] == [1, 2, 3]
    searcher = [[c[0] for c in stages if c[0].startswith("taco.searcher.")
                 and _parent_of(c, stages) is b] for b in batches]
    assert searcher == [
        ["taco.searcher.compile", "taco.searcher.device", "taco.searcher.fetch"],
        ["taco.searcher.compile", "taco.searcher.device", "taco.searcher.fetch"],
        ["taco.searcher.dispatch", "taco.searcher.device", "taco.searcher.fetch"],
    ]

    ring = [s["name"] for s in tracer.spans()]
    assert sorted(n for n in ring if n.startswith("taco.")) == sorted(names)
    chrome = {e["name"] for e in tracer.to_chrome()["traceEvents"] if e["ph"] == "X"}
    assert set(names) <= chrome
    # each request's kernel span joins its batch by number
    kernels = [s for s in tracer.spans() if s["name"] == "kernel"]
    assert sorted({s["attrs"]["batch"] for s in kernels}) == [1, 2, 3]


def test_sharded_searcher_splits_device_and_fetch(tiny_index):
    from repro.ann.searcher import ShardedSearcher

    index, cfg, data = tiny_index
    tracer = obst.Tracer(sample_rate=1.0)
    searcher = ShardedSearcher(index, cfg, shards=1)
    prev = obst.set_default_tracer(tracer)
    try:
        with tracer.stage("taco.test"):
            searcher.search(data[:2])
            searcher.search(data[:2])
    finally:
        obst.set_default_tracer(prev)
    names = [s["name"] for s in tracer.spans()]
    assert names == ["taco.searcher.compile", "taco.searcher.device",
                     "taco.searcher.fetch", "taco.searcher.dispatch",
                     "taco.searcher.device", "taco.searcher.fetch", "taco.test"]
