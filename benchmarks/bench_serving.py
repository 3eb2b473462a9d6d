"""ANN serving throughput: batched engine vs one-query-at-a-time baselines.

Two baselines bracket the status quo:

  * ``adhoc``  — what callers do today (see ROADMAP/ISSUE): each request
    issues its own ``jax.jit(query)`` closure, so every caller pays
    tracing + compilation. This is the request path the engine replaces.
  * ``cached`` — best-case steady state without an engine: one shared
    pre-compiled closure invoked per request (batch 1). Isolates the pure
    micro-batching win from the compile-amortization win.

The engine micro-batches the same request stream into padded shape
buckets with a jit cache keyed on (bucket, k, cfg), and is timed twice:
with the gather re-rank (``rerank="gather"``) and with the streaming
masked-full pipeline (``rerank="masked_full"`` — no candidate cap, no
(Q, n) intermediates; see kernels/schist.py + kernels/masked_rerank.py).
Per-stage timings for both pipelines are reported alongside. ``--shards
N`` also times the corpus-sharded backend (``backend="sharded"``) on an
N-way data mesh; on a CPU dev box the devices are forced via
``XLA_FLAGS=--xla_force_host_platform_device_count`` (set before jax
initializes — hence the deferred imports). ``--producers P`` also times
the async pipeline: P concurrent threads submitting to the background
drain worker (per-request futures, ``--deadline-ms`` SLOs), recording
async-vs-sync QPS/p99 plus queue-depth / deadline-miss / shed stats.
``--churn M`` benches a mixed
query/mutation workload three times — ``durability="none"``, ``"async"``
(WAL group-commit via the shared worker pool), ``"sync"`` (fsync on the
caller's path) — so the cost of crash safety is a number, not a guess
(the acceptance bar: async within 15% of none). ``--json PATH``
persists the numbers (QPS, p50/p99, stage timings) for trend tracking —
the committed baseline lives at BENCH_serving.json in the repo root.
Two observability rows ride along: serving-stage percentiles pulled from
the :mod:`repro.obs` metrics registry (the same histograms ``/metrics``
exports — queue wait, batch exec, WAL flush/fsync, compaction) and an
``engine-metrics-off`` row timed with the registry's global kill switch
thrown, so the whole cost of instrumentation is a committed number.

  PYTHONPATH=src python benchmarks/bench_serving.py [--n 20000] [--d 64] \
      [--requests 32] [--pressure 16] [--shards 4] [--json BENCH_serving.json]
"""
from __future__ import annotations

import argparse
import json
import time


def stage_timings(index, cfg, queries):
    """Median per-stage wall times (us) of both re-rank pipelines on one
    warm batch: SC+selection vs histogram+threshold, then re-rank."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.selection import query_aware_threshold, select_candidates
    from repro.core.taco import (
        _collision_inputs,
        compute_sc_scores,
        data_norms_of,
        rerank,
    )
    from repro.kernels import ops

    def time_call(fn, *args, warmup=1, iters=3):
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts) * 1e6)

    queries = jnp.asarray(queries)
    beta_n = float(cfg.beta * index.n)
    cap = min(index.n, max(cfg.cap_for(index.n), cfg.k))

    # --- gather pipeline stages -------------------------------------------
    sc_fn = jax.jit(lambda q: compute_sc_scores(index, q, cfg)[0])
    sc = jax.block_until_ready(sc_fn(queries))
    sel_fn = jax.jit(
        lambda s: select_candidates(s, beta_n, cfg.n_subspaces, cap,
                                    mode=cfg.selection)
    )
    cand_ids, valid, _t, _c = jax.block_until_ready(sel_fn(sc))
    grr_fn = jax.jit(
        lambda q, ci, va: rerank(index.data, q, ci, va, cfg.k,
                                 data_norms_of(index))
    )
    # --- masked-full pipeline stages --------------------------------------
    ci_fn = jax.jit(lambda q: _collision_inputs(index, q, cfg)[:5])
    d1s, d2s, a1s, a2s, taus = jax.block_until_ready(ci_fn(queries))
    # legacy before-row (ISSUE 8): the pre-optimization collision-input
    # stage — lax.sort-based activation, assignment stacks rebuilt inline —
    # timed alongside so the artifact carries the before/after delta
    import dataclasses as _dc

    legacy_cfg = _dc.replace(cfg, activation="sort_lax")
    ci_legacy_fn = jax.jit(
        lambda q: _collision_inputs(index, q, legacy_cfg, hoist=False)[:5]
    )
    jax.block_until_ready(ci_legacy_fn(queries))
    hist_fn = jax.jit(lambda *a: ops.schist(*a, impl="jnp"))
    hist = jax.block_until_ready(hist_fn(d1s, d2s, a1s, a2s, taus))
    th_fn = jax.jit(
        lambda h: query_aware_threshold(h, beta_n, cfg.n_subspaces)[0]
    )
    thresh = jax.block_until_ready(th_fn(hist))
    mrr_fn = jax.jit(
        lambda *a: ops.masked_rerank(*a, index.data, data_norms_of(index),
                                     queries, cfg.k, impl="jnp")
    )
    return {
        "gather": {
            "sc_scores_us": time_call(sc_fn, queries),
            "select_candidates_us": time_call(sel_fn, sc),
            "gather_rerank_us": time_call(grr_fn, queries, cand_ids, valid),
        },
        "masked_full": {
            "collision_inputs_us": time_call(ci_fn, queries),
            "collision_inputs_legacy_us": time_call(ci_legacy_fn, queries),
            "schist_us": time_call(hist_fn, d1s, d2s, a1s, a2s, taus),
            "threshold_us": time_call(th_fn, hist),
            "masked_rerank_us": time_call(
                mrr_fn, d1s, d2s, a1s, a2s, taus, thresh
            ),
        },
    }


def bench(n=20000, d=64, k=10, requests=32, pressure=16, shards=0, seed=0,
          churn=0, producers=0, deadline_ms=50.0, json_path=None):
    import dataclasses

    import jax
    import numpy as np

    from repro.ann import AnnIndex
    from repro.core import make_query_fn, taco_config
    from repro.data import even_shard_total, gmm_dataset, make_queries
    from repro.serving import AnnRequest

    data, held_out = make_queries(
        gmm_dataset(even_shard_total(n, 128, shards), d, seed=seed), 128
    )
    cfg = taco_config(n_subspaces=6, subspace_dim=8, n_clusters=1024,
                      alpha=0.05, beta=0.02, k=k)
    print(f"building TaCo index: n={data.shape[0]} d={d} ...", flush=True)
    ann = AnnIndex.build(data, cfg)
    index = ann.sc_index
    rng = np.random.default_rng(seed)
    qs = held_out[rng.integers(0, held_out.shape[0], requests)]

    # --- adhoc: a fresh jit closure per request (the pre-engine caller
    # path, kept as the legacy-wrapper baseline) --------------------------
    t0 = time.perf_counter()
    for i in range(requests):
        fn = make_query_fn(index, cfg)  # per-caller closure: traces+compiles
        jax.block_until_ready(fn(qs[i : i + 1]))
    adhoc_s = time.perf_counter() - t0

    # --- cached: one shared pre-compiled closure, one query per call ------
    naive = make_query_fn(index, cfg)
    jax.block_until_ready(naive(qs[:1]))  # compile outside the timing
    t0 = time.perf_counter()
    for i in range(requests):
        jax.block_until_ready(naive(qs[i : i + 1]))
    cached_s = time.perf_counter() - t0

    # --- batched engine: waves of `pressure` concurrent requests ----------
    def run_engine(placement, run_cfg, **bk):
        engine = ann.engine(placement, cfg=run_cfg,
                            max_batch=max(pressure, 1), **bk)
        engine.search([AnnRequest(query=q) for q in qs[:pressure]])  # warm
        engine.reset_telemetry()
        t0 = time.perf_counter()
        for lo in range(0, requests, pressure):
            engine.search([AnnRequest(query=q) for q in qs[lo : lo + pressure]])
        return engine, time.perf_counter() - t0

    cfg_masked = dataclasses.replace(cfg, rerank="masked_full")
    engine, engine_s = run_engine("single", cfg)
    masked_engine, masked_s = run_engine("single", cfg_masked)

    # --- metrics overhead: the same gather row with the registry's global
    # kill switch thrown — the delta is the whole cost of instrumentation
    # (the acceptance bar: metrics-on within 5% of metrics-off) ------------
    from repro.obs import metrics as obsm

    try:
        obsm.set_enabled(False)
        off_engine, metrics_off_s = run_engine("single", cfg)
        off_engine.close()
    finally:
        obsm.set_enabled(True)
    rows = [
        ("adhoc-jit", adhoc_s),
        ("cached-jit", cached_s),
        ("engine-gather", engine_s),
        ("engine-metrics-off", metrics_off_s),
        ("engine-masked", masked_s),
    ]

    sharded_t = None
    if shards > 1:
        sharded_engine, sharded_s = run_engine("sharded", cfg, shards=shards)
        rows.append((f"engine-{shards}shard", sharded_s))
        sharded_t = sharded_engine.telemetry()

    # --- async: N producer threads drive the background drain worker ------
    # same request stream as the sync engine rows (the parity the tests
    # pin), measured as one concurrent wall-clock window; per-request
    # deadlines exercise the early-close path and the miss accounting
    async_t = None
    async_s = None
    if producers > 0:
        import threading

        a_engine = ann.engine(
            "single", cfg=cfg, max_batch=max(pressure, 1), async_mode=True,
            default_deadline_s=deadline_ms / 1e3 if deadline_ms else None,
        )
        a_engine.search([AnnRequest(query=q) for q in qs[:pressure]])  # warm
        a_engine.reset_telemetry()
        n_p = min(producers, requests)
        slices = [list(range(requests))[i::n_p] for i in range(n_p)]

        def producer(idxs):
            futures = [a_engine.submit(AnnRequest(query=qs[i])) for i in idxs]
            for f in futures:
                f.result(timeout=120.0)

        threads = [threading.Thread(target=producer, args=(s,), daemon=True)
                   for s in slices]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        async_s = time.perf_counter() - t0
        rows.append((f"engine-async{n_p}p", async_s))
        async_t = a_engine.telemetry()
        a_engine.close()

    # --- churn: mixed query/insert/delete workload through a mutable
    # index (delta scan + tombstone mask + policy-driven compaction) ------
    churn_t = None
    churn_qps: dict = {}
    churn_wal_t = None
    if churn > 0:
        import tempfile

        from repro.ann import CompactionPolicy
        from repro.ann.mutable import churn_wave

        reps = 5  # repeat the wave loop so the per-mode timing is not
        # dominated by one fsync's scheduling noise; qps stays per-request

        def run_churn(durability, wal_dir=None):
            mutable = ann.mutable(
                policy=CompactionPolicy(max_delta_rows=max(8, 4 * churn)),
                durability=durability, wal_dir=wal_dir,
            )
            try:
                c_engine = mutable.engine(max_batch=max(pressure, 1))
                c_engine.search([AnnRequest(query=q) for q in qs[:pressure]])
                c_engine.reset_telemetry()
                churn_rng = np.random.default_rng(seed + 7)
                live_new: list = []
                t0 = time.perf_counter()
                for _ in range(reps):
                    for lo in range(0, requests, pressure):
                        churn_wave(mutable, churn_rng, live_new, churn,
                                   engine=c_engine)
                        c_engine.search(
                            [AnnRequest(query=q) for q in qs[lo : lo + pressure]]
                        )
                elapsed = (time.perf_counter() - t0) / reps
                return c_engine.telemetry(), elapsed
            finally:
                mutable.close()  # flushes + closes the WAL on any exit

        run_churn("none")  # absorb the delta-scan jit compiles untimed, so
        # the three timed rows below are comparable (first-run bias)
        churn_t, churn_s = run_churn("none")
        rows.append((f"engine-churn{churn}", churn_s))
        churn_qps["none"] = requests / churn_s
        # durability overhead: the same workload journaled through the WAL.
        # TemporaryDirectory as a context manager guarantees the WAL dirs
        # are removed even if a wave raises (no stranded temp dirs).
        for mode in ("async", "sync"):
            with tempfile.TemporaryDirectory(prefix=f"bench-wal-{mode}-") as wd:
                mode_t, mode_s = run_churn(mode, wal_dir=wd)
            rows.append((f"engine-churn{churn}-{mode}", mode_s))
            churn_qps[mode] = requests / mode_s
            if mode == "async":
                churn_wal_t = mode_t

    stages = stage_timings(index, cfg, qs[:pressure])

    # --- serving-stage percentiles from the process metrics registry: the
    # same numbers /metrics exports, folded into the bench artifact so the
    # trend file tracks queue-wait/exec/WAL/compaction distributions too --
    obs_stages = {}
    for fam in obsm.default_registry().families():
        if fam.cls is not obsm.Histogram or not fam.name.startswith("taco_"):
            continue
        for lv, child in fam.children():
            key = fam.name if not lv else f"{fam.name}[{','.join(lv)}]"
            s = child.summary()
            if s["count"]:
                obs_stages[key] = {k2: s[k2] for k2 in
                                   ("count", "p50", "p90", "p99")}

    t = engine.telemetry()
    mt = masked_engine.telemetry()
    print(f"requests={requests} pressure={pressure}")
    for name, secs in rows:
        print(f"  {name:14s}: {secs:7.3f}s  {requests / secs:8.0f} queries/s")
    print(f"  metrics overhead: on {requests / engine_s:.0f} q/s vs "
          f"off {requests / metrics_off_s:.0f} q/s "
          f"({engine_s / metrics_off_s - 1:+.1%} wall)")
    for key, s in sorted(obs_stages.items()):
        print(f"  obs[{key}]: n={s['count']}  p50 {s['p50'] * 1e3:.3f} ms  "
              f"p99 {s['p99'] * 1e3:.3f} ms")
    print(f"  gather p50 {t['latency_p50_s'] * 1e3:.2f} ms  p99 "
          f"{t['latency_p99_s'] * 1e3:.2f} ms  trunc {t['truncation_rate']:.3f}  "
          f"compiles {t['compiles_per_bucket']}")
    print(f"  masked p50 {mt['latency_p50_s'] * 1e3:.2f} ms  p99 "
          f"{mt['latency_p99_s'] * 1e3:.2f} ms  trunc {mt['truncation_rate']:.3f}")
    for mode, st in stages.items():
        pretty = "  ".join(f"{k2} {v:.0f}" for k2, v in st.items())
        print(f"  stages[{mode}]: {pretty}")
    if sharded_t is not None:
        print(f"  sharded p50 {sharded_t['latency_p50_s'] * 1e3:.2f} ms  "
              f"combine {sharded_t['combine_pairs_per_query']:.0f} pairs/query  "
              f"per-shard candidates/query "
              f"{[round(c) for c in sharded_t['shard_candidates_mean']]}")
    if async_t is not None:
        print(f"  async({min(producers, requests)} producers) "
              f"p50 {async_t['latency_p50_s'] * 1e3:.2f} ms  "
              f"p99 {async_t['latency_p99_s'] * 1e3:.2f} ms  "
              f"queue peak {async_t['queue_depth_peak']}  "
              f"early closes {async_t['batches_closed_early']}  "
              f"deadline misses {async_t['deadline_misses']}  "
              f"shed {async_t['shed']}")
    if churn_t is not None:
        ms = churn_t["mutable"]
        print(f"  churn p50 {churn_t['latency_p50_s'] * 1e3:.2f} ms  "
              f"{ms['compactions']} compactions  "
              f"{churn_t['index_swaps']} swaps  "
              f"{ms['n_live']} live ({ms['n_delta_live']} delta, "
              f"{ms['n_tombstones']} tombstones)")
        w = (churn_wal_t or {}).get("wal")
        print(f"  churn durability qps: "
              + "  ".join(f"{m} {q:.0f}" for m, q in churn_qps.items())
              + (f"  (async group-commit mean {w['mean_group']:.1f}, "
                 f"{w['fsyncs']} fsyncs / {w['appends']} appends)"
                 if w else ""))
    print(f"  speedup vs adhoc : {adhoc_s / engine_s:7.2f}x")
    print(f"  speedup vs cached: {cached_s / engine_s:7.2f}x")
    print(f"  masked vs gather : {engine_s / masked_s:7.2f}x")

    if json_path:
        payload = {
            "config": {"n": int(data.shape[0]), "d": d, "k": k,
                       "requests": requests, "pressure": pressure,
                       "shards": shards, "backend": jax.default_backend()},
            "rows": [
                {"name": name, "seconds": secs, "qps": requests / secs}
                for name, secs in rows
            ],
            "gather": {"latency_p50_s": t["latency_p50_s"],
                       "latency_p99_s": t["latency_p99_s"],
                       "truncation_rate": t["truncation_rate"]},
            "masked_full": {"latency_p50_s": mt["latency_p50_s"],
                            "latency_p99_s": mt["latency_p99_s"],
                            "truncation_rate": mt["truncation_rate"]},
            "stage_timings_us": stages,
            # process-cumulative over every row of this bench run —
            # including jit-compile warmup batches, which dominate the
            # tail; read these for distribution shape, serve_ann
            # --metrics-port for steady-state numbers
            "obs_stage_percentiles_s": obs_stages,
            "obs_overhead": {
                "metrics_on_s": engine_s,
                "metrics_off_s": metrics_off_s,
                "on_vs_off_wall": engine_s / metrics_off_s,
            },
            "masked_vs_gather_qps": engine_s / masked_s,
        }
        if sharded_t is not None:
            payload["sharded"] = {
                "latency_p50_s": sharded_t["latency_p50_s"],
                "combine_pairs_per_query": sharded_t["combine_pairs_per_query"],
                "shard_candidates_mean": sharded_t["shard_candidates_mean"],
            }
        if async_t is not None:
            payload["async"] = {
                "producers": min(producers, requests),
                "deadline_ms": deadline_ms,
                "seconds": async_s,
                "qps": requests / async_s,
                "latency_p50_s": async_t["latency_p50_s"],
                "latency_p99_s": async_t["latency_p99_s"],
                "queue_depth_peak": async_t["queue_depth_peak"],
                "batches_closed_early": async_t["batches_closed_early"],
                "deadline_misses": async_t["deadline_misses"],
                "shed": async_t["shed"],
                "degraded": async_t["degraded"],
                "async_vs_sync_qps": engine_s / async_s,
            }
        if churn_t is not None:
            payload["churn"] = {
                "per_wave_inserts": churn,
                "latency_p50_s": churn_t["latency_p50_s"],
                "compactions": churn_t["mutable"]["compactions"],
                "index_swaps": churn_t["index_swaps"],
                "n_live": churn_t["mutable"]["n_live"],
                "qps_by_durability": churn_qps,
                "async_vs_none_qps": churn_qps["async"] / churn_qps["none"],
            }
            if churn_wal_t is not None and "wal" in churn_wal_t:
                payload["churn"]["wal_async"] = {
                    k2: churn_wal_t["wal"][k2]
                    for k2 in ("appends", "fsyncs", "group_commits",
                               "mean_group", "max_group", "bytes_appended")
                }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, default=float)
        print(f"wrote {json_path}")
    return adhoc_s / engine_s


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--pressure", type=int, default=16)
    ap.add_argument("--shards", type=int, default=0,
                    help="also bench the sharded backend on this many devices")
    ap.add_argument("--churn", type=int, default=0, metavar="M",
                    help="also bench a mixed query/mutation workload: M "
                         "inserts + M//2 deletes per wave through a "
                         "MutableAnnIndex engine (policy compaction + swap)")
    ap.add_argument("--producers", type=int, default=0, metavar="P",
                    help="also bench the async pipeline: P concurrent "
                         "producer threads submitting to the background "
                         "drain worker (0 = skip)")
    ap.add_argument("--deadline-ms", type=float, default=50.0, metavar="MS",
                    help="per-request SLO for the async row (0 = none); "
                         "misses and early batch closes are recorded")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", nargs="?", const="BENCH_serving.json",
                    default=None, metavar="PATH",
                    help="write results as JSON (default path when bare)")
    args = ap.parse_args(argv)
    if args.pressure < 1:
        ap.error("--pressure must be >= 1")
    if args.shards > 1:
        # must precede any jax import/initialization (CPU dev boxes)
        from repro.launch.hostdev import force_host_devices

        force_host_devices(args.shards)
    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()
    bench(n=args.n, d=args.d, k=args.k, requests=args.requests,
          pressure=args.pressure, shards=args.shards, seed=args.seed,
          churn=args.churn, producers=args.producers,
          deadline_ms=args.deadline_ms, json_path=args.json)


if __name__ == "__main__":
    main()
