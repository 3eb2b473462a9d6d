"""One run of one cell: data, build, engine, window, check, metrics.

The system under test is the served path of ``repro``: ``AnnIndex.build``
and an ``AnnServingEngine`` driven through ``submit`` and its futures. The
benchmark gives it generated inputs and reads back its answers, its
``repro.obs`` counters and the profiler's trace; nothing else.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from tacobench import check, datagen, peaks, reference, spec, traffic as tr


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: spec.Cell
    traffic: tr.Traffic
    shape: dict  # n, d, n_sub, sqrt_k
    setup_s: float
    build_s: float
    window: tr.Window
    in_window: list  # the requests answered inside the window
    check: dict
    counters: dict  # repro.obs registry snapshot over the window
    memory: dict
    peak: dict | None
    trace: object = None  # tracereduce.TraceSummary in a traced run


def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs


def taco_cfg(config: dict, precision: str | None = None):
    from repro.core import taco_config

    fields = dict(config["taco"])
    if precision is not None:
        fields["precision"] = precision
    return taco_config(**fields)


def _registry():
    from repro.obs import default_registry

    return default_registry()


@dataclasses.dataclass
class Prepared:
    """A cell's set-up: data, the built index and its warmed engine."""

    devs: list
    traffic: tr.Traffic
    cfg: object  # repro SCConfig
    corpus: object
    pool: np.ndarray
    index: object
    engine: object
    build_s: float
    peak: dict | None


def prepare(cell: spec.Cell, seed: int, *, require_tpu: bool = True,
            precision: str | None = None,
            log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> Prepared:
    """Make the data, build the index through ``AnnIndex.build`` (timed),
    open the engine (not yet serving) and run every bucket the traffic
    forms once, so that the window compiles nothing."""
    import jax

    from repro.ann import AnnIndex

    devs = devices(cell.chips, require_tpu)
    traffic = tr.load(cell.traffic)
    cfg = taco_cfg(cell.config, precision)
    peak = peaks.peak(devs[0].device_kind) if require_tpu else None

    t = time.perf_counter()
    corpus, queries = datagen.make_data(seed, cell.config["data"])
    jax.block_until_ready((corpus, queries))
    pool = np.asarray(queries)
    log(f"data: {corpus.shape} {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    index = AnnIndex.build(corpus, cfg)
    jax.block_until_ready(index.sc_index)
    build_s = time.perf_counter() - t
    log(f"build: n={index.n} d={index.d} {build_s:.3f} s")

    engine = index.engine("single", cfg=cfg, async_mode=False,
                          **cell.config.get("engine", {}))
    t = time.perf_counter()
    for b in traffic.warm_buckets:
        engine.searcher.search(pool[:b], k=traffic.k)
    log(f"warm-up of buckets {traffic.warm_buckets}: "
        f"{time.perf_counter() - t:.3f} s")
    return Prepared(devs, traffic, cfg, corpus, pool, index, engine, build_s,
                    peak)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             precision: str | None = None, keep_trace: str | None = None,
             grace_s: float = 60.0,
             log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> dict:
    """Run ``cell`` once and return its result line as a dict.

    ``t_start`` is the process's start on ``time.perf_counter``'s clock:
    set-up runs from there to the window. ``precision`` overrides the
    configuration's (the precision control); ``keep_trace`` copies the raw
    trace to that directory; ``grace_s`` is how long after the window an
    answer may still come."""
    import jax

    from repro.serving import AnnRequest

    p = prepare(cell, seed, require_tpu=require_tpu, precision=precision,
                log=log)
    devs, traffic, cfg, corpus, pool = p.devs, p.traffic, p.cfg, p.corpus, p.pool
    engine, build_s, data_cfg = p.engine, p.build_s, cell.config["data"]
    p_peak = p.peak
    shape = {"n": p.index.n, "d": p.index.d, "n_sub": cfg.n_subspaces,
             "sqrt_k": cfg.sqrt_k}
    del p

    rng = np.random.default_rng(int(seed) % (1 << 64))
    seq_pool = tr.draw_queries(rng, len(pool), 1 << 18, traffic)

    def make_request(req):
        return AnnRequest(query=pool[req.pool_index], k=req.k)

    trace_dir = tempfile.mkdtemp(prefix="taco_trace_") if trace else None
    marks = {}

    def on_open():
        marks["setup_s"] = time.perf_counter() - t_start
        _registry().reset()
        if trace:
            marks["span"] = jax.profiler.TraceAnnotation("bench.window")
            marks["span"].__enter__()

    def on_close():
        if trace:
            marks["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            marks["traced"] = True
        marks["counters"] = _registry().snapshot()

    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        window = tr.run_closed(engine, make_request, seq_pool, traffic.k,
                               traffic, seconds, trace=trace,
                               on_open=on_open, on_close=on_close,
                               grace_s=grace_s)
    finally:
        if trace and "traced" not in marks:
            jax.profiler.stop_trace()
        engine.close()
    memory = dict(devs[0].memory_stats() or {})
    summary = None
    if trace:
        from tacobench import tracereduce

        path = tracereduce.find_xplane(trace_dir)
        if keep_trace:
            shutil.copy(path, keep_trace)
        summary = tracereduce.reduce_file(path)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # free the program's state; the reference needs only the corpus
    del engine
    gc.collect()

    in_window = [r for r in window.requests
                 if window.t0 < r.done_at <= window.t1]
    t = time.perf_counter()
    result = check.compare(
        window.requests, in_window, pool, corpus,
        int(data_cfg.get("n_probes", 0)), cell.config["limits"],
        knn=lambda q, k: reference.exact_knn(corpus, q, k),
        dists_of=lambda q, ids: _dists_of(corpus, q, ids))
    log(f"check against the reference: {time.perf_counter() - t:.3f} s")
    run = Run(cell=cell, traffic=traffic, shape=shape,
              setup_s=marks.get("setup_s", math.nan), build_s=build_s,
              window=window, in_window=in_window, check=result,
              counters=marks.get("counters", {}), memory=memory, peak=p_peak,
              trace=summary)
    return _result_line(run, devs, trace, log)


def _dists_of(corpus, queries, ids, block: int = 1024):
    """Exact distances of served ids, in blocks of one shape."""
    out = []
    for lo in range(0, len(ids), block):
        q, i = queries[lo:lo + block], ids[lo:lo + block]
        m = len(i)
        if m < block:
            q = np.concatenate([q, np.repeat(q[-1:], block - m, 0)])
            i = np.concatenate([i, np.repeat(i[-1:], block - m, 0)])
        out.append(np.asarray(reference.exact_sq_dists(corpus, q, i))[:m])
    return np.concatenate(out)


def _result_line(run: Run, devs, trace: bool, log) -> dict:
    wanted = run.cell.per_layer if trace else run.cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(run.memory.get("peak_bytes_in_use", 0))}
    line = {"correct": run.check["correct"],
            "attempted": run.check["checked"],
            "failed": run.check["bad_answers"],
            "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    log("host stalls in the window: " + ", ".join(
        f"{k} {v}" for k, v in run.window.stalls.items()))
    log(f"window: {run.window.t1 - run.window.t0:.3f} s, "
        f"{len(run.in_window)} requests in it, {len(run.window.requests)} sent; "
        f"recall_at_10 {run.check['recall_at_10']}")
    for name, c in run.check["checks"].items():
        side = "max" if "max" in c else "min"
        log(f"check {name}: {c['value']} {side} {c[side]}")
    line["checks"] = run.check["checks"]
    return line
