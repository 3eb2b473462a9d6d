#!/usr/bin/env python3
"""One traced run of a cell that also traces its build, read through the
program's own ``taco.*`` spans.

    python3 bench/trace_spans.py --workload deep10m.bulk --seed 7 --seconds 45 \
        [--keep DIR]

On a TPU: the cell's run as ``bench/run.py --trace 1`` makes it, except that
``AnnIndex.build`` runs inside a profiler session of its own, under a
``bench.build`` annotation, stopped before the window's session starts. The
last line of standard output is one JSON object: ``line`` (the run's
result line, with its end-to-end metrics read as well), ``build``
(:func:`tacobench.spans.build_phases` on the ``XLA Ops`` and on the ``XLA
Modules`` line, the op events' count, and ``build_s_traced``, the build's
wall seconds inside its session), ``engine_host_ms``, and the window's
idle gaps named by the program's spans with the share of idle time they
name. ``--keep`` copies both raw traces there (``build.xplane.pb``,
``window.xplane.pb``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def traced_build(trace_dir: str, timings: dict):
    """``AnnIndex.build`` inside a profiler session of its own."""
    import jax

    from repro.ann import AnnIndex
    from tacobench import spans

    build = AnnIndex.build.__func__

    def traced(cls, data, cfg):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(spans.BUILD_WINDOW):
                t = time.perf_counter()
                index = build(cls, data, cfg)
                jax.block_until_ready(index.sc_index)
                timings["build_s_traced"] = time.perf_counter() - t
        finally:
            jax.profiler.stop_trace()
        return index

    return classmethod(traced)


def run(cell, seed: int, seconds: float, keep: str | None = None,
        t_start: float = T_START, require_tpu: bool = True) -> dict:
    from repro.ann import AnnIndex
    from tacobench import spans, tracereduce
    from tacobench.cell import run_cell

    build_dir = tempfile.mkdtemp(prefix="taco_build_")
    window_dir = tempfile.mkdtemp(prefix="taco_window_")
    timings: dict = {}
    original = AnnIndex.__dict__["build"]
    AnnIndex.build = traced_build(build_dir, timings)
    try:
        cell = dataclasses.replace(cell, per_layer=cell.per_layer + cell.end_to_end)
        line = run_cell(cell, seed, seconds, True, t_start=t_start,
                        require_tpu=require_tpu, keep_trace=window_dir)
    finally:
        AnnIndex.build = original
    build_path = tracereduce.find_xplane(build_dir)
    window_path = tracereduce.find_xplane(window_dir)
    t = time.perf_counter()  # reading and reducing the build trace
    planes = spans.read(build_path)
    build = {"ops": spans.build_phases(planes, tracereduce.OPS_LINE),
             "modules": spans.build_phases(planes, spans.MODULES_LINE),
             "op_events": sum(len(iv) for iv in spans.device_intervals(planes)),
             "reduce_s": time.perf_counter() - t, **timings}
    del planes
    planes = spans.read(window_path)
    w = spans.window(planes)
    out = {"line": line, "build": build,
           "engine_host_ms": spans.engine_host_ms(planes),
           "idle_taco_share": w.taco_share(),
           "idle_gaps": [[n, ns * 1e-9] for n, ns in w.gaps[:10]]}
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(build_path, os.path.join(keep, "build.xplane.pb"))
        shutil.copy(window_path, os.path.join(keep, "window.xplane.pb"))
    shutil.rmtree(build_dir, ignore_errors=True)
    shutil.rmtree(window_dir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    import run as bench_run
    from tacobench import spec
    from tacobench.cell import NoChip

    bench_run.use_compile_cache()
    try:
        out = run(spec.cell(args.workload), args.seed, args.seconds, args.keep)
    except NoChip as e:
        print(f"bench/trace_spans.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
