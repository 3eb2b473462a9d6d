#!/usr/bin/env python3
"""TaCo's served k-NN path on one chip: one run of one benchmark cell.

    python3 bench/run.py --workload deep10m.bulk --seed 7 --seconds 10 --trace 0

Run from the root of a checkout. The cell's configuration, traffic mix and
metric readers are found by the names in ``BENCHMARK.json`` (see
``bench/tacobench/spec.py``). One process: it makes the corpus and queries
on the device from ``--seed``, builds the index through ``AnnIndex.build``,
opens the serving engine, warms the buckets the traffic forms, drives the
measured window through ``AnnServingEngine.submit``, then checks every
answer against an exact brute-force reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, ``breakdown`` (traced runs) and ``checks``, each
compared number beside its limit. Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.

JAX's compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` at the root of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _paths() -> None:
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def use_compile_cache() -> str:
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    # cache every program, small ones too, so that set-up is the same in
    # every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    from tacobench import spec
    from tacobench.cell import NoChip, run_cell

    cell = spec.cell(args.workload)
    use_compile_cache()
    try:
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        t_start=T_START)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
