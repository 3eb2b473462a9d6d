#!/usr/bin/env python3
"""Record the small chip trace that ``bench/tests/test_reduce.py`` reads.

    python3 bench/tests/data/record_trace.py OUT_DIR

On a TPU: one traced run of a cut-down bulk cell (500,000 x 96, a window of
about two batches), through the same harness as every cell. The raw
``.xplane.pb`` is copied to ``OUT_DIR``, and the run's result line is
printed. ``gzip -9`` of the trace is ``v5e_bulk_500k.xplane.pb.gz`` here,
which ``test_reduce.py`` checks against numbers worked out by hand.
"""
import copy
import dataclasses
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def main(out_dir: str) -> int:
    import run as bench_run
    from tacobench import spec
    from tacobench.cell import run_cell

    bench_run.use_compile_cache()
    c = spec.cell("deep10m.bulk")
    config = copy.deepcopy(c.config)
    config["data"].update(n=500_000, n_queries=512, n_probes=32)
    traffic = dict(c.traffic, outstanding=64, warm_s=0.05)
    c = dataclasses.replace(c, config=config, traffic=traffic)
    line = run_cell(c, 7, 0.1, True, t_start=time.perf_counter(),
                    keep_trace=out_dir)
    print(json.dumps(line))
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
