#!/usr/bin/env python3
"""Record the chip traces with the program's spans that
``bench/tests/test_spans.py`` reads.

    python3 bench/tests/data/record_spans.py OUT_DIR

On a TPU: one run of ``bench/trace_spans.py`` on the bulk cell cut down as
``record_trace.py`` cuts it (500,000 x 96, 64 requests in flight), with a
window of a few batches: an untraced build first, so that the traced build
compiles nothing, then the build traced in a session of its own, then the
traced window. Both raw
traces land in ``OUT_DIR`` (``build.xplane.pb``, ``window.xplane.pb``) and
the tool's JSON line is printed. ``gzip -9`` of them are
``v5e_build_500k.xplane.pb.gz`` and ``v5e_spans_500k.xplane.pb.gz`` here.
"""
import copy
import dataclasses
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def main(out_dir: str) -> int:
    import run as bench_run
    import trace_spans
    from repro.ann import AnnIndex
    from tacobench import datagen, spec
    from tacobench.cell import taco_cfg

    bench_run.use_compile_cache()
    c = spec.cell("deep10m.bulk")
    config = copy.deepcopy(c.config)
    config["data"].update(n=500_000, n_queries=512, n_probes=32)
    traffic = dict(c.traffic, outstanding=64, warm_s=0.05)
    c = dataclasses.replace(c, config=config, traffic=traffic)
    corpus, _queries = datagen.make_data(7, config["data"])
    AnnIndex.build(corpus, taco_cfg(config))
    del corpus, _queries
    out = trace_spans.run(c, 7, 0.6, keep=out_dir)
    print(json.dumps(out))
    # not `correct`: the recall floor is the 10M cell's, not this cut's
    return 0 if out["line"]["checks"]["bad_answers"]["value"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
