"""Shared by the benchmark's tests: import paths and a cell cut to a size
a CPU test run holds (the harness and the served path are the real ones;
only the data, the index and the traffic are small)."""
import copy
import dataclasses
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from tacobench import spec  # noqa: E402

TINY_DATA = {"n": 3000, "d": 48, "n_queries": 96, "n_probes": 8}


def tiny_cell(name: str = "deep10m.bulk"):
    c = spec.cell(name)
    config = copy.deepcopy(c.config)
    config["data"].update(TINY_DATA)
    config["taco"]["n_clusters"] = 64
    config["engine"]["max_batch"] = 16
    traffic = dict(c.traffic, outstanding=48, warm_buckets=[16], warm_s=0.3)
    return dataclasses.replace(c, config=config, traffic=traffic)
