"""Shared by the benchmark's tests: import paths and a cell cut to a size
a CPU test run holds (the harness and the served path are the real ones;
only the data, the index and the traffic are small).

Each configuration brings its own cut in ``bench/tests/tiny/<config>.json``:
overrides of its ``data``, ``taco`` and ``engine`` blocks, the recall@10
floor that a sound run at that cut meets on ``SEED`` and the faults of
``test_correct`` do not, and ``why``, the readings that place it. The
traffic follows the tiny ``max_batch``."""
import copy
import dataclasses
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from tacobench import spec  # noqa: E402

#: the one seed of every tiny run; a configuration places its floor at it
SEED = 2**32 + 41


def tiny(config: str, root: Path = ROOT) -> dict:
    """The configuration's tiny file."""
    return json.loads(
        (root / "bench" / "tests" / "tiny" / f"{config}.json").read_text())


TINY_DATA = tiny("deep10m")["data"]


@dataclasses.dataclass
class TinyCell(spec.Cell):
    """A cell at its tiny cut, with the configuration ``BENCHMARK.json``
    gives it."""
    config_name: str = ""


def tiny_cell(name: str = "deep10m.bulk", root: Path = ROOT) -> TinyCell:
    bench = spec.load_benchmark(root)
    c = spec.cell(name, bench, root)
    config_name = next(
        w["config"] for w in bench["workloads"] if w["name"] == name)
    cut = tiny(config_name, root)
    config = copy.deepcopy(c.config)
    for block in ("data", "taco", "engine"):
        config[block].update(cut[block])
    config["limits"]["recall_at_10_min"] = cut["recall_at_10_min"]
    batch = config["engine"]["max_batch"]
    traffic = dict(c.traffic, outstanding=3 * batch, warm_buckets=[batch],
                   warm_s=0.3)
    fields = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    return TinyCell(**dict(fields, config=config, traffic=traffic),
                    config_name=config_name)
