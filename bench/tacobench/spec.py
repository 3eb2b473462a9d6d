"""Find a cell's files by the names ``BENCHMARK.json`` gives.

* ``bench/configs/<config>.json``: one deployment (data shapes, TaCo
  parameters, engine settings, limits of the comparison);
* ``bench/traffic/<traffic>.json``: one traffic mix (see
  :mod:`tacobench.traffic`);
* ``bench/metrics/<metric>.py``: one reader per metric, a module with
  ``read(run) -> float | None`` (``None``: nothing to read in this run).
  A metric named ``<base>.<part>`` without a file of its own, such as
  ``qps.deep10m``, is read by ``bench/metrics/<base>.py``: the part names
  the cells it belongs to, so that each configuration has its own bound.

A metric belongs to a cell when its ``workloads`` list names the cell, or,
without that key, when the cell reports the end-to-end metric it ``moves``
(an end-to-end metric without the key belongs to every cell).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _belongs(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def cell(name: str, bench: dict | None = None, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    workloads = {w["name"]: w for w in bench["workloads"]}
    if name not in workloads:
        raise KeyError(f"no workload {name!r}; known: {sorted(workloads)}")
    w = workloads[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _belongs(m, name, set())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _belongs(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)


def reader(metric: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``bench/metrics/<metric>.py``, else of
    ``bench/metrics/<base>.py`` for a metric named ``<base>.<part>``."""
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.exists():
        path = bench_dir / "metrics" / f"{metric.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "tacobench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
