"""The comparison that decides ``correct`` fails what it must.

A whole run of a cell, cut to a CPU size (``bench_tiny``), with the chip
check skipped: a sound run is correct; the precision control (the
program's own bfloat16 path) is not; nor is a run whose served path is
broken underneath by each fault a serving cell can have, or by each fault
planted in pass 1 (``tacobench.faults``).

At this size recall@10 depends on the seed far more than at the cells'
sizes, so the tests run one seed, ``bench_tiny.SEED``, and hold each cell
to the floor of its configuration's tiny file
(``bench/tests/tiny/<config>.json``), placed between the readings of sound
runs and of the pass-1 faults on that seed (its ``why``)."""
import json
import shutil
import time

import numpy as np
import pytest

from bench_tiny import ROOT, SEED, tiny, tiny_cell
from tacobench import check, faults, spec
from tacobench.cell import run_cell

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def _run(cell="deep10m.bulk", **kw):
    """A whole run of a tiny cell, given as one or by its name."""
    if isinstance(cell, str):
        cell = tiny_cell(cell)
    kw.setdefault("log", lambda _m: None)
    return run_cell(cell, SEED, 1.0, False, t_start=time.perf_counter(),
                    require_tpu=False, grace_s=1.0, **kw)


def test_checks_hold_values_to_their_side_of_the_limit():
    assert check.passes({"value": 0, "max": 0})
    assert not check.passes({"value": 1, "max": 0})
    assert check.passes({"value": 0.9, "min": 0.9})
    assert not check.passes({"value": 0.8, "min": 0.9})
    assert not check.passes({"value": None, "max": 0.5})


def assert_sound(cell, root=ROOT):
    """A sound tiny run of the cell is correct, and its recall is judged
    against its own configuration's floor."""
    floor = tiny(cell.config_name, root)["recall_at_10_min"]
    line = _run(cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["checks"]["probe_mismatch"]["value"] == 0.0
    recall = line["checks"]["recall_at_10"]
    assert floor <= recall["value"] <= 1.0
    assert recall["min"] == floor
    assert (line["metrics"]["recall_at_10." + cell.config_name]["value"]
            == recall["value"])
    return line


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(cell_name):
    assert_sound(tiny_cell(cell_name))


def test_a_configuration_joins_through_its_own_files(tmp_path):
    """A second configuration made of new files only (its configuration,
    traffic and tiny cut) and appended ``BENCHMARK.json`` entries is found
    by ``spec.cell`` and ``tiny_cell``, and its sound tiny run is judged
    against its own floor. Its generator settings (as many mixture centres
    as rows, in a low-rank span; GIST1M-like) read 0.411 at the tiny cut on
    ``SEED``, under deep10m's floor; the pass-1 faults read 0.331
    (``beta_halved``) and 0.183 (``subspace_zeroed``), so its floor is
    0.37 (CPU)."""
    for d in ("configs", "traffic", "tests/tiny"):
        shutil.copytree(ROOT / "bench" / d, tmp_path / "bench" / d)
    config = json.loads((ROOT / "bench/configs/deep10m.json").read_text())
    config["data"].update(n_mix=config["data"]["n"], rank_frac=0.02,
                          cluster_std=0.015)
    files = {
        "configs/wide.json": dict(config, name="wide"),
        "traffic/wide.bulk.json": json.loads(
            (ROOT / "bench/traffic/deep10m.bulk.json").read_text()),
        "tests/tiny/wide.json": dict(
            tiny("deep10m"), recall_at_10_min=0.37,
            data=dict(tiny("deep10m")["data"], n_mix=3000),
            why="sound 0.411, beta_halved 0.331, subspace_zeroed 0.183"),
    }
    for name, body in files.items():
        path = tmp_path / "bench" / name
        assert not path.exists()
        path.write_text(json.dumps(body))
    bench = spec.load_benchmark()
    bench["configs"].append({"name": "wide", "file": "bench/configs/wide.json"})
    bench["workloads"].append({"name": "wide.bulk", "config": "wide",
                               "traffic": "wide.bulk", "chips": 1})
    bench["end_to_end"] += [
        {"name": f"{base}.wide", "unit": "u", "workloads": ["wide.bulk"]}
        for base in ("qps", "recall_at_10")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = tiny_cell("wide.bulk", tmp_path)
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "qps.wide", "recall_at_10.wide"]
    assert cell.config_name == "wide"
    assert cell.config["data"]["rank_frac"] == 0.02
    assert cell.config["limits"]["recall_at_10_min"] == 0.37
    line = assert_sound(cell, tmp_path)
    assert line["checks"]["recall_at_10"]["value"] < tiny("deep10m")["recall_at_10_min"]
    # the cells already there are cut and judged as before
    assert tiny_cell("deep10m.bulk", tmp_path).config == tiny_cell("deep10m.bulk").config


def test_checks_come_last_on_stderr_and_in_the_line():
    lines = []
    line = _run(log=lines.append)
    checks = line["checks"]
    assert list(line)[-1] == "checks"
    assert list(checks) == ["bad_answers", "probe_mismatch", "recall_at_10"]
    assert lines[-3:] == [
        f"check {name}: {c['value']} {'max' if 'max' in c else 'min'} "
        f"{c.get('max', c.get('min'))}" for name, c in checks.items()]


@pytest.mark.parametrize("cell_name", CELLS)
def test_precision_control_is_not_correct(cell_name):
    line = _run(cell_name, precision="bf16")
    assert line["correct"] is False
    mismatch = line["checks"]["probe_mismatch"]
    assert mismatch["value"] > mismatch["max"]


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_pass1_fault_is_not_correct(fault, cell_name):
    with faults.planted(fault):
        line = _run(cell_name)
    assert line["correct"] is False
    assert line["checks"]["probe_mismatch"]["value"] == 0.0  # probes blind
    if fault != "threshold_raised":
        assert not check.passes(line["checks"]["recall_at_10"])


def test_faults_leave_with_their_context():
    from repro.core import selection, taco

    inputs = taco._collision_inputs
    for fault in faults.FAULTS:
        with faults.planted(fault):
            assert (taco.query_aware_threshold is not selection.query_aware_threshold
                    or taco._collision_inputs is not inputs)
        assert taco.query_aware_threshold is selection.query_aware_threshold
        assert taco._collision_inputs is inputs
    with pytest.raises(ValueError), faults.planted("no_such_fault"):
        pass


def _alter_first_id(res):
    res.ids = res.ids.copy()
    res.ids[:, 0] = np.where(res.ids[:, 0] > 0, res.ids[:, 0] - 1, 1)
    return res


def _swap_first_two(res):
    res.ids, res.dists = res.ids.copy(), res.dists.copy()
    res.ids[:, [0, 1]] = res.ids[:, [1, 0]]
    res.dists[:, [0, 1]] = res.dists[:, [1, 0]]
    return res


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("fault", ["answer_altered", "answer_reordered",
                                   "stale_answer", "half_batch_dropped"])
def test_broken_served_path_is_not_correct(monkeypatch, fault, cell_name):
    from repro.ann.searcher import SingleDeviceSearcher
    from repro.serving.ann_engine import AnnServingEngine

    run_padded = SingleDeviceSearcher.run_padded
    first = {}

    def broken(self, bucket, k, cfg, queries):
        res = run_padded(self, bucket, k, cfg, queries)
        if fault == "answer_altered":
            return _alter_first_id(res)
        if fault == "answer_reordered":
            return _swap_first_two(res)
        # the state left unchanged: every batch gets its bucket's first answers
        return first.setdefault(bucket, res)

    if fault == "half_batch_dropped":
        execute = AnnServingEngine._execute
        monkeypatch.setattr(
            AnnServingEngine, "_execute",
            lambda self, key, batch, *rest: execute(
                self, key, batch[:(len(batch) + 1) // 2], *rest))
    else:
        monkeypatch.setattr(SingleDeviceSearcher, "run_padded", broken)
    line = _run(cell_name)
    assert line["correct"] is False
    if fault == "answer_reordered":  # self-consistent answers, wrong order
        mismatch = line["checks"]["probe_mismatch"]
        assert mismatch["value"] > mismatch["max"]
    else:
        assert line["failed"] > 0
