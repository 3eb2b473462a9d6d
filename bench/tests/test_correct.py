"""The comparison that decides ``correct`` fails what it must.

A whole run of a cell, cut to a CPU size (``bench_tiny``), with the chip
check skipped: a sound run is correct; the precision control (the
program's own bfloat16 path) is not; nor is a run whose served path is
broken underneath by each fault a serving cell can have, or by each fault
planted in pass 1 (``tacobench.faults``).

At this size recall@10 depends on the seed far more than at the cells'
sizes (0.476 to 0.560 over three seeds on the CPU), so the tests run one
seed and hold it to a floor of their own, ``TINY_RECALL_MIN``: on that
seed sound runs read 0.560, ``beta_halved`` 0.500 and ``subspace_zeroed``
0.481 (``threshold_raised`` leaves queries with fewer than k candidates,
which ``bad_answers`` counts)."""
import dataclasses
import time

import numpy as np
import pytest

from bench_tiny import tiny_cell
from tacobench import check, faults, spec
from tacobench.cell import run_cell

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
SEED = 2**32 + 41
TINY_RECALL_MIN = 0.53


def _run(cell_name="deep10m.bulk", **kw):
    cell = tiny_cell(cell_name)
    limits = dict(cell.config["limits"], recall_at_10_min=TINY_RECALL_MIN)
    cell = dataclasses.replace(cell, config=dict(cell.config, limits=limits))
    kw.setdefault("log", lambda _m: None)
    return run_cell(cell, SEED, 1.0, False, t_start=time.perf_counter(),
                    require_tpu=False, grace_s=1.0, **kw)


def test_checks_hold_values_to_their_side_of_the_limit():
    assert check.passes({"value": 0, "max": 0})
    assert not check.passes({"value": 1, "max": 0})
    assert check.passes({"value": 0.9, "min": 0.9})
    assert not check.passes({"value": 0.8, "min": 0.9})
    assert not check.passes({"value": None, "max": 0.5})


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(cell_name):
    line = _run(cell_name)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["checks"]["probe_mismatch"]["value"] == 0.0
    recall = line["checks"]["recall_at_10"]
    assert TINY_RECALL_MIN <= recall["value"] <= 1.0
    assert recall["min"] == TINY_RECALL_MIN
    name = "recall_at_10." + cell_name.split(".")[0]
    assert line["metrics"][name]["value"] == recall["value"]


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
            lambda self, key, batch: execute(self, key, batch[:(len(batch) + 1) // 2]))
    else:
        monkeypatch.setattr(SingleDeviceSearcher, "run_padded", broken)
    line = _run(cell_name)
    assert line["correct"] is False
    if fault == "answer_reordered":  # self-consistent answers, wrong order
        mismatch = line["checks"]["probe_mismatch"]
        assert mismatch["value"] > mismatch["max"]
    else:
        assert line["failed"] > 0
