"""The roofline work counts against hand arithmetic, and the peak table."""
import types

import pytest

import bench_tiny  # noqa: F401  (import paths)
from tacobench import counts, peaks, spec

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_code_bytes():
    assert [counts.code_bytes(s) for s in (2, 32, 256, 257, 65536)] == [1, 1, 1, 2, 2]


@pytest.mark.parametrize("n, d, schist, rerank", [
    # deep10m: 12 one-byte codes a row; tables 2*6*64*32*4 + 6*64*4
    (10_000_000, 96, 120_000_000 + 98_304 + 1_536,
     3_840_000_000 + 40_000_000 + 120_000_000 + 24_576),
    # gist1m
    (1_000_000, 960, 12_000_000 + 98_304 + 1_536,
     3_840_000_000 + 4_000_000 + 12_000_000 + 245_760),
])
def test_counts_at_the_configs_shapes(n, d, schist, rerank):
    assert counts.schist_bytes(64, n, 6, 32) == schist
    assert counts.rerank_bytes(64, n, d, 6, 32) == rerank
    assert counts.rerank_flops(64, n, d) == 2 * 64 * n * d == 122_880_000_000
    # both passes are bound by bytes on a v5e, not by flops
    least = counts.least_seconds(counts.rerank_flops(64, n, d), rerank, V5E)
    assert least == pytest.approx(rerank / 819e9)
    assert least > 122_880_000_000 / 197e12


def test_config_files_match_the_counted_shapes():
    data = spec.cell("deep10m.bulk").config["data"]
    assert (data["n"], data["d"]) == (10_000_000, 96)


def test_a_cell_gets_its_own_configurations_metrics():
    cell = spec.cell("deep10m.bulk")
    assert sorted(m["name"] for m in cell.end_to_end) == [
        "build_s.deep10m", "qps.deep10m", "recall_at_10.deep10m", "setup_s"]
    assert sorted(m["name"] for m in cell.per_layer) == [
        "device_idle.deep10m", "hbm_peak_gb.deep10m",
        "masked_rerank_roofline.deep10m", "schist_roofline.deep10m"]
    bench = {"workloads": [{"name": "x.bulk", "config": "deep10m",
                            "traffic": "deep10m.bulk", "chips": 1}],
             "configs": spec.load_benchmark()["configs"],
             "end_to_end": [{"name": "qps.x", "workloads": ["x.bulk"]},
                            {"name": "setup_s"}],
             "per_layer": [{"name": "a", "moves": "qps.x"},
                           {"name": "b", "moves": "qps.y"},
                           {"name": "c", "moves": "qps.x", "workloads": []}]}
    other = spec.cell("x.bulk", bench)
    assert [m["name"] for m in other.end_to_end] == ["qps.x", "setup_s"]
    assert [m["name"] for m in other.per_layer] == ["a"]


def test_peak_table():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("TPU v9 imaginary")


def _run(kernel_s, launches, q=64, n=10_000_000, d=96):
    trace = types.SimpleNamespace(
        kernel_seconds=lambda *names: (kernel_s, launches))
    return types.SimpleNamespace(
        trace=trace, peak=V5E, shape={"n": n, "d": d, "n_sub": 6, "sqrt_k": 32},
        counters={"taco_engine_batches_total": launches,
                  "taco_engine_requests_total{outcome=executed}": q * launches})


def test_roofline_readers_hit_100_at_the_least_time():
    rerank = spec.reader("masked_rerank_roofline")
    schist = spec.reader("schist_roofline")
    least_r = counts.rerank_bytes(64, 10_000_000, 96, 6, 32) / 819e9
    least_s = counts.schist_bytes(64, 10_000_000, 6, 32) / 819e9
    assert rerank(_run(3 * least_r, 3)) == pytest.approx(100.0)
    assert schist(_run(3 * least_s, 3)) == pytest.approx(100.0)
    assert rerank(_run(30 * least_r, 3)) == pytest.approx(10.0)
    # nothing to read: no launch, no trace
    assert rerank(_run(0.0, 0)) is None
    assert schist(types.SimpleNamespace(trace=None, peak=V5E)) is None


def test_every_declared_metric_has_a_reader():
    bench = spec.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.reader(m["name"])), m["name"]
    # a per-configuration name is read by its base's reader
    assert spec.reader("qps.deep10m").__module__ != spec.reader("build_s").__module__
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric.deep10m")
