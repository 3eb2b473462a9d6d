"""The roofline work counts against hand arithmetic, and the peak table."""
import json
import types

import pytest

import bench_tiny
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


def assert_own_metrics(bench, cell_name):
    """The rule by which a cell gets its metrics: the end-to-end metrics
    whose ``workloads`` name it or that have none (``setup_s`` among
    them, and one more), none named for another configuration, and at
    least one per-layer metric, each moving one of its own end-to-end
    metrics."""
    config = {w["name"]: w["config"] for w in bench["workloads"]}[cell_name]
    others = {c["name"] for c in bench["configs"]} - {config}
    cell = spec.cell(cell_name, bench)
    e2e = [m["name"] for m in cell.end_to_end]
    assert e2e == [m["name"] for m in bench["end_to_end"]
                   if cell_name in m.get("workloads", [cell_name])]
    assert "setup_s" in e2e and len(e2e) > 1
    for m in cell.end_to_end + cell.per_layer:
        assert m["name"].rsplit(".", 1)[-1] not in others, m["name"]
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, m["name"]
    return cell


@pytest.mark.parametrize(
    "cell_name", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_every_cell_gets_its_own_configurations_metrics(cell_name):
    assert_own_metrics(spec.load_benchmark(), cell_name)


def test_a_cell_gets_its_own_configurations_metrics():
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
    # a second traffic mix on the same configuration, with a metric of
    # the configuration's own that only it reports, leaves x.bulk as it was
    bench["workloads"].append({"name": "x.poisson", "config": "deep10m",
                               "traffic": "deep10m.bulk", "chips": 1})
    bench["end_to_end"].append(
        {"name": "p99_ms.deep10m", "workloads": ["x.poisson"]})
    bench["per_layer"].append({"name": "d.deep10m", "moves": "p99_ms.deep10m"})
    poisson = assert_own_metrics(bench, "x.poisson")
    assert [m["name"] for m in poisson.end_to_end] == ["setup_s", "p99_ms.deep10m"]
    assert [m["name"] for m in poisson.per_layer] == ["d.deep10m"]
    bulk = assert_own_metrics(bench, "x.bulk")
    assert [m["name"] for m in bulk.end_to_end] == ["qps.x", "setup_s"]
    assert [m["name"] for m in bulk.per_layer] == ["a"]


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


def test_rerank_merge_share_reads_the_program_counters():
    read = spec.reader("rerank_merge_share.deep10m")

    def run(**counters):
        return types.SimpleNamespace(counters=counters)

    assert read(run(taco_rerank_blocks_merged_total=3750.0,
                    taco_rerank_blocks_total=156_256.0)) == pytest.approx(
        100 * 3750 / 156_256)
    assert read(run(taco_rerank_blocks_merged_total=0.0,
                    taco_rerank_blocks_total=64.0)) == 0.0
    # nothing to read: pass 2 counted no step (the jnp path), or no counter
    assert read(run(taco_rerank_blocks_merged_total=0.0,
                    taco_rerank_blocks_total=0.0)) is None
    assert read(run()) is None


@pytest.mark.parametrize(
    "config", [c["name"] for c in spec.load_benchmark()["configs"]])
def test_every_configuration_has_a_tiny_cut(config):
    cut = bench_tiny.tiny(config)
    assert set(cut) == {"data", "taco", "engine", "recall_at_10_min", "why"}
    assert {"n", "d", "n_queries", "n_probes"} <= set(cut["data"])
    assert "max_batch" in cut["engine"]
    assert 0 < cut["recall_at_10_min"] <= 1
    assert cut["why"]
    # eigensystem_allocation needs the subspaces to fit the tiny width
    file = next(c["file"] for c in spec.load_benchmark()["configs"]
                if c["name"] == config)
    taco = dict(json.loads((bench_tiny.ROOT / file).read_text())["taco"],
                **cut["taco"])
    assert taco["n_subspaces"] * taco["subspace_dim"] <= cut["data"]["d"]


def test_every_declared_metric_has_a_reader():
    bench = spec.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.reader(m["name"])), m["name"]
    # a per-configuration name is read by its base's reader
    assert spec.reader("qps.deep10m").__module__ != spec.reader("build_s").__module__
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric.deep10m")
