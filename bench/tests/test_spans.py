"""The program's spans in a trace, against numbers worked out by hand: on
planes made up here, on traces recorded on the chip, and through
``bench/trace_spans.py`` on a CPU run."""
import collections
import gzip
from pathlib import Path

import pytest

import bench_tiny
from tacobench import spans

Ev = collections.namedtuple("Ev", "name start_ns duration_ns stats")


def _ev(name, start, end, stats=()):
    return Ev(name, start, end - start, list(stats))


def _batch(start, form, stage, dispatch, device, fetch, end, n):
    """A batch cycle's spans; each argument is where a stage ends."""
    return [
        _ev("taco.engine.batch", start, end, [("batch", n), ("bucket", 64)]),
        _ev("taco.engine.form", start, form),
        _ev("taco.engine.stage", form, stage),
        _ev("taco.searcher.dispatch", stage, dispatch),
        _ev("taco.searcher.device", dispatch, device),
        _ev("taco.searcher.fetch", device, fetch),
        _ev("taco.engine.resolve", fetch, end),
    ]


WINDOW = [
    ("/host:CPU", [
        ("python", [_ev("bench.window", 1_000, 21_000),
                    _ev("bench.wait", 2_000, 20_000)]),
        ("drain", _batch(1_500, 1_800, 2_000, 2_500, 8_000, 8_300, 9_000, 1)
         + _batch(9_200, 9_400, 9_500, 10_000, 16_000, 16_200, 17_000, 2)
         # a cycle that answered from the result cache: no device stage
         + [_ev("taco.engine.batch", 17_100, 17_200),
            _ev("taco.engine.form", 17_100, 17_200)]
         # a batch that the window's end cuts
         + _batch(20_800, 20_850, 20_900, 21_000, 21_500, 21_600, 22_000, 3)),
    ]),
    ("/device:TPU:0", [("XLA Ops", [
        _ev("fusion.1", 2_600, 7_900),
        _ev("fusion.1", 10_100, 15_900),
        _ev("copy.2", 18_000, 19_000),
        _ev("copy.2", 20_500, 20_600),
    ])]),
]


def test_window_gaps_named_by_the_innermost_program_span():
    w = spans.window(WINDOW)
    assert w.seconds == pytest.approx(20_000e-9)
    assert w.busy_ns == 5_300 + 5_800 + 1_000 + 100
    # [7900, 10100]: resolve of batch 1 (700 ns) outweighs every other
    # stage of depth 1 in it; [1000, 2600] overlaps batch 1 itself for
    # 1100 ns, but its dispatch stage (500 ns) lies deeper; [15900, 18000]
    # holds the cache-only cycle (100 ns) and resolve of batch 2 (800);
    # [19000, 20500] meets no program span and falls back to bench.wait;
    # [20600, 21000] meets the start of the cut batch, dispatch longest
    assert w.gaps == [("taco.engine.resolve", 2_200),
                      ("taco.engine.resolve", 2_100),
                      ("taco.searcher.dispatch", 1_600),
                      ("bench.wait", 1_500),
                      ("taco.searcher.dispatch", 400)]
    assert w.taco_share() == pytest.approx(6_300 / 7_800)


def test_engine_host_ms_is_the_median_batch_less_its_device_time():
    # batch 1: 7500 - 5500 = 2000 ns; batch 2: 7800 - 6000 = 1800 ns; the
    # cache-only cycle and the batch the window cuts do not count
    assert spans.engine_host_ms(WINDOW) == pytest.approx(1_900e-6)
    main_thread_only = [("/host:CPU", [WINDOW[0][1][0]])]
    assert spans.engine_host_ms(main_thread_only) is None


def test_a_window_that_is_not_in_the_trace_is_refused():
    with pytest.raises(ValueError):
        spans.window(WINDOW, "bench.build")


BUILD = [
    ("/host:CPU", [("python", [
        _ev("bench.build", 0, 10_000),
        _ev("taco.build", 100, 9_900, [("n", 500_000), ("d", 96)]),
        _ev("taco.build.transform", 100, 1_100),
        _ev("taco.build.subspace", 1_100, 4_600, [("i", 0)]),
        _ev("taco.build.subspace", 4_600, 8_100, [("i", 1)]),
        _ev("taco.build.norms", 8_100, 9_800),
    ])]),
    ("/device:TPU:0", [
        ("XLA Ops", [_ev("fusion.1", 200, 1_000), _ev("while.2", 1_250, 4_500),
                     _ev("while.2", 4_700, 8_000), _ev("fusion.3", 8_200, 9_700),
                     _ev("copy.4", 9_950, 9_990)]),
        ("XLA Modules", [_ev("jit__cov_eig", 150, 1_050),
                         _ev("jit_kmeans", 1_150, 4_550),
                         _ev("jit_kmeans", 4_650, 8_050),
                         _ev("jit_norms", 8_150, 9_750)]),
    ]),
]


@pytest.mark.parametrize("line, busy_ns", [
    (spans.OPS_LINE, 800 + 3_250 + 3_300 + 1_500),
    (spans.MODULES_LINE, 900 + 3_400 + 3_400 + 1_600),
])
def test_build_phases_by_hand(line, busy_ns):
    b = spans.build_phases(BUILD, line)
    assert b["build_s"] == pytest.approx(9_800e-9)
    assert b["transform_s"] == pytest.approx(1_000e-9)
    assert b["kmeans_s"] == pytest.approx(7_000e-9)
    assert b["norms_s"] == pytest.approx(1_700e-9)
    assert b["covered"] == pytest.approx(9_700 / 9_800)
    assert b["busy_pct"] == pytest.approx(100 * busy_ns / 9_800)


def test_the_build_window():
    w = spans.window(BUILD, spans.BUILD_WINDOW)
    assert w.seconds == pytest.approx(10_000e-9)
    assert w.busy_ns == 800 + 3_250 + 3_300 + 1_500 + 40
    # [1000, 1250] touches the transform for 100 ns and the first subspace
    # for 150; [9700, 9950] the norms for 100 and taco.build, less deep,
    # for 200; [0, 200] the transform; [9990, 10000] lies after taco.build,
    # inside bench.build only
    assert w.gaps[:3] == [("taco.build.subspace", 250),
                          ("taco.build.norms", 250),
                          ("taco.build.transform", 200)]
    assert w.gaps[-1] == ("unattributed", 10)
    assert w.taco_share() == pytest.approx(1 - 10 / (10_000 - w.busy_ns))


def test_spans_nest_by_line():
    got = {(s.name, s.start): s.depth for s in spans.host_spans(BUILD)}
    assert got[("bench.build", 0)] == 0
    assert got[("taco.build", 100)] == 1
    assert got[("taco.build.subspace", 4_600)] == 2


# Traces recorded on one TPU v5e by ``data/record_spans.py``: the bulk cell
# cut to 500,000 x 96 with 64 requests in flight, its build traced after a
# warm-up build, then a window of five whole batches. The numbers are read
# off the raw events by hand (ns, on the trace's clock).
DATA = Path(__file__).parent / "data"
BUILD_NS = 1_092_637_557  # taco.build, from 47,902,783
TRANSFORM_NS = 6_105_369
SUBSPACE_NS = (180_422_642, 180_918_583, 180_549_902, 180_671_624,
               180_992_884, 180_923_713)  # i = 0..5
NORMS_NS = 1_873_340
# the union of the device's intervals inside taco.build: 51,023 op events,
# 146 executable runs
BUILD_BUSY_NS = {spans.OPS_LINE: 1_059_164_512, spans.MODULES_LINE: 1_059_266_394}
WINDOW_NS, WINDOW_BUSY_NS = 867_051_848, 815_207_478  # from 96,942,525
# batches 2-6 lie inside the window (batch 1 starts before it): each
# taco.engine.batch less its taco.searcher.device
BATCH_HOST_NS = (4_810_600, 4_575_809, 4_988_349, 4_889_279, 5_171_540)
# the longest idle gap, 947,392,601 to the window's end: batch 6's device
# stage overlaps it for 746,534 ns, its fetch for 2,153,799, its resolve
# for 765,730; the engine then waits for requests inside no stage
LONGEST_GAP = ("taco.searcher.fetch", 16_601_772)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    out = {}
    for name, fixture in (("build", "v5e_build_500k"), ("window", "v5e_spans_500k")):
        path = tmp_path_factory.mktemp(name) / f"{name}.xplane.pb"
        raw = (DATA / f"{fixture}.xplane.pb.gz").read_bytes()
        path.write_bytes(gzip.decompress(raw))
        out[name] = spans.read(str(path))
    return out


@pytest.mark.parametrize("line", [spans.OPS_LINE, spans.MODULES_LINE])
def test_recorded_build_by_hand(recorded, line):
    b = spans.build_phases(recorded["build"], line)
    assert b["build_s"] == pytest.approx(BUILD_NS * 1e-9, abs=1e-12)
    assert b["transform_s"] == pytest.approx(TRANSFORM_NS * 1e-9, abs=1e-12)
    assert b["kmeans_s"] == pytest.approx(sum(SUBSPACE_NS) * 1e-9, abs=1e-12)
    assert b["norms_s"] == pytest.approx(NORMS_NS * 1e-9, abs=1e-12)
    assert b["covered"] == pytest.approx(
        (TRANSFORM_NS + sum(SUBSPACE_NS) + NORMS_NS) / BUILD_NS)
    assert b["busy_pct"] == pytest.approx(100 * BUILD_BUSY_NS[line] / BUILD_NS)
    subs = [s for s in spans.host_spans(recorded["build"])
            if s.name == "taco.build.subspace"]
    assert [(s.stats["i"], s.ns) for s in subs] == list(enumerate(SUBSPACE_NS))


def test_recorded_window_by_hand(recorded):
    w = spans.window(recorded["window"])
    assert w.end - w.start == WINDOW_NS
    assert w.busy_ns == WINDOW_BUSY_NS
    assert sum(ns for _n, ns in w.gaps) == WINDOW_NS - WINDOW_BUSY_NS
    assert w.gaps[0] == LONGEST_GAP
    assert w.taco_share() == 1.0
    assert spans.engine_host_ms(recorded["window"]) == pytest.approx(
        sorted(BATCH_HOST_NS)[2] * 1e-6)


def test_trace_spans_tool_on_a_cpu_run(tmp_path):
    """The tool's whole path on a CPU-sized cell: the build in a session of
    its own, the traced window, and the program's spans read back. A CPU
    trace has no device plane, so only the host's spans are checked."""
    import trace_spans

    out = trace_spans.run(bench_tiny.tiny_cell(), 3_900_000_401, 0.5,
                          keep=str(tmp_path), require_tpu=False)
    assert out["line"]["checks"]["bad_answers"]["value"] == 0
    assert set(out["line"]["metrics"]) >= {"qps.deep10m", "build_s.deep10m"}
    b = out["build"]["ops"]
    assert b["kmeans_s"] > 0 and b["transform_s"] > 0
    assert b["covered"] >= 0.95
    assert out["build"]["build_s_traced"] >= b["build_s"]
    assert out["engine_host_ms"] > 0
    assert sorted(p.name for p in tmp_path.iterdir()) == ["build.xplane.pb",
                                                          "window.xplane.pb"]
