"""The trace reduction against numbers worked out by hand: on planes made
up here, and on a small trace recorded on the chip."""
import collections
import gzip
import types
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (import paths)
from tacobench import spec, tracereduce

Ev = collections.namedtuple("Ev", "name start_ns duration_ns stats")


def _ev(name, start, end, stats=()):
    return Ev(name, start, end - start, list(stats))


PLANES = [
    ("/host:CPU", [("python", [
        _ev("bench.window", 1_000, 11_000),
        _ev("bench.submit", 2_000, 2_500),
        _ev("bench.wait", 6_000, 9_000),
        _ev("not.bench", 4_000, 5_000),
    ])]),
    ("/device:TPU:0", [
        ("XLA Ops", [
            _ev("copy.1", 0, 1_200),  # clipped to [1000, 1200]
            _ev("fusion.2", 1_500, 3_000),
            _ev("fusion.3", 2_500, 4_000),  # overlaps fusion.2
            _ev("%schist_pallas.4 = s32[64,128] custom-call(%fusion.3)", 5_000, 6_000,
                [("device_duration_ps", 1_000_000)]),
            _ev("fusion.2", 12_000, 13_000),  # after the window
        ]),
        ("Steps", [_ev("step 0", 0, 20_000)]),
    ]),
]


def test_reduction_by_hand():
    t = tracereduce.reduce_planes(PLANES)
    assert t.window_s == pytest.approx(10_000e-9)
    # union: [1000,1200] + [1500,4000] + [5000,6000] = 200 + 2500 + 1000 ns
    assert t.busy_s == pytest.approx(3_700e-9)
    assert t.op_seconds == pytest.approx(
        {"copy.1": 200e-9, "fusion.2": 1_500e-9, "fusion.3": 1_500e-9,
         "schist_pallas.4": 1_000e-9})
    # copy.1 ran 1200 ns, 200 of them inside the window: 1/6 of a launch
    assert t.op_counts == pytest.approx(
        {"copy.1": 1 / 6, "fusion.2": 1.0, "fusion.3": 1.0, "schist_pallas.4": 1.0})
    assert t.kernel_seconds("schist_pallas") == (pytest.approx(1_000e-9), 1.0)
    assert t.kernel_seconds("masked_rerank_pallas") == (0, 0)
    # idle gaps: [1200,1500] and [4000,5000] overlap no bench span (the
    # not.bench span does not count), [6000,11000] overlaps bench.wait
    assert t.gaps == [("bench.wait", pytest.approx(5_000e-9)),
                      ("unattributed", pytest.approx(1_000e-9)),
                      ("unattributed", pytest.approx(300e-9))]
    b = t.breakdown(top=2)
    assert [n for n, _s in b["device_ops"]] == ["fusion.2", "fusion.3"]
    assert len(b["idle_gaps"]) == 2


def test_busy_time_is_averaged_over_device_planes():
    second = ("/device:TPU:1", [("XLA Ops", [_ev("fusion.9", 1_000, 2_000)])])
    t = tracereduce.reduce_planes(PLANES + [second])
    assert t.busy_s == pytest.approx((3_700e-9 + 1_000e-9) / 2)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tracereduce.reduce_planes(PLANES[1:])


# A trace recorded on one TPU v5e by ``data/record_trace.py``: a bulk cell
# cut to 500,000 x 96 with 64 requests in flight, and a window of about one
# and a half batches. The numbers below are read off its events by hand
# (start and duration in ns, on the trace's clock).
FIXTURE = Path(__file__).parent / "data" / "v5e_bulk_500k.xplane.pb.gz"
WINDOW_START, WINDOW_NS = 94_943_298, 202_927_535  # the bench.window span
# schist ran twice: from 46,222,499 for 51,164,945 ns (its last 2,444,146 ns
# inside the window) and from 196,459,701 for 51,164,943 ns
SCHIST_NS = (46_222_499 + 51_164_945 - WINDOW_START) + 51_164_943
SCHIST_LAUNCHES = 1 + (46_222_499 + 51_164_945 - WINDOW_START) / 51_164_945
RERANK_NS = 90_528_783  # one launch, from 98,885,057, inside the window
# the union of the 1,083 op intervals inside the window (a plain sweep over
# them sorted by start gives the same)
BUSY_NS = 147_663_670
# the last op inside the window ends at 249,122,502; the host was then in
# bench.wait (from 247,053,525 to the window's end)
LONGEST_GAP_NS = WINDOW_START + WINDOW_NS - 249_122_502


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "v5e.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return tracereduce.reduce_file(str(path))


def test_recorded_trace_by_hand(recorded):
    t = recorded
    assert t.window_s == pytest.approx(WINDOW_NS * 1e-9, abs=1e-12)
    assert t.busy_s == pytest.approx(BUSY_NS * 1e-9, abs=1e-12)
    secs, launches = t.kernel_seconds("schist_pallas")
    assert secs == pytest.approx(SCHIST_NS * 1e-9, abs=1e-12)
    assert launches == pytest.approx(SCHIST_LAUNCHES)
    secs, launches = t.kernel_seconds("masked_rerank_pallas")
    assert secs == pytest.approx(RERANK_NS * 1e-9, abs=1e-12)
    assert launches == pytest.approx(1.0)
    assert t.gaps[0] == ("bench.wait", pytest.approx(LONGEST_GAP_NS * 1e-9, abs=1e-12))
    b = t.breakdown()
    assert [n for n, _s in b["device_ops"][:2]] == ["masked_rerank_pallas.1",
                                                    "schist_pallas.1"]
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10


def test_recorded_trace_rooflines(recorded):
    """The readers on the recorded trace: 64 queries a batch, 500,000 x 96."""
    run = types.SimpleNamespace(
        trace=recorded, peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        shape={"n": 500_000, "d": 96, "n_sub": 6, "sqrt_k": 32},
        counters={"taco_engine_batches_total": 2,
                  "taco_engine_requests_total{outcome=executed}": 128})
    # pass 2 reads 500,000 x (96 x 4 + 4 + 12) bytes and 64 x 96 x 4 of
    # queries: 200,024,576 bytes, 244.23 us at 819 GB/s, in 90.53 ms
    least = 200_024_576 / 819e9
    assert spec.reader("masked_rerank_roofline")(run) == pytest.approx(
        100 * least / (RERANK_NS * 1e-9))
    # pass 1 reads 500,000 x 12 code bytes and 64 x (2 x 6 x 32 + 6) x 4
    # bytes of tables and thresholds: 6,099,840 bytes
    least = 6_099_840 / 819e9
    assert spec.reader("schist_roofline")(run) == pytest.approx(
        100 * SCHIST_LAUNCHES * least / (SCHIST_NS * 1e-9))
