"""The generators and the reference, on the CPU at tiny sizes; and the
harness's refusal to run without a chip."""
import gc
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import BENCH, ROOT, TINY_DATA
from tacobench import datagen, reference, traffic as tr

DATA = dict(TINY_DATA, probe_group=16, probe_radius=0.001, probe_spacing=3e-5)


def test_data_is_deterministic_per_seed_and_has_the_configured_shapes():
    big = 2**33 + 1
    c1, q1 = datagen.make_data(big, DATA)
    c2, q2 = datagen.make_data(big, DATA)
    c3, _ = datagen.make_data(1, DATA)  # same low 32 bits as ``big``
    assert c1.shape == (DATA["n"], DATA["d"]) and q1.shape == (DATA["n_queries"], DATA["d"])
    assert c1.dtype == jnp.float32 and q1.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    assert not np.array_equal(np.asarray(c1), np.asarray(c3))


@pytest.mark.parametrize("rows_per_chunk", [None, 500])
def test_probes_are_planted_at_their_distances(rows_per_chunk):
    corpus, queries = (np.asarray(a, np.float64) for a in datagen.make_data(
        3, DATA, rows_per_chunk=rows_per_chunk))
    plain, _ = datagen.make_data(3, dict(DATA, n_probes=0),
                                 rows_per_chunk=rows_per_chunk)
    p, g = DATA["n_probes"], DATA["probe_group"]
    rows = datagen.probe_rows(DATA["n"], p, g)
    assert len(np.unique(rows)) == p * g and rows.max() < DATA["n"]
    # 3000 // 128 = 23 rows apart; probe 1's rows are 1*23, (8+1)*23, ...
    assert list(rows[g:g + 3]) == [23, 9 * 23, 17 * 23]
    # each probe's rows reach from the first to the last 1/G of the corpus
    by_probe = rows.reshape(p, g)
    assert np.all(by_probe[:, 0] < DATA["n"] / g)
    assert np.all(by_probe[:, -1] >= DATA["n"] * (g - 1) / g - p * 23)
    # the planted rows replace those rows and no others
    changed = np.flatnonzero(np.any(corpus != np.asarray(plain, np.float64), axis=1))
    np.testing.assert_array_equal(changed, np.sort(rows))
    planted = corpus[rows].reshape(p, g, -1)
    q = queries[:p]
    got = np.sum((planted - q[:, None, :]) ** 2, axis=2)
    qn = np.sum(q * q, axis=1)[:, None]
    want = qn * (DATA["probe_radius"] + DATA["probe_spacing"] * np.arange(g))
    np.testing.assert_allclose(got, want, rtol=1e-3)


def test_chunking_divides_the_rows():
    for n in (10_000_000, 1_000_000, 3000, 97):
        rows = datagen.chunk_rows(n, 96)
        assert n % rows == 0 and rows * 96 <= 1 << 27
        assert n % reference._chunk(n) == 0


def _numpy_knn(x, q, k):
    x, q = np.asarray(x, np.float64), np.asarray(q, np.float64)
    d = np.sum((q[:, None, :] - x[None, :, :]) ** 2, axis=2)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1)


@pytest.mark.parametrize("n_queries", [5, 37])
def test_reference_equals_numpy_exact_knn(n_queries):
    corpus, queries = datagen.make_data(5, dict(DATA, n_queries=n_queries, n_probes=4))
    want_ids, want_d = _numpy_knn(corpus, queries, 12)
    ids, dists = reference.exact_knn(corpus, np.asarray(queries), 12, query_block=16)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(dists, want_d, rtol=1e-5, atol=1e-6)


def test_draws():
    rng = np.random.default_rng(0)
    t = tr.load({"loop": "closed", "outstanding": 1})
    assert t.k == 10 and t.warm_buckets == (64,)
    q = tr.draw_queries(rng, 50, 4000, t)
    assert q.min() >= 0 and q.max() < 50 and len(np.unique(q)) == 50
    for bad in ({"loop": "sometimes", "outstanding": 1}, {"loop": "closed"},
                {"loop": "closed", "outstanding": 1, "draw": "zipf"},
                {"loop": "closed", "outstanding": 1, "k": 0}):
        with pytest.raises(ValueError):
            tr.load(bad)


def test_stalls_count_gaps_and_collections():
    s = tr.Stalls()
    s.turn()
    time.sleep(0.05)
    s.turn()
    gc.collect()
    report = s.close()
    assert 0.05 <= report["longest_turn_s"] < 1.0
    assert 0.05 <= report["longest_turn_at_s"] < 1.0
    assert report["gc_s"] > 0 and report["gc_longest_s"] <= report["gc_s"]
    assert s._on_gc not in gc.callbacks
    # the heartbeat process woke up and has ended
    assert 0.0 < report["heartbeat_longest_s"] < 1.0
    assert -1.0 < report["heartbeat_longest_at_s"] < 10.0
    assert s._beat.poll() == 0


class _Future:
    def __init__(self):
        self._cb, self._res, self._done = [], None, False
        self._lock = threading.Lock()

    def add_done_callback(self, fn):
        with self._lock:
            if not self._done:
                self._cb.append(fn)
                return
        fn(self)

    def result(self, timeout=None):
        return self._res

    def resolve(self, res):
        with self._lock:
            self._res, self._done = res, True
            callbacks, self._cb = self._cb, []
        for fn in callbacks:
            fn(self)


class FakeEngine:
    """Serves queued requests in batches of up to ``batch`` every
    ``batch_s`` seconds on its own thread, and records the queue."""

    def __init__(self, batch=8, batch_s=0.01):
        self.batch, self.batch_s = batch, batch_s
        self.queue, self.lock = [], threading.Lock()
        self.max_in_flight = self.in_flight = 0
        self.stop = threading.Event()
        self.thread = None

    def submit(self, request):
        fut = _Future()
        with self.lock:
            self.queue.append((request, fut))
            self.in_flight += 1
            self.max_in_flight = max(self.max_in_flight, self.in_flight)
        return fut

    def start(self):
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        while not self.stop.is_set():
            time.sleep(self.batch_s)
            with self.lock:
                batch, self.queue = self.queue[:self.batch], self.queue[self.batch:]
                self.in_flight -= len(batch)
            for req, fut in batch:
                fut.resolve(("answer", req))

    def close(self):
        self.stop.set()
        self.thread.join(5)
        assert not self.thread.is_alive()


def test_closed_loop_keeps_its_requests_in_flight():
    engine = FakeEngine(batch=8, batch_s=0.01)
    t = tr.load({"loop": "closed", "outstanding": 24, "warm_s": 0.1})
    seq = np.arange(1000)
    w = tr.run_closed(engine, lambda r: r.pool_index, seq, 10, t, 0.5,
                      grace_s=5.0)
    engine.close()
    assert engine.max_in_flight == 24
    assert all(r.result is not None for r in w.requests)
    done = np.array([r.done_at for r in w.requests])
    inside = np.sum((done > w.t0) & (done <= w.t1))
    assert inside % 8 == 0 and inside > 0  # whole batches only
    assert 0.5 <= w.t1 - w.t0 < 0.5 + 0.1


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "deep10m.bulk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert "TPU" in p.stderr and not p.stdout.strip()


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "deep10m.bulk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
