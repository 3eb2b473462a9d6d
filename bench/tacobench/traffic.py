"""The one general traffic generator: every mix is a data file under
``bench/traffic/`` that this module reads.

Keys of a traffic file (all but ``loop`` and ``outstanding`` optional):

* ``loop``: ``"closed"``: ``outstanding`` requests in flight; each answer
  releases the next request.
* ``draw``: ``"uniform"`` over the query pool.
* ``k``: the result count.
* ``warm_buckets``: the batch buckets the cell's traffic forms; set-up
  compiles and runs exactly these.
* ``warm_s``: seconds the loop runs before the measured window opens.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import threading
import time

import numpy as np

LOOPS = ("closed",)
DRAWS = ("uniform",)


@dataclasses.dataclass(frozen=True)
class Traffic:
    loop: str
    outstanding: int
    draw: str = "uniform"
    k: int = 10
    warm_buckets: tuple = (64,)
    warm_s: float = 1.0


def load(spec: dict) -> Traffic:
    """Parse and validate one traffic file's contents."""
    t = Traffic(
        loop=spec["loop"], outstanding=int(spec.get("outstanding", 0)),
        draw=spec.get("draw", "uniform"), k=int(spec.get("k", 10)),
        warm_buckets=tuple(int(b) for b in spec.get("warm_buckets", (64,))),
        warm_s=float(spec.get("warm_s", 1.0)))
    if t.loop not in LOOPS:
        raise ValueError(f"loop {t.loop!r} not in {LOOPS}")
    if t.draw not in DRAWS:
        raise ValueError(f"draw {t.draw!r} not in {DRAWS}")
    if t.outstanding < 1:
        raise ValueError("a closed loop needs outstanding >= 1")
    if t.k < 1:
        raise ValueError("k must be positive")
    return t


def draw_queries(rng: np.random.Generator, pool: int, count: int,
                 traffic: Traffic) -> np.ndarray:
    """``count`` pool indices."""
    return rng.integers(0, pool, size=count)


@dataclasses.dataclass
class Request:
    """One request's life, on ``time.perf_counter``'s clock."""

    pool_index: int
    k: int
    submitted: float = math.nan
    done_at: float = math.nan
    result: object = None
    error: str | None = None


def _annotate(trace: bool):
    if trace:
        import jax

        return jax.profiler.TraceAnnotation
    return lambda _name: contextlib.nullcontext()


class _Completions:
    """Collects answers from the engine's serving thread."""

    def __init__(self):
        self.cond = threading.Condition()
        self.fresh: list = []
        self.in_flight = 0

    def watch(self, req: Request, future) -> None:
        def done(fut):
            t = time.perf_counter()
            try:
                req.result = fut.result(0)
            except Exception as e:  # the answer is an error, not a crash
                req.error = repr(e)
            req.done_at = t
            with self.cond:
                self.in_flight -= 1
                self.fresh.append(req)
                self.cond.notify_all()

        with self.cond:
            self.in_flight += 1
        future.add_done_callback(done)

    def take(self, timeout: float) -> list:
        with self.cond:
            if not self.fresh:
                self.cond.wait(timeout)
            out, self.fresh = self.fresh, []
            return out

    def wait_all(self, deadline: float) -> None:
        with self.cond:
            while self.in_flight and time.perf_counter() < deadline:
                self.cond.wait(min(0.1, max(0.0, deadline - time.perf_counter())))


def _submit(engine, make_request, req: Request, completions: _Completions):
    req.submitted = time.perf_counter()
    try:
        fut = engine.submit(make_request(req))
    except Exception as e:  # refused at the door: counts as a failure
        req.error = repr(e)
        req.done_at = req.submitted
        return
    completions.watch(req, fut)


def _nothing():
    pass


def batch_ends(done_times: np.ndarray, gap_s: float) -> np.ndarray:
    """Completion times after which the next answer came more than
    ``gap_s`` later: the ends of the engine's batches."""
    t = np.sort(done_times)
    if len(t) < 2:
        return t
    return t[:-1][np.diff(t) > gap_s]


#: a second process that wakes every 5 ms until its standard input closes,
#: then prints its longest gap between two wake-ups and when that gap ended
_HEARTBEAT = """
import sys, threading, time
done = threading.Event()
threading.Thread(target=lambda: (sys.stdin.read(), done.set()), daemon=True).start()
last = time.perf_counter()
worst, at = 0.0, last
while not done.is_set():
    time.sleep(0.005)
    now = time.perf_counter()
    if now - last > worst:
        worst, at = now - last, now
    last = now
print(worst, at, flush=True)
"""


class Stalls:
    """What kept the host from the loop inside the window: the longest gap
    between two turns of the loop (each turn waits at most 50 ms for an
    answer) and when it ended, Python's garbage collections, and the
    longest gap of a heartbeat in a second process. A long turn with no
    long heartbeat gap is this process's own; with one at the same time,
    the whole machine stood still. Times are seconds from the window's
    opening (``time.perf_counter`` is the system's monotonic clock)."""

    def __init__(self):
        import subprocess
        import sys

        self.t0 = time.perf_counter()
        self.longest_turn = (0.0, math.nan)
        self.gc_s = 0.0
        self.gc_longest_s = 0.0
        self._gc_t = None
        self._last = None
        gc.callbacks.append(self._on_gc)
        self._beat = subprocess.Popen(
            [sys.executable, "-c", _HEARTBEAT], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)

    def _on_gc(self, phase, _info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            dt = time.perf_counter() - self._gc_t
            self.gc_s += dt
            self.gc_longest_s = max(self.gc_longest_s, dt)

    def turn(self) -> None:
        now = time.perf_counter()
        if self._last is not None and now - self._last > self.longest_turn[0]:
            self.longest_turn = (now - self._last, now - self.t0)
        self._last = now

    def close(self) -> dict:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        out, _ = self._beat.communicate(timeout=30)
        try:
            beat, beat_at = (float(x) for x in out.split())
        except ValueError:
            beat, beat_at = math.nan, math.nan
        return {"longest_turn_s": self.longest_turn[0],
                "longest_turn_at_s": self.longest_turn[1],
                "heartbeat_longest_s": beat,
                "heartbeat_longest_at_s": beat_at - self.t0,
                "gc_s": self.gc_s, "gc_longest_s": self.gc_longest_s}


@dataclasses.dataclass
class Window:
    """What a loop measured: every request it sent, and the window."""

    requests: list
    t0: float
    t1: float
    stalls: dict = dataclasses.field(default_factory=dict)


def run_closed(engine, make_request, seq_pool: np.ndarray, k: int,
               traffic: Traffic, seconds: float, *, trace: bool = False,
               on_open=_nothing, on_close=_nothing, gap_s: float = 0.002,
               grace_s: float = 60.0) -> Window:
    """Closed loop. ``engine`` must not be serving yet: the first
    ``outstanding`` requests queue up before ``engine.start()``, so the
    first batch is as full as every later one. The window opens at the end
    of the first batch after ``warm_s`` and closes at the end of the first
    batch that ends ``seconds`` later, so it holds whole batches only.
    ``on_open`` runs once ``warm_s`` has passed, ``on_close`` when the loop
    stops sending."""
    note = _annotate(trace)
    completions = _Completions()
    sent: list = []

    def send():
        req = Request(int(seq_pool[len(sent) % len(seq_pool)]), k)
        sent.append(req)
        _submit(engine, make_request, req, completions)

    with note("bench.submit"):
        for _ in range(traffic.outstanding):
            send()
    t_open = time.perf_counter() + traffic.warm_s
    engine.start()
    done_times: list = []

    def window_of(times):
        ends = batch_ends(np.asarray(times), gap_s)
        opens = ends[ends >= t_open]
        if not len(opens):
            return None, None
        closes = ends[ends >= opens[0] + seconds]
        return opens[0], (closes[0] if len(closes) else None)

    stalls = None
    try:
        while True:
            with note("bench.wait"):
                fresh = completions.take(0.05)
            done_times.extend(r.done_at for r in fresh)
            now = time.perf_counter()
            if stalls is None and now >= t_open:
                on_open()
                stalls = Stalls()
            if stalls is not None:
                stalls.turn()
            if now >= t_open + seconds:
                # run on until a batch has ended past the window, or give
                # up on batch ends after another window's length
                if (window_of(done_times)[1] is not None
                        or now >= t_open + 2 * seconds):
                    break
            with note("bench.submit"):
                for _ in fresh:
                    send()
    finally:
        report = stalls.close() if stalls is not None else {}
    on_close()
    completions.wait_all(time.perf_counter() + grace_s)
    t0, t1 = window_of([r.done_at for r in sent if np.isfinite(r.done_at)])
    t0 = t_open if t0 is None else t0
    t1 = t0 + seconds if t1 is None else t1
    return Window(sent, float(t0), float(t1), report)
