"""The program's own spans in a profiler trace: the build's phases, the
engine's batch cycle, and idle gaps named by them.

The program opens each ``taco.*`` stage (``repro.obs.trace.Tracer.stage``)
as a ``jax.profiler.TraceAnnotation``: an event on a host plane, on the
device planes' clock, nested by time inside the stages open on its thread.
The benchmark's own spans are named ``bench.*``. Planes come in the form
:func:`tacobench.tracereduce.reduce_planes` takes.

* :func:`window`: a named window (``bench.build``, ``bench.window``): its
  length, the device's busy time in it and its idle gaps, each named by the
  innermost ``taco.*`` span that overlaps it (the most overlap among those
  as deep), else by the ``bench.*`` span that overlaps it most, else
  ``unattributed``.
* :func:`build_phases`: the ``taco.build`` span, the device's busy share of
  it, and the seconds of its transform, k-means (the sum of the
  ``taco.build.subspace`` spans) and norms phases.
* :func:`engine_host_ms`: per batch of a window, ``taco.engine.batch``
  less its ``taco.searcher.device`` child, the host time during which the
  chip waits on the engine; their median in ms.

Device busy time is the union of one line's event intervals: ``XLA Ops``
(one event per operation), or ``XLA Modules`` (one per executable run), for
a build whose loops emit millions of op events.
"""
from __future__ import annotations

import bisect
import dataclasses
import statistics

import numpy as np

from tacobench.tracereduce import DEVICE_PREFIX, OPS_LINE, WINDOW_SPAN, _union

MODULES_LINE = "XLA Modules"
BUILD_WINDOW = "bench.build"


@dataclasses.dataclass
class Span:
    name: str
    start: int  # ns, on the trace's clock
    end: int
    line: tuple  # (plane index, line index) it was recorded on
    stats: dict
    depth: int = 0  # spans of its line that hold it

    @property
    def ns(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Window:
    name: str
    start: int
    end: int
    busy_ns: float
    gaps: list  # [(name, ns)], longest first

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9

    def taco_share(self) -> float:
        """Share of the idle time in gaps named by a ``taco.*`` span."""
        idle = sum(ns for _n, ns in self.gaps)
        taco = sum(ns for n, ns in self.gaps if n.startswith("taco."))
        return taco / idle if idle else 1.0


def read(path: str) -> list:
    """The planes of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    return [(p.name, [(ln.name, list(ln.events)) for ln in p.lines])
            for p in ProfileData.from_file(path).planes]


def host_spans(planes, prefixes=("taco.", "bench.")) -> list[Span]:
    """Every host event named with one of ``prefixes``, with its depth."""
    out = []
    for pi, (pname, lines) in enumerate(planes):
        if pname.startswith(DEVICE_PREFIX):
            continue
        for li, (_lname, events) in enumerate(lines):
            line = sorted(
                (Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                      (pi, li), dict(ev.stats))
                 for ev in events if ev.name.startswith(prefixes)),
                key=lambda s: (s.start, -s.end))
            open_: list[Span] = []
            for s in line:
                while open_ and open_[-1].end < s.end:
                    open_.pop()
                s.depth = len(open_)
                open_.append(s)
            out += line
    return out


def device_intervals(planes, line: str = OPS_LINE) -> list[list]:
    """Per device plane that has ``line``: its events' ``(start, end)``."""
    out = []
    for pname, lines in planes:
        if not pname.startswith(DEVICE_PREFIX):
            continue
        for lname, events in lines:
            if lname == line:
                out.append([(ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in events])
    return out


def _clipped_union(intervals, start: int, end: int) -> list:
    return _union((max(s, start), min(e, end)) for s, e in intervals
                  if min(e, end) > max(s, start))


def busy_ns(devices: list, start: int, end: int) -> float:
    """Device busy time in ``[start, end]``, averaged over the planes that
    ran anything there."""
    busy = [sum(e - s for s, e in u) for u in
            (_clipped_union(iv, start, end) for iv in devices) if u]
    return sum(busy) / len(busy) if busy else 0.0


def _find(spans, name: str) -> Span:
    for s in spans:
        if s.name == name:
            return s
    raise ValueError(f"the trace has no {name} span")


def name_gaps(gaps, spans: list[Span], skip: str) -> list:
    """``[(name, ns)]`` for ``(start, end)`` gaps, longest first."""
    spans = [s for s in spans if s.name != skip]
    starts = np.array([s.start for s in spans], np.int64)
    ends = np.array([s.end for s in spans], np.int64)
    taco = np.array([s.name.startswith("taco.") for s in spans], bool)
    depth = np.array([s.depth for s in spans], np.int64)
    out = []
    for gs, ge in gaps:
        name = "unattributed"
        if spans:
            overlap = np.minimum(ge, ends) - np.maximum(gs, starts)
            hit = overlap > 0
            if (hit & taco).any():
                pick = hit & taco
                pick &= depth == depth[pick].max()
            else:
                pick = hit
            if pick.any():
                name = spans[int(np.argmax(np.where(pick, overlap, -1)))].name
        out.append((name, ge - gs))
    out.sort(key=lambda g: -g[1])
    return out


def window(planes, name: str = WINDOW_SPAN) -> Window:
    """The window of the first host span called ``name``."""
    spans = host_spans(planes)
    w = _find(spans, name)
    devices = device_intervals(planes)
    gaps = []
    first = next((u for u in (_clipped_union(iv, w.start, w.end)
                              for iv in devices) if u), None)
    if first is not None:
        edges = [w.start] + [x for iv in first for x in iv] + [w.end]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    return Window(name, w.start, w.end, busy_ns(devices, w.start, w.end),
                  name_gaps(gaps, spans, skip=name))


def build_phases(planes, line: str = OPS_LINE) -> dict:
    """Seconds of the ``taco.build`` span and of its phases, and the
    device's busy share of it in percent."""
    spans = host_spans(planes, ("taco.build",))
    build = _find(spans, "taco.build")

    def seconds(phase: str) -> float:
        return sum(s.ns for s in spans if s.name == phase and s.line == build.line
                   and build.start <= s.start and s.end <= build.end) * 1e-9

    out = {"build_s": build.ns * 1e-9,
           "transform_s": seconds("taco.build.transform"),
           "kmeans_s": seconds("taco.build.subspace"),
           "norms_s": seconds("taco.build.norms")}
    out["covered"] = (out["transform_s"] + out["kmeans_s"]
                      + out["norms_s"]) / out["build_s"]
    out["busy_pct"] = 100.0 * busy_ns(device_intervals(planes, line),
                                      build.start, build.end) / build.ns
    return out


def engine_host_ms(planes, name: str = WINDOW_SPAN) -> float | None:
    """Median over the window's executed batches of ``taco.engine.batch``
    less its ``taco.searcher.device`` time, in ms; None without batches."""
    spans = host_spans(planes)
    w = _find(spans, name)
    device: dict = {}  # line -> sorted starts, and the spans
    for s in spans:
        if s.name == "taco.searcher.device":
            device.setdefault(s.line, []).append(s)
    starts = {ln: [s.start for s in ds] for ln, ds in device.items()}
    host = []
    for b in spans:
        if b.name != "taco.engine.batch" or not w.start <= b.start <= b.end <= w.end:
            continue
        ds, st = device.get(b.line, []), starts.get(b.line, [])
        inside = [s.ns for s in ds[bisect.bisect_left(st, b.start):
                                   bisect.bisect_right(st, b.end)]
                  if s.end <= b.end]
        if inside:
            host.append(b.ns - sum(inside))
    return statistics.median(host) * 1e-6 if host else None
