"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. Device planes are named
``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per executed
operation. The benchmark's own host spans (``jax.profiler.TraceAnnotation``
named ``bench.*``) are events on the host plane, on the same clock.

* ``window``: the ``bench.window`` span, the measured window as traced.
* ``busy_s``: per device plane, the length of the union of its op intervals
  inside the window; averaged over the planes that ran anything.
* ``op_seconds`` / ``op_counts``: device time and launches per op, inside
  the window. An op that the window's edge cuts is clipped to it and counts
  as the fraction of its launch that lies inside, so that work counted per
  launch stays in proportion to the time. On a TPU an event's name is the op's
  whole HLO text, ``%schist_pallas.1 = s32[64,128]... custom-call(...)``;
  an op is keyed by its own name, the part before `` = `` (``schist_pallas.1``),
  so ops of that name in the executables of several buckets add up.
* ``op_labels``: per op, its first event's full name and stats.
* ``gaps``: idle intervals of the first device plane inside the window,
  each named by the ``bench.*`` host span that overlaps it most.
"""
from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    op_seconds: dict
    op_counts: dict
    op_labels: dict
    gaps: list  # [(name, seconds)], longest first

    def kernel_seconds(self, *needles: str) -> tuple[float, int]:
        """Total device seconds and launches of the ops whose own name
        holds any of ``needles``."""
        secs = count = 0
        for name, s in self.op_seconds.items():
            if any(nd in name for nd in needles):
                secs += s
                count += self.op_counts[name]
        return secs, count

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(event_name: str) -> str:
    """``%pad.27 = f32[...] pad(...)`` -> ``pad.27``; other names as they are."""
    head, sep, _rest = event_name.partition(" = ")
    return head.lstrip("%") if sep else event_name


def _label(event) -> str:
    try:
        stats = " ".join(f"{k}={v}" for k, v in event.stats)
    except (TypeError, ValueError):
        stats = ""
    return f"{event.name} | {stats}"


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_planes(planes) -> TraceSummary:
    """Reduce planes given as ``[(name, [(line name, [events])])]`` where an
    event has ``name``, ``start_ns``, ``duration_ns`` and ``stats``."""
    host_spans = []
    devices = []
    for pname, lines in planes:
        if pname.startswith(DEVICE_PREFIX):
            for lname, events in lines:
                if lname == OPS_LINE:
                    devices.append(list(events))
        else:
            for _lname, events in lines:
                for ev in events:
                    if ev.name.startswith("bench."):
                        host_spans.append(
                            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    ws, we = windows[0]
    op_seconds, op_counts, op_labels = {}, {}, {}
    busy = []
    first_union = None
    for events in devices:
        ivals = []
        for ev in events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            s, e = max(s, ws), min(e, we)
            if e <= s:
                continue
            ivals.append((s, e))
            name = op_name(ev.name)
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) * 1e-9
            share = (e - s) / ev.duration_ns if ev.duration_ns > 0 else 1.0
            op_counts[name] = op_counts.get(name, 0.0) + share
            if name not in op_labels:
                op_labels[name] = _label(ev)
        if ivals:
            u = _union(ivals)
            busy.append(sum(e - s for s, e in u) * 1e-9)
            if first_union is None:
                first_union = u
    gaps = []
    if first_union is not None:
        edges = [ws] + [x for iv in first_union for x in iv] + [we]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            best, name = 0, "unattributed"
            for hn, hs, he in host_spans:
                if hn == WINDOW_SPAN:
                    continue
                overlap = min(e, he) - max(s, hs)
                if overlap > best:
                    best, name = overlap, hn
            gaps.append((name, (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(we - ws) * 1e-9,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        op_seconds=op_seconds, op_counts=op_counts, op_labels=op_labels,
        gaps=gaps)


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = [(p.name, [(ln.name, list(ln.events)) for ln in p.lines])
              for p in pd.planes]
    return reduce_planes(planes)
