#!/usr/bin/env python3
"""Print what a profiler trace holds, to name kernels in the metric readers.

    python3 bench/inspect_trace.py path/to/host.xplane.pb [--top 25]

For every plane: its lines and their event counts; for device planes, the
ops of the ``XLA Ops`` line by total time, each with the stats of its first
event (where a Pallas kernel's own name shows).
"""
import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(args.path)
    for plane in pd.planes:
        lines = [(ln.name, list(ln.events)) for ln in plane.lines]
        print(f"PLANE {plane.name}: " + ", ".join(
            f"{n} ({len(ev)})" for n, ev in lines))
        for name, events in lines:
            if not plane.name.startswith("/device:") or name != "XLA Ops":
                bench = sorted({e.name for e in events if e.name.startswith("bench.")})
                if bench:
                    print(f"  line {name}: bench spans {bench}")
                continue
            total, first = {}, {}
            for e in events:
                total[e.name] = total.get(e.name, 0) + e.duration_ns
                first.setdefault(e.name, e)
            for op, ns in sorted(total.items(), key=lambda kv: -kv[1])[:args.top]:
                stats = " ".join(f"{k}={v}" for k, v in first[op].stats)
                print(f"  {ns / 1e6:12.3f} ms  {op}  |  {stats[:400]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
