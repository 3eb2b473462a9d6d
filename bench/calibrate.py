#!/usr/bin/env python3
"""Readings of the numbers ``correct`` compares, for setting their limits.

    python3 bench/calibrate.py --workload deep10m.bulk --seconds 10 \\
        --seeds 101-112 --control-seeds 201-203 \\
        --fault beta_halved --fault-seeds 301-303

One process runs the cell once per seed as the configuration states it
(the sound readings), then once per control seed with the program's own
bfloat16 path switched on (the precision control), then once per fault
seed for each ``--fault`` planted in pass 1 (``tacobench.faults``), each
at the cell's own size, load and window. It prints one JSON line per run
with the compared numbers, the end-to-end metrics and the run's seconds,
and a summary of each number's least and greatest reading per kind of
run.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parent)]

from tacobench import faults  # noqa: E402


def _seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112")
    ap.add_argument("--control-seeds", default="", help="e.g. 201-203")
    ap.add_argument("--fault", action="append", default=[],
                    choices=faults.FAULTS)
    ap.add_argument("--fault-seeds", default="", help="e.g. 301-303")
    args = ap.parse_args(argv)
    import run as bench_run
    from tacobench import spec
    from tacobench.cell import NoChip, run_cell

    cell = spec.cell(args.workload)
    bench_run.use_compile_cache()
    runs = [(s, "sound", None) for s in _seeds(args.seeds)]
    if args.control_seeds:
        runs += [(s, "bf16", None) for s in _seeds(args.control_seeds)]
    if args.fault_seeds:
        runs += [(s, f, f) for f in args.fault for s in _seeds(args.fault_seeds)]
    rows = []
    for seed, kind, fault in runs:
        t = time.perf_counter()
        try:
            with faults.planted(fault):
                line = run_cell(cell, seed, args.seconds, False, t_start=t,
                                precision="bf16" if kind == "bf16" else None)
        except NoChip as e:
            print(f"bench/calibrate.py: {e}", file=sys.stderr)
            return 2
        row = {"seed": seed, "kind": kind, "correct": line["correct"],
               "attempted": line["attempted"],
               **{f"check.{k}": c["value"] for k, c in line["checks"].items()},
               **{k: m["value"] for k, m in line["metrics"].items()},
               "run_s": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for kind in dict.fromkeys(r["kind"] for r in rows):
        mine = [r for r in rows if r["kind"] == kind]
        summary[kind] = {
            key: [min(vals), max(vals)]
            for key in mine[0]
            if key not in ("seed", "kind", "correct")
            for vals in [[r[key] for r in mine
                          if isinstance(r.get(key), (int, float))]]
            if vals}
        summary[kind]["correct"] = [r["correct"] for r in mine]
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
