"""Run one cell with values of its traffic file overridden, several times in
one process-per-run loop: the tool behind the knee sweep and the batch
rehearsal that PERF.md records. Not part of a benchmark run.

    python3 bench/tools/sweep.py --workload mistral7b-serve-chat --seconds 30 \\
        --set rate=2.0 --set rate=2.6 --seeds 11,12

Each ``--set`` is one point (``key=value`` pairs joined by commas); every
point runs in a fresh process (a chip belongs to one process) and prints the
result line with everything the kind measured.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def one(args):
    from bench import run as R
    from bench.lib import spec
    cell = spec.Cell(args.workload)
    for pair in filter(None, args.point.split(",")):
        key, value = pair.split("=")
        where = cell.traffic
        *path, last = key.split(".")
        for k in path:
            where = where[k]
        where[last] = json.loads(value)
    device = R.start(cell)
    run, out = R.drive(cell, args)
    m = {k: v for k, v in out["measured"].items()
         if isinstance(v, (int, float))}
    m["setup_s"] = run.setup_s
    print("SWEEP " + json.dumps({
        "point": args.point, "seed": args.seed, "measured": m,
        "numbers": out["numbers"], "attempted": out["attempted"],
        "failed": out["failed"], "memory_peak_bytes": out["memory_peak_bytes"],
        "device": device, "notes": run.notes}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--set", action="append", default=[], dest="points")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--point")           # internal: run this one point
    ap.add_argument("--seed", type=int)  # internal
    args = ap.parse_args()
    if args.point is not None:
        return one(args)
    for point in args.points or [""]:
        for seed in args.seeds.split(","):
            rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                                 "--workload", args.workload, "--seconds",
                                 str(args.seconds), "--trace", str(args.trace),
                                 "--point", point, "--seed", seed]).returncode
            if rc:
                print(f"SWEEP {json.dumps({'point': point, 'seed': seed, 'rc': rc})}",
                      flush=True)


if __name__ == "__main__":
    main()
