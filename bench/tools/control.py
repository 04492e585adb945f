"""Read, on the chip at a cell's own size, the numbers that decide ``correct``
for the program and for its control: the readings PERF.md sets every limit
from. Not part of a benchmark run.

    python3 bench/tools/control.py --workload <cell> --seconds 30 \\
        --seeds 1,2,3 --control-seeds 1,2,3

One process per seed (a chip belongs to one process). Each runs the cell's
kind for ``--seconds`` and prints the program's numbers; on the control's
seeds it then puts the plain reference, computed in float8 (the nearest
precision below bfloat16), in the program's place over the same batches or
the same prompts and tokens, and for a training cell also the reference with
half of the batch left out.
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
    from bench.lib import compare, serving, spec
    from bench.reference.common import fp8
    cell = spec.Cell(args.workload)
    device = R.start(cell)
    _, out = R.drive(cell, args)
    line = {"seed": args.seed, "program": out["numbers"], "device": device["kind"]}
    if args.with_control:
        arch, cfg = cell.arch(), cell.config
        if "sequences" in out:
            line["served_tokens"] = sum(len(s) for _, s in out["sequences"])
            line["control_fp8"] = serving.control_numbers(
                arch, cfg, args.seed, out["sequences"], fp8)
        else:
            from bench.kinds import train_batches as tb
            from bench.reference import train_steps
            t = cell.traffic
            bs = tb.batches(t, cfg["vocab_size"], args.seed, tb.FOLLOWED)
            for name, kw in (("control_fp8", {"q": fp8}),
                             ("fault_half_batch", {"fault": "half_batch"})):
                got = train_steps.follow(arch, cfg, args.seed, bs, tb.hyper(t), **kw)
                line[name] = compare.training_numbers(got, out["reference"])
    print("CONTROL " + json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int)                       # internal
    ap.add_argument("--with-control", action="store_true")    # internal
    args = ap.parse_args()
    if args.seed is not None:
        return one(args)
    controls = set(filter(None, args.control_seeds.split(",")))
    for seed in args.seeds.split(","):
        cmd = [sys.executable, os.path.abspath(__file__), "--workload",
               args.workload, "--seconds", str(args.seconds), "--seeds", "x",
               "--seed", seed]
        if seed in controls:
            cmd.append("--with-control")
        rc = subprocess.run(cmd).returncode
        if rc:
            print("CONTROL " + json.dumps({"seed": seed, "rc": rc}), flush=True)


if __name__ == "__main__":
    main()
