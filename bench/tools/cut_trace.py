"""Cut a few steps out of a traced run into a fixture for the tests:

    python3 bench/tools/cut_trace.py .bench_trace/<cell> out.trace.json.gz \
        [--skip 20] [--steps 2]

From the start of program step ``skip`` (a ``serve.step`` or ``train.step``
span) to the start of step ``skip + steps``: the device's operations and
modules in between, the program's and the benchmark's host spans, and the
metadata naming their lines. Arguments the reductions do not read are
dropped and an operation's HLO text is cut short (its result shape, its
operands' names and whether it is a Pallas call stay), so two steps fit in a
few hundred KB.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import spans as S, trace as T     # noqa: E402

KEEP_ARGS = ("tf_op", "hlo_category", "source")
PALLAS = 'custom_call_target="tpu_custom_call"'
HOST_SPANS = S.PROGRAM + ("bench.",)


def cut(events, skip, steps):
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    host = lambda e: procs.get(e["pid"], "").startswith("/host:")
    starts = sorted(e["ts"] for e in events if e.get("ph") == "X" and host(e)
                    and e["name"] in ("serve.step", "train.step"))
    if len(starts) < skip + steps + 1:
        raise SystemExit(f"the trace holds {len(starts)} steps")
    lo, hi = starts[skip], starts[skip + steps]
    out, used = [], set()
    for e in events:
        if e.get("ph") != "X" or not lo <= e["ts"] < hi:
            continue
        line = threads.get((e["pid"], e["tid"]))
        if host(e):
            if not e["name"].startswith(HOST_SPANS):
                continue
            e = dict(e)
        elif line in (T.OPS_LINE, T.MODULES_LINE):
            a = e.get("args", {})
            args = {k: a[k] for k in KEEP_ARGS if k in a}
            if "long_name" in a:
                text = a["long_name"]
                args["long_name"] = " ".join(
                    [text[:120]]
                    + re.findall(r"%[\w.\-]+", text.partition(" = ")[2])
                    + [PALLAS] * (PALLAS in text))
            e = dict(e, args=args)
        else:
            continue
        out.append(e)
        used.add((e["pid"], e["tid"]))
    meta = [e for e in events if e.get("ph") == "M" and (
        (e["name"] == "process_name" and e["pid"] in {p for p, _ in used})
        or (e["name"] == "thread_name" and (e["pid"], e["tid"]) in used))]
    return meta + out


def earliest(events, n):
    """What the profiler's export does to a trace of more than a million
    events: the metadata, and the ``n`` events that start first."""
    timed = sorted((e for e in events if e.get("ph") != "M"),
                   key=lambda e: e["ts"])[:n]
    return [e for e in events if e.get("ph") == "M"] + timed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--skip", type=int, default=20)
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args(argv)
    path = T.find(args.trace_dir)
    if path is None:
        raise SystemExit(f"no trace under {args.trace_dir}")
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    kept = cut(events, args.skip, args.steps)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt", compresslevel=9) as f:
        json.dump({"traceEvents": kept}, f, separators=(",", ":"))
    print(f"{len(kept)} events, {os.path.getsize(args.out)} bytes -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
