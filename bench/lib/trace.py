"""From the profiler's trace to numbers: the one reduction every PR shares.

Reads the ``*.trace.json.gz`` that ``jax.profiler`` writes beside the
``.xplane.pb``: it carries, for every device operation, its start and
duration, its HLO text (``long_name``), its category and the source line that
emitted it, and the host's ``TraceAnnotation`` spans on the same clock.

  busy         union of the intervals in which an operation ran on a device
  time by ...  summed durations of the operations a predicate picks
  idle gaps    the stretches with no operation, by the benchmark's host span
               (``bench.*``) that covers each gap's middle
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAP_FLOOR_US = 20.0        # shorter breaks between operations are not "idle"


def find(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    return found[-1] if found else None


def load(path: str) -> dict:
    """{"devices": {index: {"ops": [...], "modules": [...]}}, "spans": [...]}
    with times in microseconds. An op is (start, dur, name, category,
    long_name, source)."""
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e["name"] == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e["name"] == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    devices = defaultdict(lambda: {"ops": [], "modules": []})
    spans = []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        m = re.fullmatch(r"/device:TPU:(\d+)", proc)
        if m:
            line = threads.get((e["pid"], e["tid"]))
            a = e.get("args", {})
            if line == OPS_LINE:
                devices[int(m.group(1))]["ops"].append(
                    (e["ts"], e["dur"], e["name"], a.get("hlo_category", ""),
                     a.get("long_name", ""), a.get("source", "")))
            elif line == MODULES_LINE:
                devices[int(m.group(1))]["modules"].append(
                    (e["ts"], e["dur"], e["name"]))
        elif proc.startswith("/host:") and e["name"].startswith("bench."):
            spans.append((e["ts"], e["dur"], e["name"]))
    return {"devices": dict(devices), "spans": sorted(spans)}


def busy_intervals(ops):
    """The union of [start, start+dur) over ``ops``, as sorted intervals."""
    out = []
    for start, dur, *_ in sorted(ops):
        end = start + dur
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def busy_us(ops) -> float:
    return sum(b - a for a, b in busy_intervals(ops))


def time_where(ops, pick) -> float:
    """Summed duration (us) of the operations ``pick(op)`` accepts."""
    return sum(op[1] for op in ops if pick(op))


def is_pallas(op) -> bool:
    return 'custom_call_target="tpu_custom_call"' in op[4]


def from_source(op, fragment: str) -> bool:
    return fragment in op[5]


def label(op) -> str:
    """A name for an operation that survives renumbering: its category, the
    shape it produces and the source line that emitted it."""
    shape = re.match(r"%\S+ = \(?([a-z0-9]+\[[0-9,]*\])", op[4])
    src = "/".join(op[5].split("/")[-2:]) if op[5] else ""
    return " ".join(x for x in (op[3] or op[2], shape.group(1) if shape else "",
                                src) if x)


def top_ops(ops, n=10):
    total, count = defaultdict(float), defaultdict(int)
    for op in ops:
        k = label(op)
        total[k] += op[1]
        count[k] += 1
    best = sorted(total, key=total.get, reverse=True)[:n]
    return [[f"{k} x{count[k]}", total[k] / 1e6] for k in best]


def idle_gaps(ops, spans, start, end, n=10):
    """Idle stretches of [start, end] by the host span over their middle."""
    busy = busy_intervals(ops)
    edges = [start] + [t for iv in busy for t in iv] + [end]
    by = defaultdict(float)
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a < GAP_FLOOR_US:
            continue
        mid = (a + b) / 2
        name = "outside bench spans"
        for s, d, nm in spans:
            if s <= mid <= s + d:
                name = nm          # the innermost (latest-starting) wins
        by[name] += b - a
    best = sorted(by, key=by.get, reverse=True)[:n]
    return [[k, by[k] / 1e6] for k in best]


def reduce_dir(trace_dir: str, window_s: float) -> dict:
    """Everything the readers and the result line take from one trace: the
    one device's. (A cell across chips brings the reduction over several
    devices with it: PERF.md section 7.)"""
    path = find(trace_dir)
    if path is None:
        raise RuntimeError(f"the profiler left no trace under {trace_dir}")
    t = load(path)
    if len(t["devices"]) != 1:
        raise RuntimeError("the reduction reads one device; the trace holds "
                           f"{sorted(t['devices'])}")
    (dev,) = t["devices"].values()
    ops = dev["ops"]
    if not ops:
        raise RuntimeError("the trace holds no device operation")
    lo = min(op[0] for op in ops)
    hi = max(op[0] + op[1] for op in ops)
    return {
        "window_s": window_s,
        "busy_s": busy_us(ops) / 1e6,
        "ops": ops,
        "modules": dev["modules"],
        "spans": t["spans"],
        "top_ops": top_ops(ops),
        "idle_gaps": idle_gaps(ops, t["spans"], lo, hi),
    }
