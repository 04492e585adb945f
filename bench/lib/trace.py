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
STEP_SPANS = ("bench.engine_step", "bench.train_step")   # one a step, the kinds'


def find(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    return found[-1] if found else None


def count_events(path: str) -> int:
    """The events the export holds, as its cap counts them: all but the
    metadata."""
    with gzip.open(path) as f:
        return sum(1 for e in json.load(f)["traceEvents"] if e.get("ph") != "M")


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


def kept(ctx, module):
    """What the trace kept of the slice's steps, for every reader that divides
    by steps or by time. The profiler's export keeps the earliest million
    events (PERF.md section 7), so a slice may be cut: this counts the
    executions of the step program (``module``: the start of its name on the
    ``XLA Modules`` line) that the trace holds and pairs them with the first
    that many steps of the slice that ran a plan. Where the trace holds fewer
    executions than the slice has steps it was cut, and the last execution may
    have lost operations: it is left out. Returns None where nothing was kept,
    else

      steps     the kept steps' records, as the kinds keep them
      seconds   the kept executions' summed device seconds
      busy_s    seconds in which an operation ran, from the first kept
                execution's start to the start of the one after the last
                (to the last operation's end in an uncut trace): whole
                periods, the argmax and check programs between steps in them
      cut       whether the trace was cut

    Nothing here is divided by the slice's length."""
    t, m = ctx["trace"], ctx["measured"]
    first, last = m.get("slice", (None, None))
    if first is None or last is None or last <= first:
        return None
    steps = [s for s in m["steps"][first:last] if s[2] > 0]
    runs = sorted((ts, d) for ts, d, n in t["modules"] if n.startswith(module))
    cut = len(runs) < len(steps)
    n = min(len(runs) - 1 if cut else len(runs), len(steps))
    if n <= 0:
        return None
    lo = runs[0][0]
    hi = runs[n][0] if n < len(runs) else max(op[0] + op[1] for op in t["ops"])
    return {"steps": steps[:n], "seconds": sum(d for _, d in runs[:n]) / 1e6,
            "busy_s": busy_us([op for op in t["ops"] if lo <= op[0] < hi]) / 1e6,
            "cut": cut}


def reduce_dir(trace_dir: str, window_s: float, steps: int = None) -> dict:
    """Everything the readers and the result line take from one trace: the
    one device's. (A cell across chips brings the reduction over several
    devices with it: PERF.md section 7.) ``window_s`` is the slice's length
    on the host's clock and ``steps`` the steps the kind ran in it. Where the
    trace holds fewer of the kinds' step spans (``STEP_SPANS``) than that, the
    export dropped the slice's end (``trace_cut``): the window is then from
    the first kept step's start to the last kept one's, busy time is taken
    inside it, and the last step, which may have lost operations, is outside."""
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
    step_starts = [s[0] for s in t["spans"] if s[2] in STEP_SPANS]
    cut = steps is not None and 1 < len(step_starts) < steps
    inside = ops
    if cut:
        lo, hi = step_starts[0], step_starts[-1]
        window_s = (hi - lo) / 1e6
        inside = [op for op in ops if lo <= op[0] < hi]
    return {
        "window_s": window_s,
        "busy_s": busy_us(inside) / 1e6,
        "trace_cut": cut,
        "steps_kept": len(step_starts) - 1 if cut else len(step_starts),
        "ops": ops,
        "modules": dev["modules"],
        "spans": t["spans"],
        "top_ops": top_ops(ops),
        "idle_gaps": idle_gaps(inside, t["spans"], lo, hi),
    }
