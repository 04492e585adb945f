"""The program's own spans and scopes, read from the profiler's trace.

``paddle_tpu`` writes fixed host spans (``serve.*`` around the phases of an
engine step, ``train.*`` around a training step) into whatever
``jax.profiler`` trace is running, with a few counts as their arguments, and
names its device work by scope (``jax.named_scope``, a Pallas kernel's
``name``): the scope path is the operation's ``tf_op`` argument. This module
reads both from the run's ``*.trace.json.gz`` (found through
``bench.lib.trace.find``, under ``<cell.root>/.bench_trace/<cell>`` as
``Run.trace_dir`` builds it), which ``bench.lib.trace`` reduces without them.

  spans        (start, dur, name, args) of every ``serve.*``/``train.*`` span
  ops          (start, dur, name, tf_op, own) of every device operation
  idle         the stretches with no operation, cut at the spans' edges, each
               piece by the innermost program span over it

The compiler makes operations of its own (a copy, a convert, a change of
layout) and they carry no ``tf_op``: such an operation is read under the scope
of the operation whose result it takes, or else of the one that takes its
result, inside its own program (``own`` is False then: the label is inferred,
and ``time_in(..., inferred_only=True)`` says how much of a scope's time is).
A trace with no program span is of a program that has none: nothing to read.
"""
from __future__ import annotations

import bisect
import functools
import gzip
import json
import os
import re
from collections import defaultdict, deque

from . import trace as T

PROGRAM = ("serve.", "train.")
OUTSIDE = "outside program spans"


@functools.lru_cache(maxsize=2)
def load(path: str):
    """{"spans": [...], "ops": [...]} of the one device and the host, times
    in microseconds, both sorted by start; None where the trace holds no
    program span or no device operation."""
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
    spans, ops, modules = [], [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        if proc.startswith("/host:"):
            if e["name"].startswith(PROGRAM):
                spans.append((e["ts"], e["dur"], e["name"], e.get("args", {})))
        elif re.fullmatch(r"/device:TPU:\d+", proc):
            line = threads.get((e["pid"], e["tid"]))
            if line == T.OPS_LINE:
                a = e.get("args", {})
                ops.append((e["ts"], e["dur"], e["name"], a.get("tf_op", ""),
                            a.get("long_name", "")))
            elif line == T.MODULES_LINE:
                modules.append((e["ts"], e["dur"], e["name"]))
    if not spans or not ops:
        return None
    modules.sort()
    starts = [m[0] for m in modules]

    def module_of(ts):
        """The program (``jit_step_fn(<fingerprint>)``) running at ``ts``."""
        i = bisect.bisect_right(starts, ts) - 1
        return modules[i][2] if i >= 0 and ts <= sum(modules[i][:2]) else ""

    ops = [op + (module_of(op[0]),) for op in ops]
    scope = inherited_scopes(ops)
    ops = [(ts, dur, name, tf_op or scope.get((module, name), ""), bool(tf_op))
           for ts, dur, name, tf_op, _, module in ops]
    return {"spans": sorted(spans, key=lambda s: s[:2]), "ops": sorted(ops)}


def inherited_scopes(ops) -> dict:
    """{(module, name): tf_op} for the operations that carry none of their
    own: that of the nearest operation it takes a result from, through chains
    of such operations, else of the nearest one that takes its result. An
    operation's name (``fusion.7``, ``copy-start``) is its own only inside
    its program, so the graph is kept per module. ``ops`` hold the HLO text
    (``%name = shape op(... %operand ...)``) and the module in their last two
    places. Producer before consumer is a choice of label, not a measurement:
    a copy between two scopes could as well be charged to the one that reads
    it."""
    own, feeds, fed_by = {}, defaultdict(list), {}
    for _, _, name, tf_op, text, module in ops:
        key = (module, name)
        if key in fed_by:
            continue                   # the same program, one step later
        if tf_op:
            own[key] = tf_op
        fed_by[key] = [(module, operand) for operand in re.findall(
            r"%([\w.\-]+)", text.partition(" = ")[2])]
        for operand in fed_by[key]:
            feeds[operand].append(key)

    def nearest(key, edges):
        seen, queue = {key}, deque([key])
        while queue:
            for other in edges.get(queue.popleft(), ()):
                if other in own:
                    return own[other]
                if other in fed_by and other not in seen:
                    seen.add(other)
                    queue.append(other)
        return ""

    return {key: nearest(key, fed_by) or nearest(key, feeds)
            for key in fed_by if key not in own}


def of_run(ctx):
    """The loaded trace of the run a reader is called for, or None."""
    cell = ctx["cell"]
    path = T.find(os.path.join(cell.root, ".bench_trace", cell.name))
    return load(path) if path else None


def named(t, name):
    return [s for s in t["spans"] if s[2] == name]


def total(t, names) -> float:
    """Summed duration (us) of the spans called one of ``names``."""
    return sum(s[1] for s in t["spans"] if s[2] in names)


def between(t, name) -> float:
    """Summed time (us) from the end of one span called ``name`` to the
    start of the next one."""
    got = named(t, name)
    return sum(max(0.0, b[0] - (a[0] + a[1])) for a, b in zip(got, got[1:]))


def argument(t, name, keys) -> float:
    """Sum over the spans called ``name`` of the arguments ``keys`` (the
    profiler hands them over as strings)."""
    return sum(float(s[3][k]) for s in named(t, name) for k in keys)


@functools.lru_cache(maxsize=None)
def _whole(fragment: str):
    return re.compile(
        rf"(?<![A-Za-z0-9_]){re.escape(fragment)}(?![A-Za-z0-9_])").search


def has(fragment: str, op) -> bool:
    """Is ``fragment`` a whole name in the operation's scope path (``tf_op``:
    ``jit(step_fn)/transpose(jvp(head_loss))/dot_general``) or in its own
    name (``flash_fwd.3``)? ``attention`` is not in ``paged_attention``."""
    found = _whole(fragment)
    return bool(found(op[3]) or found(op[2]))


def time_in(t, fragments, inferred_only=False) -> float:
    """Summed duration (us) of the operations under any of ``fragments``;
    with ``inferred_only`` of those among them that carry no ``tf_op`` of
    their own and are there by a neighbour's."""
    return sum(op[1] for op in t["ops"] if not (inferred_only and op[4])
               and any(has(f, op) for f in fragments))


def idle_by_span(t) -> dict:
    """Idle seconds of the device between its first and its last operation,
    by program span. A gap is cut at every edge of a span inside it, and each
    piece goes to the innermost span over it: the latest to start; ``OUTSIDE``
    where none covers it. (One gap a step reaches from the end of one step's
    program through emit, the generator, schedule and pack into the next
    launch: its middle alone would name one of them.) Gaps under
    ``trace.GAP_FLOOR_US`` are not idle."""
    busy = T.busy_intervals(t["ops"])
    spans = t["spans"]
    starts = [s[0] for s in spans]
    edges = sorted({x for s in spans for x in (s[0], s[0] + s[1])})
    by = defaultdict(float)
    for (_, a), (b, _) in zip(busy, busy[1:]):
        if b - a < T.GAP_FLOOR_US:
            continue
        cuts = [a] + edges[bisect.bisect_right(edges, a):
                           bisect.bisect_left(edges, b)] + [b]
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            i = bisect.bisect_right(starts, mid) - 1
            while i >= 0 and spans[i][0] + spans[i][1] < mid:
                i -= 1                 # ended before this piece: a sibling
            by[spans[i][2] if i >= 0 else OUTSIDE] += (hi - lo) / 1e6
    return dict(by)


def scope_of(op) -> str:
    """The operation's scope path without the program's name in front and
    the primitive behind: ``transpose(jvp(jvp()))/checkpoint/attention``."""
    path = op[3].split(";")[0].rstrip(":").split("/")
    return "/".join(path[1:-1])
