"""Profiler + observability plane.

Reference parity: python/paddle/profiler/ (Profiler profiler.py:358 with
states CLOSED/READY/RECORD/RECORD_AND_RETURN, ProfilerTarget, RecordEvent
utils.py:47, make_scheduler, chrome-trace export, summary tables) wrapping
the C++ host tracer + CUPTI (fluid/platform/profiler/).

TPU-native: every ``RecordEvent`` is a ``jax.profiler.TraceAnnotation``, so
its span lands in the host plane of whatever ``jax.profiler`` trace is
running, beside the device operations and on their clock (XLA's TPU trace is
the platform's CUPTI equivalent). While a ``Profiler`` records, spans are
also kept in ONE shared, lock-guarded buffer (they may begin/end on any
thread — dataloader worker spans are collected too) for ``summary()`` and
the chrome export. The serving engine and the trainer carry fixed spans
(``serve.*``, ``train.*``; README "Observability"; ``host_time`` counts where
each engine step's host time went and puts every Python collection on the
trace as a span); the spans per dispatched op and per collective entry
point, thousands a step, stay behind a single boolean so disabled runs pay
one check.

Exports: chrome-trace JSON with rank-qualified pids, process/thread-name
metadata and a wall-clock anchor (``tools/trace_merge.py`` merges N ranks
into one timeline); protobuf wire format (``export_protobuf``); summary
tables (``Profiler.summary`` honoring ``SortedKeys``).

Beyond tracing, this package is the metrics plane (``profiler.metrics``:
Counter/Gauge/Histogram registry with JSON + Prometheus text exporters,
framework built-ins in ``profiler.instrument``) and the structured run log
(``profiler.runlog``: per-rank JSONL step records with step time, loss,
tokens/s and a FLOPs-based MFU estimate).
"""
from __future__ import annotations

import json
import os
import threading
import time
from enum import Enum
from typing import Callable, List, Optional

from jax.profiler import TraceAnnotation

from . import evidence, host_time, instrument, memwatch, metrics  # noqa: F401
from . import runlog  # noqa: F401 (re-export)
from .memwatch import (MemoryWatcher, MemWatchConfig,  # noqa: F401
                       resolve_watcher)
from .metrics import (Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, disable_metrics, enable_metrics,
                      get_registry, metrics_enabled, reset_registry)
from .runlog import RunLog, model_flops_per_step, read_runlog  # noqa: F401

CLOCK_ANCHOR_EVENT = "paddle_tpu.clock_anchor"


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class TracerEventType(Enum):
    Operator = 0
    Dataloader = 1
    ProfileStep = 2
    Forward = 3
    Backward = 4
    Optimization = 5
    Communication = 6
    PythonOp = 7
    UserDefined = 8


class _HostTracer:
    """Process-wide span buffer. NOT thread-local: spans begun on worker
    threads (dataloader, async checkpoint) land in the same lock-guarded
    list the profiler collects from — the old per-thread buffers silently
    dropped every worker-thread span."""

    __slots__ = ("enabled", "events", "lock")

    def __init__(self):
        self.enabled = False
        self.events: List[dict] = []
        self.lock = threading.Lock()


_tracer = _HostTracer()


def _now_us() -> float:
    return time.perf_counter_ns() / 1000.0


_pid_cell: List[Optional[int]] = [None]


def _trace_pid() -> int:
    """Rank-qualified pid: the global rank in multi-rank jobs (so merged
    timelines get one track per rank), the OS pid otherwise."""
    if _pid_cell[0] is None:
        from ..distributed.host_collectives import world_info
        rank, world = world_info()
        _pid_cell[0] = rank if world > 1 else os.getpid()
    return _pid_cell[0]


class RecordEvent:
    """Context manager / start-end span (parity: profiler/utils.py:47).

    The span always enters a ``jax.profiler.TraceAnnotation``: whatever
    ``jax.profiler`` trace is running (this module's ``Profiler`` or a bare
    ``jax.profiler.start_trace``) gets it in its host plane, on the clock
    the device operations are on, with ``counts`` as the event's arguments
    (taken when the span is built). With no trace running that is an
    inactive ``TraceMe``, under a microsecond. The private buffer is only
    what ``Profiler.summary()`` and the chrome export read while a
    ``Profiler`` records."""

    __slots__ = ("name", "event_type", "counts", "_ann", "_begin")

    def __init__(self, name: str,
                 event_type: TracerEventType = TracerEventType.UserDefined,
                 **counts):
        self.name = name
        self.event_type = event_type
        self.counts = counts
        self._ann = TraceAnnotation(name, **counts)
        self._begin = None

    def begin(self):
        self._ann.__enter__()
        # buffer off: one boolean check, no clock read
        self._begin = _now_us() if _tracer.enabled else None

    def end(self):
        self._ann.__exit__(None, None, None)
        if self._begin is None or not _tracer.enabled:
            self._begin = None
            return
        ev = {
            "name": self.name, "cat": self.event_type.name, "ph": "X",
            "ts": self._begin, "dur": _now_us() - self._begin,
            "pid": _trace_pid(), "tid": threading.get_ident() % 100000,
        }
        if self.counts:
            ev["args"] = dict(self.counts)
        with _tracer.lock:
            _tracer.events.append(ev)
        self._begin = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """State machine over step numbers (parity: profiler.make_scheduler)."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def _chrome_payload(events: List[dict]) -> dict:
    """Chrome-trace JSON body: spans + process/thread-name metadata
    (ph:"M") + a wall-clock anchor instant event so multi-rank traces can
    be aligned by tools/trace_merge.py. displayTimeUnit makes Perfetto
    render ms instead of raw microsecond ticks."""
    from ..distributed.host_collectives import world_info
    rank, world = world_info()
    meta: List[dict] = []
    seen_pids, seen_tids = set(), set()
    for e in events:
        pid = e.get("pid", 0)
        if pid not in seen_pids:
            seen_pids.add(pid)
            pname = f"rank {rank} (paddle_tpu)" if world > 1 \
                else f"paddle_tpu host {pid}"
            meta.append({"name": "process_name", "ph": "M", "pid": pid,
                         "args": {"name": pname}})
            meta.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                         "args": {"sort_index": rank}})
        tkey = (pid, e.get("tid", 0))
        if tkey not in seen_tids:
            seen_tids.add(tkey)
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tkey[1],
                         "args": {"name": f"thread {tkey[1]}"}})
    anchor_pid = next(iter(seen_pids)) if seen_pids else _trace_pid()
    anchor = {"name": CLOCK_ANCHOR_EVENT, "ph": "i", "s": "g",
              "pid": anchor_pid, "tid": 0, "ts": _now_us(),
              "args": {"unix_time_us": time.time() * 1e6, "rank": rank}}
    return {"traceEvents": meta + [anchor] + list(events),
            "displayTimeUnit": "ms"}


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready callback writing chrome://tracing JSON."""
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_{prof._export_seq}.json")
        prof._export_seq += 1
        with open(path, "w") as f:
            json.dump(_chrome_payload(prof._events), f)
        prof.last_export_path = path
    return handler


class Profiler:
    """Parity: paddle.profiler.Profiler (profiler.py:358).

    with Profiler(targets=[...], scheduler=(2, 5)) as p:
        for batch: train(); p.step()
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only: bool = False, record_shapes: bool = False,
                 profile_memory: bool = False, with_flops: bool = False):
        self.targets = list(targets or [ProfilerTarget.CPU])
        if scheduler is None:
            self._scheduler = lambda step: ProfilerState.RECORD
        elif isinstance(scheduler, tuple):
            start, end = scheduler
            self._scheduler = make_scheduler(closed=max(start, 0), ready=0,
                                             record=end - start, repeat=1)
        else:
            self._scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._events: List[dict] = []
        self._export_seq = 0
        self.last_export_path = None
        self._step_times: List[float] = []
        self._last_step_ts = None
        self._jax_trace_dir = None

    # -- lifecycle ------------------------------------------------------------
    def start(self):
        self._state = self._scheduler(self._step)
        self._apply_state()

    def stop(self):
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN):
            self._collect()
            self._finish_record()
        self._state = ProfilerState.CLOSED
        _tracer.enabled = False

    def step(self, num_samples: Optional[int] = None):
        now = _now_us()
        if self._last_step_ts is not None:
            self._step_times.append((now - self._last_step_ts) / 1000.0)
        self._last_step_ts = now
        prev = self._state
        if prev in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._collect()
        self._step += 1
        self._state = self._scheduler(self._step)
        if prev == ProfilerState.RECORD_AND_RETURN or (
                prev == ProfilerState.RECORD
                and self._state not in (ProfilerState.RECORD,
                                        ProfilerState.RECORD_AND_RETURN)):
            self._finish_record()
        self._apply_state()

    def _apply_state(self):
        recording = self._state in (ProfilerState.RECORD,
                                    ProfilerState.RECORD_AND_RETURN)
        if recording and not _tracer.enabled:
            with _tracer.lock:
                _tracer.events = []
            _tracer.enabled = True
            if not self.timer_only and (
                    ProfilerTarget.TPU in self.targets
                    or ProfilerTarget.GPU in self.targets):
                self._start_device_trace()
        elif not recording and _tracer.enabled:
            _tracer.enabled = False

    def _start_device_trace(self):
        if self._jax_trace_dir is not None:
            return
        import tempfile

        import jax
        self._jax_trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_trace_")
        try:
            jax.profiler.start_trace(self._jax_trace_dir)
        except Exception:
            self._jax_trace_dir = None

    def _collect(self):
        with _tracer.lock:
            collected = _tracer.events
            _tracer.events = []
        self._events.extend(collected)

    def _finish_record(self):
        if self._jax_trace_dir is not None:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_trace_dir = None
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- results --------------------------------------------------------------
    def export(self, path: str, format: str = "json"):
        with open(path, "w") as f:
            json.dump(_chrome_payload(self._events), f)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms") -> str:
        """Render the per-name table, sorted per ``sorted_by`` (a
        ``SortedKeys``; GPU* keys alias their CPU counterparts — host spans
        are the only timed events here). Returns the rendered table."""
        by_name = {}
        for e in self._events:
            d = by_name.setdefault(e["name"], {"calls": 0, "total_us": 0.0,
                                               "max_us": 0.0,
                                               "min_us": float("inf")})
            d["calls"] += 1
            d["total_us"] += e["dur"]
            d["max_us"] = max(d["max_us"], e["dur"])
            d["min_us"] = min(d["min_us"], e["dur"])
        sort_key = {
            SortedKeys.CPUTotal: lambda d: d["total_us"],
            SortedKeys.GPUTotal: lambda d: d["total_us"],
            SortedKeys.CPUAvg: lambda d: d["total_us"] / max(d["calls"], 1),
            SortedKeys.GPUAvg: lambda d: d["total_us"] / max(d["calls"], 1),
            SortedKeys.CPUMax: lambda d: d["max_us"],
            SortedKeys.GPUMax: lambda d: d["max_us"],
            SortedKeys.CPUMin: lambda d: d["min_us"],
            SortedKeys.GPUMin: lambda d: d["min_us"],
        }.get(sorted_by, lambda d: d["total_us"])
        rows = sorted(by_name.items(), key=lambda kv: -sort_key(kv[1]))
        div, unit = {"s": (1e6, "s"), "ms": (1e3, "ms"),
                     "us": (1.0, "us")}.get(time_unit, (1e3, "ms"))
        lines = [f"{'name':<40} {'calls':>8} {f'total({unit})':>14} "
                 f"{f'avg({unit})':>12} {f'max({unit})':>12} "
                 f"{f'min({unit})':>12}"]
        for name, d in rows[:50]:
            lines.append(
                f"{name:<40} {d['calls']:>8} {d['total_us'] / div:>14.3f} "
                f"{d['total_us'] / max(d['calls'], 1) / div:>12.3f} "
                f"{d['max_us'] / div:>12.3f} {d['min_us'] / div:>12.3f}")
        text = "\n".join(lines)
        print(text)
        return text

    def step_info(self, unit=None) -> str:
        if not self._step_times:
            return "no steps recorded"
        import numpy as np
        div, u = {"s": (1e3, "s"), "ms": (1.0, "ms"),
                  "us": (1e-3, "us")}.get(unit or "ms", (1.0, "ms"))
        arr = np.asarray(self._step_times) / div
        return (f"steps: {len(arr)}, avg: {arr.mean():.3f} {u}, "
                f"p50: {np.percentile(arr, 50):.3f} {u}, "
                f"p99: {np.percentile(arr, 99):.3f} {u}")


def host_tracing_enabled() -> bool:
    return _tracer.enabled


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)


class SortedKeys(Enum):
    """Parity: paddle.profiler.SortedKeys — summary table sort orders."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(Enum):
    """Parity: paddle.profiler.SummaryView."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def _pb_varint(v: int) -> bytes:
    out = b""
    v &= (1 << 64) - 1
    while True:
        b7 = v & 0x7F
        v >>= 7
        if v:
            out += bytes([b7 | 0x80])
        else:
            return out + bytes([b7])


def _pb_field(num: int, wire: int, payload: bytes) -> bytes:
    return _pb_varint((num << 3) | wire) + payload


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    """Parity: paddle.profiler.export_protobuf — on_trace_ready callback
    serializing the trace in protobuf wire format:

      message Event { string name=1; uint64 start_us=2; uint64 end_us=3;
                      string cat=4; uint32 pid=5; uint32 tid=6; }
      message Trace { repeated Event events=1; }
    """
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_{prof._export_seq}.pb")
        prof._export_seq += 1
        blob = b""
        for e in prof._events:
            nm = str(e.get("name", "")).encode()
            ev = _pb_field(1, 2, _pb_varint(len(nm)) + nm)
            start = int(e.get("ts", 0))
            dur = int(e.get("dur", 0))
            ev += _pb_field(2, 0, _pb_varint(start))
            ev += _pb_field(3, 0, _pb_varint(start + dur))
            cat = str(e.get("cat", e.get("ph", ""))).encode()
            ev += _pb_field(4, 2, _pb_varint(len(cat)) + cat)
            ev += _pb_field(5, 0, _pb_varint(int(e.get("pid", 0))))
            ev += _pb_field(6, 0, _pb_varint(int(e.get("tid", 0))))
            blob += _pb_field(1, 2, _pb_varint(len(ev)) + ev)
        with open(path, "wb") as f:
            f.write(blob)
        prof.last_export_path = path
    return handler
