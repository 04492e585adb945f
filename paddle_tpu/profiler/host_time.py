"""Where a host thread's time went, counted by the program itself.

Two process-wide hooks, installed once at import, keep running sums: a
``gc.callbacks`` hook (Python's collections: nanoseconds and count) and a
``jax.monitoring`` listener on ``COMPILE_EVENT`` (compiles and compile-cache
loads: that event times ``compile_or_get_cached``, so a cache load's own
``/jax/compilation_cache/cache_retrieval_time_sec`` lies inside it and is not
added a second time). The gc hook also puts each collection on the trace's
clock, as a span ``<realm>.gc`` with its ``generation`` and ``collected``:
``serve`` inside ``ServingEngine.step``, ``train`` inside
``SpmdTrainer.train_step`` and ``block`` (``Realm``), ``host`` elsewhere.

``Interval`` reads, from its start to ``read()``, the thread's wall clock,
those sums, the thread's time waiting on the device and the CPU time it
spent there (inside ``DeviceWait``), and ``getrusage(RUSAGE_THREAD)``: the
thread's CPU time and, where the OS counts them, its involuntary switches
and major faults. ``HostLog`` keeps running sums of the readings and the
slowest of the last ones. A step costs five reads of the wall clock and
four ``getrusage`` calls, two of them around its device wait; no switch.
"""
from __future__ import annotations

import gc
import heapq
import resource
import threading
import time
from collections import deque

from jax import monitoring
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# [gc ns, collections, compile ns, compiles], process-wide: a collection
# or a compile stalls every thread's step, whichever thread runs it
_sums = [0, 0, 0, 0]
_compiles_lock = threading.Lock()   # compiles may end on several threads
_gc_open = [None, 0]          # the collection running: its span, its start
_local = threading.local()    # realm, device wait (ns)
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)


class Realm:
    """Names the collections that run on this thread inside it
    (``<prefix>.gc``)."""

    __slots__ = ("prefix", "_prev")

    def __init__(self, prefix: str):
        self.prefix = prefix

    def __enter__(self):
        self._prev = getattr(_local, "realm", "host")
        _local.realm = self.prefix
        return self

    def __exit__(self, *exc):
        _local.realm = self._prev
        return False


def _on_gc(phase, info):
    if phase == "start":
        span = TraceAnnotation(getattr(_local, "realm", "host") + ".gc",
                               generation=info["generation"])
        span.__enter__()
        _gc_open[0], _gc_open[1] = span, time.monotonic_ns()
        return
    span, _gc_open[0] = _gc_open[0], None
    if span is None:
        return
    _sums[0] += time.monotonic_ns() - _gc_open[1]
    _sums[1] += 1
    span.set_metadata(collected=info["collected"])
    span.__exit__(None, None, None)


def _on_event(event, duration_s, **_):
    if event == COMPILE_EVENT:
        with _compiles_lock:
            _sums[2] += int(duration_s * 1e9)
            _sums[3] += 1


gc.callbacks.append(_on_gc)
monitoring.register_event_duration_secs_listener(_on_event)


def _thread():
    """This thread's CPU time (us), involuntary switches and major faults
    (None where the OS has no per-thread ``getrusage``: the CPU clock)."""
    if _RUSAGE_THREAD is None:
        return time.thread_time_ns() // 1000, None, None
    r = resource.getrusage(_RUSAGE_THREAD)
    return int((r.ru_utime + r.ru_stime) * 1e6), r.ru_nivcsw, r.ru_majflt


def _waits():
    return getattr(_local, "wait_ns", 0), getattr(_local, "wait_cpu_us", 0)


class DeviceWait:
    """Around a wait on the device: its time, and the CPU time the thread
    spent in it (a wait that spins), count as this thread's device wait."""

    __slots__ = ("_t0", "_cpu0")

    def __enter__(self):
        self._t0, self._cpu0 = time.monotonic_ns(), _thread()[0]
        return self

    def __exit__(self, *exc):
        wait, cpu = _waits()
        _local.wait_ns = wait + time.monotonic_ns() - self._t0
        _local.wait_cpu_us = cpu + _thread()[0] - self._cpu0
        return False


class Interval:
    """One stretch of this thread, from construction to ``read()``; a
    stretch that starts by taking a lock calls ``locked()`` once it holds
    it."""

    __slots__ = ("t0", "lock_ns", "_sums0", "_waits0", "_thread0")

    def __init__(self):
        self.t0 = time.monotonic_ns()
        self._sums0 = tuple(_sums)
        self._waits0 = _waits()
        self._thread0 = _thread()
        self.lock_ns = 0

    def locked(self) -> None:
        """The lock is held: the stretch so far was the wait for it."""
        self.lock_ns = time.monotonic_ns() - self.t0

    def read(self) -> dict:
        """In microseconds: ``host_wall_us`` the whole stretch,
        ``host_sync_us`` the device waits in it, ``host_cpu_us`` the
        thread's CPU time, ``host_offcpu_us`` the rest (neither running nor
        waiting on the device: descheduled, faulting or blocked; the CPU
        time of a device wait that spins is counted in the wait only),
        ``host_lock_us``, ``gc_us`` / ``gc_collections`` and ``compile_us``
        / ``compiles`` that ran meanwhile, process-wide; ``invol_switches``
        and ``major_faults`` of the thread where the OS counts them.
        Where the OS counts a thread's CPU time in ticks (the TPU host's:
        10 ms) one stretch's CPU time is 0 or a tick and ``host_offcpu_us``
        may read below zero: it is not clipped, so that its sum over many
        stretches, which is what a metric reads, is not biased upward."""
        wall = time.monotonic_ns() - self.t0
        wait, wait_cpu = (a - b for a, b in zip(_waits(), self._waits0))
        now = _thread()
        cpu = now[0] - self._thread0[0]
        sums = [a - b for a, b in zip(_sums, self._sums0)]
        got = {"host_wall_us": wall // 1000, "host_sync_us": wait // 1000,
               "host_cpu_us": cpu,
               "host_offcpu_us": (wall - wait) // 1000 - cpu + wait_cpu,
               "host_lock_us": self.lock_ns // 1000,
               "gc_us": sums[0] // 1000, "gc_collections": sums[1],
               "compile_us": sums[2] // 1000, "compiles": sums[3]}
        if now[1] is not None:
            got["invol_switches"] = now[1] - self._thread0[1]
            got["major_faults"] = now[2] - self._thread0[2]
        return got


class HostLog:
    """Running sums of every reading's numbers, and the ``slowest`` of the
    last ``keep`` readings by ``host_wall_us``, each with its step. The
    slowest are kept as readings come and go (a snapshot is taken every
    step where a caller polls ``telemetry()``); the window is searched
    again only when one of them leaves it."""

    def __init__(self, keep: int = 1024, slowest: int = 8):
        self.steps = 0
        self.sums: dict = {}
        self._last = deque(maxlen=keep)
        self._slowest = slowest
        self._top: list = []          # (wall, step, reading), slowest first

    def add(self, step: int, reading: dict) -> None:
        self.steps += 1
        for k, v in reading.items():
            self.sums[k] = self.sums.get(k, 0) + v
        last, top = self._last, self._top
        gone = last[0] if len(last) == last.maxlen else None
        entry = (reading["host_wall_us"], step, reading)
        last.append(entry)
        # of equal walls the newer is kept: it stays in the window longer
        if any(e is gone for e in top):
            self._top = heapq.nlargest(self._slowest, last,
                                       key=lambda e: e[:2])
        elif len(top) < self._slowest or entry[:2] > top[-1][:2]:
            top.append(entry)
            top.sort(key=lambda e: e[:2], reverse=True)
            del top[self._slowest:]

    def snapshot(self) -> dict:
        return {"steps": self.steps, "sums": dict(self.sums),
                "slowest": [dict(r, step=step) for _, step, r in self._top]}
