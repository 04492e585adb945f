"""What every kind of run shares around its measured window: the clock,
the count of compilations, the device's memory, the profiler's slice.
"""
from __future__ import annotations

import shutil
import time

import jax

clock = time.perf_counter


class CompileCounter:
    """Counts JAX compilations (and traces) while ``armed``: nothing may
    compile inside a measured window."""

    def __init__(self):
        self.armed = False
        self.seen = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_, **__):
        if self.armed and name.startswith("/jax/core/compile"):
            self.seen.append(name)


def memory_peak_bytes():
    """The peak on the fullest chip, as the backend reports it (the CPU
    backend of the tests reports none: 0)."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(stats):
        return 0
    return max(int(s["peak_bytes_in_use"]) for s in stats)


# the profiler's JSON export keeps the earliest 1,000,000 events of a trace
SLICE_EVENTS = 900_000        # what a slice may make, so that none is dropped


def start_profiler(directory: str):
    """The profiler with its Python tracer off: the harness reads no Python
    frame, and they were six tenths of a decode step's events. The program's
    and the benchmark's spans are the host tracer's (``TraceAnnotation``) and
    stay."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)


class TraceSlice:
    """The profiler over a slice of the window: started at a step boundary
    once six tenths of the window have passed, stopped at the first boundary
    at which 8 seconds (a quarter of a short window) have passed or
    ``max_steps`` steps have run, whichever comes first. ``max_steps`` keeps
    the slice under the events the export keeps; ``calibrate`` measures it.
    Off (never starts) unless ``on``. Starting and stopping the profiler stall
    the host for seconds, so a traced run takes its host-clock numbers from
    before ``quiet_end``."""

    def __init__(self, on: bool, directory: str, seconds: float):
        self.on, self.dir = on, directory
        self.start_at, self.length = 0.6 * seconds, min(8.0, 0.25 * seconds)
        self.max_steps = self.events_a_step = None    # no limit by steps
        self.quiet = self.t0 = self.t1 = None   # clock readings
        self.first_step = self.last_step = None

    def calibrate(self, step, steps: int = 3):
        """In set-up, in a traced run: trace ``steps`` calls of ``step`` into
        a directory beside the slice's, count the events a step left in the
        export, and set ``max_steps`` to what fits ``SLICE_EVENTS``. It also
        pays the profiler's first start, the slowest, before the window."""
        if not self.on:
            return
        from . import trace as T
        scratch = self.dir + ".calibration"
        shutil.rmtree(scratch, ignore_errors=True)
        start_profiler(scratch)
        try:
            for _ in range(steps):
                step()
        finally:
            jax.profiler.stop_trace()
        path = T.find(scratch)
        events = T.count_events(path) if path else 0
        shutil.rmtree(scratch, ignore_errors=True)
        if events:
            self.events_a_step = events / steps
            self.max_steps = max(1, int(SLICE_EVENTS / self.events_a_step))

    def boundary(self, elapsed: float, step_index: int):
        """Call between steps with the device idle."""
        if not self.on:
            return
        if self.t0 is None and elapsed >= self.start_at:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.quiet = clock()
            start_profiler(self.dir)
            self.t0, self.first_step = clock(), step_index
        elif self.t0 is not None and self.t1 is None and (
                clock() - self.t0 >= self.length
                or (self.max_steps is not None
                    and step_index - self.first_step >= self.max_steps)):
            self.stop(step_index)

    def stop(self, step_index: int):
        if self.on and self.t0 is not None and self.t1 is None:
            self.t1 = clock()
            jax.profiler.stop_trace()
            self.last_step = step_index

    def quiet_end(self, window_end: float) -> float:
        """The clock reading up to which the profiler disturbed nothing."""
        return window_end if self.quiet is None else self.quiet
