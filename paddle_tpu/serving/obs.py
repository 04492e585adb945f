"""Serving observability plane: lifecycle traces, SLO telemetry, flight recorder.

PR 1 gave training a full observability plane; this module is the
serving tier's equivalent, built from three layers that share one
``ServingObserver`` object wired through the engine and scheduler:

  * **Per-request lifecycle tracing** — every submitted request carries a
    ``RequestTrace``: timestamped events from submit through admission,
    each prefill chunk, first token, decode/spec-verify steps, preemption
    and exactly ONE terminal ``finish`` event. Traces export as
    chrome-trace JSON (one track per request; spans for queue-wait /
    prefill / decode) carrying the same ``paddle_tpu.clock_anchor``
    instant event the training profiler emits, so
    ``tools/trace_merge.py`` lines serving traces up with multi-rank
    training traces on the shared wall clock.

  * **Flight recorder** — a bounded ring of the last N step-plan records
    (the scheduler's structured explanation of every engine step: budget
    split, who was admitted/evicted/preempted and why, pool occupancy,
    prefix-hit deltas, spec outcome) plus the last M completed request
    lifecycles. Anomaly triggers — driver stall, pool exhaustion, chaos
    fault, SLO deadline blow — each dump the ring to JSON exactly once
    (latched per reason; armed-but-quiet runs dump nothing), and
    ``ServingEngine.dump_flight_record()`` dumps on demand. The dump
    path itself is a chaos site (``serve.flight_dump``) and NEVER
    raises: a postmortem that crashes the patient is worse than none.

  * **SLO / goodput telemetry** — requests accept optional TTFT and
    per-output-token (TPOT) deadlines; the observer tracks streaming
    p50/p95/p99 for TTFT/TPOT/e2e through the bounded quantile sketch on
    ``profiler.metrics.Histogram`` (fixed-size log-bucket array — no
    unbounded latency lists on the hot path), counts violations,
    attainment, and goodput (tokens from requests that met their
    deadlines). ``ServingEngine.telemetry()`` returns the snapshot
    ``tools/serve_top.py`` renders live.

Gate discipline (same as PR 1): the layer is DISARMED by default — the
engine holds ``obs=None`` and every instrumented seam costs one
``is None`` check (microbench-pinned in tests). Arm per engine with
``EngineConfig(obs=True | ObsConfig(...))`` or globally with
``PADDLE_SERVE_OBS=1``; ``PADDLE_SERVE_FLIGHT=<file>`` names the flight
dump file (``tools/supervise.py`` inlines it into crash reports) and
also arms, ``PADDLE_SERVE_TELEMETRY=<file>`` streams periodic telemetry
snapshots for ``serve_top --watch``.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..profiler import instrument as _instr
from ..profiler import metrics as _metrics
from ..resilience import chaos
from . import wire as _wire
from .locking import OrderedLock

logger = logging.getLogger(__name__)

ENV_OBS = "PADDLE_SERVE_OBS"
ENV_FLIGHT = "PADDLE_SERVE_FLIGHT"
ENV_TELEMETRY = "PADDLE_SERVE_TELEMETRY"

#: the one terminal lifecycle event kind — every submitted request's
#: trace ends with exactly one of these (test-pinned), whatever path
#: (eos, max_new_tokens, eviction after preemption) got it there.
TERMINAL_EVENT = "finish"

_QUANTS = (("p50", 0.50), ("p95", 0.95), ("p99", 0.99))
_TRUTHY = ("1", "true", "on", "yes")


def _atomic_json(path: str, payload, indent: Optional[int] = None) -> None:
    """tmp-write + rename so readers (serve_top, supervise) never see a
    torn file; the orphaned tmp is removed if the dump itself fails."""
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=indent)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


class ObsConfig:
    """Knobs for one engine's observability plane.

    flight_steps / flight_requests bound the flight-recorder rings;
    stall_threshold_s is the driver-stall watchdog (a single engine step
    exceeding it triggers a flight dump); dump_path / telemetry_path
    default to the PADDLE_SERVE_FLIGHT / PADDLE_SERVE_TELEMETRY envs;
    max_events_per_request caps a single lifecycle trace (the terminal
    event always lands, drops are counted)."""

    def __init__(self, flight_steps: int = 128, flight_requests: int = 64,
                 stall_threshold_s: float = 60.0,
                 dump_path: Optional[str] = None,
                 telemetry_path: Optional[str] = None,
                 telemetry_every: int = 32,
                 max_events_per_request: int = 512):
        if flight_steps < 1 or flight_requests < 1:
            raise ValueError(
                f"flight rings need >= 1 slot (got {flight_steps}, "
                f"{flight_requests})")
        if telemetry_every < 1:
            raise ValueError(
                f"telemetry_every must be >= 1, got {telemetry_every}")
        self.flight_steps = int(flight_steps)
        self.flight_requests = int(flight_requests)
        self.stall_threshold_s = float(stall_threshold_s)
        self.dump_path = dump_path
        self.telemetry_path = telemetry_path
        self.telemetry_every = int(telemetry_every)
        self.max_events_per_request = int(max_events_per_request)


class RequestTrace:
    """One request's timestamped lifecycle. Bounded: past the cap only
    the terminal event is still appended; drops are counted so a
    truncated trace is visibly truncated, never silently complete."""

    __slots__ = ("rid", "events", "dropped", "_cap")

    def __init__(self, rid: int, cap: int):
        self.rid = rid
        self.events: List[Dict[str, Any]] = []
        self.dropped = 0
        self._cap = cap

    def add(self, kind: str, t: float, **data) -> None:
        if len(self.events) >= self._cap and kind != TERMINAL_EVENT:
            self.dropped += 1
            return
        ev = {"t_s": t, "kind": kind}
        if data:
            ev.update(data)
        self.events.append(ev)

    def terminal_events(self) -> List[Dict[str, Any]]:
        return [e for e in self.events if e["kind"] == TERMINAL_EVENT]

    def to_dict(self) -> Dict[str, Any]:
        return {"rid": self.rid, "events": list(self.events),
                "dropped_events": self.dropped}


class ServingObserver:
    """The armed observability plane for one ServingEngine.

    All hooks are called by the engine/scheduler under the engine lock;
    the observer's own RLock additionally protects against concurrent
    ``telemetry()`` / ``dump()`` / ``export_chrome_trace()`` readers on
    other threads (lock order is always engine -> observer, never the
    reverse, so the pairing cannot deadlock)."""

    def __init__(self, config: Optional[ObsConfig] = None):
        cfg = config or ObsConfig()
        self.config = cfg
        self.armed = True
        # reentrant; PADDLE_LOCKCHECK=1 arms LOCK_ORDER enforcement
        self._lock = OrderedLock("observer")
        # one (monotonic, wall) instant pair: every exported/unix
        # timestamp derives from it, so functions on the chaos-probed
        # dump path never read the wall clock directly
        self._anchor_mono = time.monotonic()
        self._anchor_wall = time.time()
        self._pid = os.getpid()
        self._steps: "deque[dict]" = deque(maxlen=cfg.flight_steps)
        self._done: "deque[dict]" = deque(maxlen=cfg.flight_requests)
        self._live: Dict[int, Any] = {}          # rid -> Request
        self.counters = {"submitted": 0, "admitted": 0, "finished": 0,
                         "preempted": 0, "requeued": 0, "failed": 0,
                         "shed": 0, "handoff_out": 0, "handoff_in": 0}
        # bounded quantile sketches (private Histogram instances — the
        # registry-facing gauges are updated through instrument.record_*)
        self._lat = {
            "ttft": _metrics.Histogram("serve_ttft_sketch",
                                       track_quantiles=True),
            "tpot": _metrics.Histogram("serve_tpot_sketch",
                                       track_quantiles=True),
            "e2e": _metrics.Histogram("serve_e2e_sketch",
                                      track_quantiles=True),
        }
        self.slo = {"tracked": 0, "met": 0,
                    "violations": {"ttft": 0, "tpot": 0},
                    "goodput_tokens": 0, "total_tokens": 0}
        self._pending: List[tuple] = []          # (reason, detail)
        self._latched: set = set()               # auto-dumped reasons
        self.dumps: List[Dict[str, Any]] = []
        self.dump_failures = 0
        self.dump_path = cfg.dump_path if cfg.dump_path is not None \
            else (os.environ.get(ENV_FLIGHT, "").strip() or None)
        self.telemetry_path = cfg.telemetry_path \
            if cfg.telemetry_path is not None \
            else (os.environ.get(ENV_TELEMETRY, "").strip() or None)

    # -- clock ----------------------------------------------------------------
    def _wall(self, mono: float) -> float:
        """Wall-clock instant for a monotonic timestamp (derived from the
        construction-time anchor: monotonic by construction, so the
        chaos-probed dump path never reads a jumpable clock)."""
        return self._anchor_wall + (mono - self._anchor_mono)

    # -- lifecycle hooks (engine/scheduler side, under the engine lock) -------
    def on_submit(self, req) -> None:
        if not self.armed:
            return
        now = time.monotonic()
        with self._lock:
            self.counters["submitted"] += 1
            tr = RequestTrace(req.rid, self.config.max_events_per_request)
            req.trace = tr
            tr.add("submit", now, prompt_tokens=len(req.prompt),
                   max_new_tokens=req.max_new_tokens,
                   ttft_deadline_s=req.ttft_deadline,
                   tpot_deadline_s=req.tpot_deadline)
            self._live[req.rid] = req

    def on_admit(self, req, chunk: int, prefix_tokens: int) -> None:
        if not self.armed or req.trace is None:
            return
        with self._lock:
            self.counters["admitted"] += 1
            req.trace.add("admit", time.monotonic(), slot=req.slot,
                          chunk=chunk, prefix_tokens=prefix_tokens)

    def on_prefill(self, req, start: int, n: int) -> None:
        if not self.armed or req.trace is None:
            return
        with self._lock:
            req.trace.add("prefill", time.monotonic(), start=start, n=n)

    def on_first_token(self, req, ttft: float) -> None:
        if not self.armed:
            return
        with self._lock:
            self._lat["ttft"].observe(ttft)
            ok = req.ttft_deadline is None or ttft <= req.ttft_deadline
            if req.trace is not None:
                req.trace.add("first_token", time.monotonic(),
                              ttft_s=round(ttft, 6), slo_ok=ok)
            if not ok:
                self.slo["violations"]["ttft"] += 1
                _instr.record_serve_slo_violation("ttft")
                self.note_anomaly("slo_blow", {
                    "rid": req.rid, "kind": "ttft",
                    "ttft_s": round(ttft, 6),
                    "deadline_s": req.ttft_deadline})

    def on_decode(self, req, emitted: int, drafted: int,
                  accepted: int) -> None:
        if not self.armed or req.trace is None:
            return
        with self._lock:
            kind = "spec_verify" if drafted else "decode"
            data = {"emitted": emitted}
            if drafted:
                data["drafted"] = drafted
                data["accepted"] = accepted
            req.trace.add(kind, time.monotonic(), **data)

    def on_preempt(self, req, to_grow: Optional[int] = None) -> None:
        if not self.armed:
            return
        with self._lock:
            self.counters["preempted"] += 1
            if req.trace is not None:
                req.trace.add("preempt", time.monotonic(),
                              reason="pool_pressure", to_grow=to_grow,
                              generated=len(req.output))

    def on_requeue(self, req, reason: str) -> None:
        """A contained step fault kicked the request back to the waiting
        queue for recompute (serving/resilience.py). NOT terminal — the
        request's one finish event still comes later, from wherever it
        actually ends (completion or terminal failure)."""
        if not self.armed:
            return
        with self._lock:
            self.counters["requeued"] += 1
            if req.trace is not None:
                req.trace.add("step_fault_requeue", time.monotonic(),
                              reason=reason, retries=req.step_retries,
                              generated=len(req.output))

    def on_handoff_out(self, req, pages: int, n_tokens: int) -> None:
        """Prefill complete, KV pages exported to the decode pool: the
        ``kv_handoff`` lifecycle event — it sits between the prefill
        chunks and the first_token the DECODE replica will record onto
        the same trace (the trace object rides with the request across
        the pool boundary). NOT terminal: the one finish event lands on
        the receiving observer. The request leaves this observer's live
        set — it is no longer this engine's to account."""
        if not self.armed:
            return
        with self._lock:
            self.counters["handoff_out"] += 1
            if req.trace is not None:
                req.trace.add("kv_handoff", time.monotonic(),
                              pages=pages, tokens=n_tokens)
            self._live.pop(req.rid, None)

    def on_handoff_in(self, req, outcome: str = "pages") -> None:
        """A handed-off request landed on this (decode-pool) engine —
        ``outcome`` says how: "pages" (KV import, no recompute) or
        "recompute" (fallback: pages were unobtainable or the prefill
        replica died mid-handoff; the prompt re-prefills here). The
        request joins this observer's live set; its eventual finish /
        fail records the trace's single terminal event here."""
        if not self.armed:
            return
        with self._lock:
            self.counters["handoff_in"] += 1
            self._live[req.rid] = req
            if req.trace is not None:
                req.trace.add("handoff_admit", time.monotonic(),
                              outcome=outcome)

    def on_fail(self, req, reason: str) -> None:
        """Terminal failure/shed: exactly ONE finish event with the
        failure reason, same lifecycle bookkeeping as a clean finish —
        but never counted toward SLO attainment or goodput (a shed or
        failed request produced no deliverable result; its tokens are
        not goodput)."""
        if not self.armed:
            return
        now = time.monotonic()
        with self._lock:
            self.counters["shed" if reason == "shed" else "failed"] += 1
            if req.trace is not None:
                req.trace.add(TERMINAL_EVENT, now, reason=reason,
                              output_tokens=len(req.output), slo_ok=False)
                life = req.trace.to_dict()
                life.update({
                    "prompt_tokens": len(req.prompt),
                    "output_tokens": len(req.output),
                    "prefix_tokens": req.n_prefix,
                    "preemptions": req.preemptions,
                    "reason": reason,
                    "e2e_s": round(now - req.arrival, 6),
                    "error": repr(req.error) if req.error is not None
                    else None,
                })
                self._done.append(life)
            self._live.pop(req.rid, None)

    def on_finish(self, req, reason: str) -> None:
        if not self.armed:
            return
        now = time.monotonic()
        with self._lock:
            self.counters["finished"] += 1
            e2e = now - req.arrival
            self._lat["e2e"].observe(e2e)
            tpot = None
            if req.first_token_at is not None and len(req.output) > 1:
                tpot = (now - req.first_token_at) / (len(req.output) - 1)
                self._lat["tpot"].observe(tpot)
            ttft = (req.first_token_at - req.arrival
                    if req.first_token_at is not None else None)
            ttft_ok = (req.ttft_deadline is None or ttft is None
                       or ttft <= req.ttft_deadline)
            tpot_ok = (req.tpot_deadline is None or tpot is None
                       or tpot <= req.tpot_deadline)
            if not tpot_ok:
                self.slo["violations"]["tpot"] += 1
                _instr.record_serve_slo_violation("tpot")
                self.note_anomaly("slo_blow", {
                    "rid": req.rid, "kind": "tpot",
                    "tpot_s": round(tpot, 6),
                    "deadline_s": req.tpot_deadline})
            tracked = (req.ttft_deadline is not None
                       or req.tpot_deadline is not None)
            ok = ttft_ok and tpot_ok
            if tracked:
                self.slo["tracked"] += 1
                if ok:
                    self.slo["met"] += 1
            self.slo["total_tokens"] += len(req.output)
            if ok:
                self.slo["goodput_tokens"] += len(req.output)
            _instr.record_serve_goodput(len(req.output) if ok else 0)
            _instr.record_serve_slo_attainment(self._attainment())
            for kind, h in self._lat.items():
                if h.count:
                    _instr.record_serve_quantiles(
                        kind, *(h.quantile(q) for _, q in _QUANTS))
            if req.trace is not None:
                req.trace.add(TERMINAL_EVENT, now, reason=reason,
                              output_tokens=len(req.output), slo_ok=ok)
                life = req.trace.to_dict()
                life.update({
                    "prompt_tokens": len(req.prompt),
                    "output_tokens": len(req.output),
                    "prefix_tokens": req.n_prefix,
                    "preemptions": req.preemptions,
                    "reason": reason,
                    "ttft_s": round(ttft, 6) if ttft is not None else None,
                    "tpot_s": round(tpot, 6) if tpot is not None else None,
                    "e2e_s": round(e2e, 6),
                    "slo": {"tracked": tracked, "ok": ok,
                            "ttft_ok": ttft_ok, "tpot_ok": tpot_ok},
                })
                self._done.append(life)
            self._live.pop(req.rid, None)

    # -- anomaly triggers / flight recorder -----------------------------------
    def note_anomaly(self, reason: str, detail: Optional[dict] = None
                     ) -> None:
        """Mark an anomaly; the dump happens at the END of the current
        engine step (after its plan record landed in the ring) so the
        dump's last step record is the one that explains the anomaly.
        Deduplicated per reason within a step; auto-dumps latch per
        reason for the observer's lifetime (one anomaly class = one
        postmortem, not a dump storm)."""
        if not self.armed:
            return
        with self._lock:
            if reason in self._latched or \
                    any(r == reason for r, _ in self._pending):
                return
            self._pending.append((reason, detail))

    def record_step(self, rec: Dict[str, Any],
                    host: Optional[Dict[str, Any]] = None) -> None:
        """Append one engine step's plan record to the flight ring, run
        the stall watchdog, and flush any pending anomaly into a dump.
        ``host`` is where the step's host time went (``profiler.host_time``
        ``Interval.read()``): the record's step clock (``dt_s``) and its
        breakdown, so a stall dump says what the thread was doing."""
        if not self.armed:
            return
        if host is not None:
            rec["dt_s"], rec["host"] = host["host_wall_us"] / 1e6, host
        with self._lock:
            self._steps.append(rec)
            if rec.get("dt_s", 0.0) > self.config.stall_threshold_s:
                self.note_anomaly("stall", {
                    "step": rec.get("step"), "dt_s": rec.get("dt_s"),
                    "threshold_s": self.config.stall_threshold_s})
            pending, self._pending = self._pending, []
            for reason, detail in pending:
                if reason in self._latched:
                    continue
                self._latched.add(reason)
                self.dump(reason=reason, detail=detail)

    def has_pending(self) -> bool:
        """Anomalies noted but not yet flushed into a dump (the engine
        checks this so an EMPTY step plan still lands its record and
        flushes — a wedged engine must not postpone its postmortem)."""
        with self._lock:
            return bool(self._pending)

    def reset_triggers(self) -> None:
        """Re-arm latched auto-dump reasons (tests / long-lived engines
        that rotated their dump file)."""
        with self._lock:
            self._latched.clear()

    def dump(self, reason: str = "manual", detail: Optional[dict] = None,
             path: Optional[str] = None) -> Optional[Dict[str, Any]]:
        """Dump the flight record; returns the record dict, or None on
        failure. NEVER raises — a dump triggered by a fault must not
        become a second fault (the ``serve.flight_dump`` chaos site
        drills exactly that)."""
        try:
            chaos.site("serve.flight_dump")
            with self._lock:
                rec = self._flight_record(reason, detail)
                target = path if path is not None else self.dump_path
                if target:
                    _atomic_json(target, rec, indent=1)
                self.dumps.append({"reason": reason,
                                   "unix_time": rec["unix_time"],
                                   "path": target or None})
            _instr.record_serve_flight_dump(reason)
            logger.info("serve.obs: flight dump (%s)%s", reason,
                        f" -> {target}" if target else "")
            return rec
        except Exception:  # noqa: BLE001 — dump-on-fault must not raise
            with self._lock:
                self.dump_failures += 1
            logger.warning("serve.obs: flight dump failed (reason=%s)",
                           reason, exc_info=True)
            return None

    def _flight_record(self, reason: str, detail: Optional[dict]
                       ) -> Dict[str, Any]:
        live = []
        for req in self._live.values():
            entry = {"rid": req.rid, "state": req.state, "pos": req.pos,
                     "output_tokens": len(req.output),
                     "preemptions": req.preemptions}
            if req.trace is not None:
                entry["events"] = list(req.trace.events[-32:])
            live.append(entry)
        return _wire.seal({
            "version": 1,
            "reason": reason,
            "detail": detail,
            "unix_time": self._wall(time.monotonic()),
            "ring": {"flight_steps": self.config.flight_steps,
                     "flight_requests": self.config.flight_requests},
            "steps": list(self._steps),
            "requests": list(self._done),
            "live_requests": live,
            "telemetry": self._telemetry_locked({}),
        }, "flight_dump")

    # -- telemetry ------------------------------------------------------------
    def _attainment(self) -> float:
        t = self.slo["tracked"]
        return self.slo["met"] / t if t else 1.0

    def _telemetry_locked(self, base: Dict[str, Any]) -> Dict[str, Any]:
        lat = {}
        for kind, h in self._lat.items():
            lat[kind] = {"count": h.count, "mean": round(h.mean, 6)}
            for name, q in _QUANTS:
                lat[kind][name] = round(h.quantile(q), 6) if h.count \
                    else 0.0
        lat["quantile_rel_error"] = _metrics.QUANTILE_RELATIVE_ERROR
        goodput = self.slo["goodput_tokens"]
        total = self.slo["total_tokens"]
        base.update({
            "unix_time": self._wall(time.monotonic()),
            "requests": dict(self.counters,
                             live=len(self._live)),
            "slo": {
                "tracked": self.slo["tracked"],
                "met": self.slo["met"],
                "violations": dict(self.slo["violations"]),
                "attainment": round(self._attainment(), 6),
                "goodput_tokens": goodput,
                "total_tokens": total,
                "goodput_fraction": round(goodput / total, 6)
                if total else 1.0,
            },
            "latency": lat,
            "flight": {"buffered_steps": len(self._steps),
                       "buffered_requests": len(self._done),
                       "dumps": list(self.dumps),
                       "dump_failures": self.dump_failures},
        })
        return base

    def telemetry(self, base: Optional[Dict[str, Any]] = None
                  ) -> Dict[str, Any]:
        """Merge the observer's snapshot into ``base`` (the engine's own
        counters) and return it."""
        with self._lock:
            return self._telemetry_locked(dict(base) if base else {})

    def write_telemetry(self, tel: Dict[str, Any],
                        path: Optional[str] = None) -> bool:
        """Atomically write a telemetry snapshot (serve_top --watch reads
        it). Never raises: telemetry is advisory."""
        target = path if path is not None else self.telemetry_path
        if not target:
            return False
        try:
            _wire.seal(tel, "telemetry_line")
            _atomic_json(target, tel, indent=1)
            return True
        except _wire.WireContractViolation:
            # the one hole in the never-raise fence: an ARMED wire
            # contract violation must surface at this producing seam,
            # not be swallowed as an advisory-telemetry hiccup
            raise
        except Exception:   # noqa: BLE001 — "Never raises" is the contract
            logger.warning("serve.obs: could not write telemetry %s",
                           target, exc_info=True)
            return False

    # -- chrome-trace export --------------------------------------------------
    def export_chrome_trace(self, path: Optional[str] = None
                            ) -> Dict[str, Any]:
        """Chrome-trace payload of every buffered lifecycle: one track
        (tid) per request under one serving process (pid), spans for
        queue-wait / prefill / decode, instants for chunks, preemptions
        and finish — with the same wall-clock anchor instant the
        training profiler emits, so ``tools/trace_merge.py`` aligns
        serving and training traces on real time."""
        with self._lock:
            lifecycles = list(self._done)
            for req in self._live.values():
                if req.trace is not None:
                    lifecycles.append(req.trace.to_dict())
        pid = self._pid
        meta: List[dict] = [
            {"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": f"paddle_tpu serve {pid}"}},
        ]
        anchor = {"name": "paddle_tpu.clock_anchor", "ph": "i", "s": "g",
                  "pid": pid, "tid": 0,
                  "ts": self._anchor_mono * 1e6,
                  "args": {"unix_time_us": self._anchor_wall * 1e6,
                           "rank": "serve"}}
        events: List[dict] = []
        for life in lifecycles:
            rid = life["rid"]
            evs = life.get("events", [])
            times = {}
            for e in evs:
                times.setdefault(e["kind"], e["t_s"])  # first of each kind
            t_submit = times.get("submit")
            t_admit = times.get("admit")
            t_first = times.get("first_token")
            t_end = evs[-1]["t_s"] if evs else None
            if t_submit is None or t_end is None:
                continue
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": rid, "args": {"name": f"req {rid}"}})

            def span(name, t0, t1, **args):
                events.append({"name": name, "cat": "serving", "ph": "X",
                               "pid": pid, "tid": rid, "ts": t0 * 1e6,
                               "dur": max(t1 - t0, 0.0) * 1e6,
                               "args": args})

            span("queue_wait", t_submit, t_admit if t_admit is not None
                 else t_end, rid=rid)
            if t_admit is not None:
                span("prefill", t_admit,
                     t_first if t_first is not None else t_end, rid=rid)
            if t_first is not None:
                span("decode", t_first, t_end, rid=rid,
                     tokens=life.get("output_tokens"))
            for e in evs:
                # router_* kinds are the PR 16 fleet-plane spans — a
                # single-engine export still shows where the router
                # placed / handed off / failed over this request
                if e["kind"] in ("prefill", "preempt", "spec_verify",
                                 "router_route", "router_handoff",
                                 "router_handoff_defer",
                                 "router_failover"):
                    args = {k: v for k, v in e.items()
                            if k not in ("t_s", "kind")}
                    events.append({"name": e["kind"], "cat": "serving",
                                   "ph": "i", "s": "t", "pid": pid,
                                   "tid": rid, "ts": e["t_s"] * 1e6,
                                   "args": args})
        payload = {"traceEvents": meta + [anchor] + events,
                   "displayTimeUnit": "ms",
                   "metadata": {"source": "paddle_tpu.serving.obs"}}
        if path:
            _atomic_json(path, payload)
        return payload


def resolve_observer(spec) -> Optional[ServingObserver]:
    """Normalize ``EngineConfig.obs``: an observer passes through, an
    ObsConfig builds one, True arms the defaults, False disarms, and
    None defers to the env (PADDLE_SERVE_OBS truthy, or a
    PADDLE_SERVE_FLIGHT dump file being named, arms)."""
    if spec is None:
        if os.environ.get(ENV_OBS, "").strip().lower() in _TRUTHY or \
                os.environ.get(ENV_FLIGHT, "").strip():
            return ServingObserver()
        return None
    if spec is False:
        return None
    if spec is True:
        return ServingObserver()
    if isinstance(spec, ObsConfig):
        return ServingObserver(spec)
    if isinstance(spec, ServingObserver):
        return spec
    raise TypeError(
        f"EngineConfig.obs wants None/bool/ObsConfig/ServingObserver, "
        f"got {type(spec).__name__}")


__all__ = ["ObsConfig", "RequestTrace", "ServingObserver",
           "resolve_observer", "TERMINAL_EVENT",
           "ENV_OBS", "ENV_FLIGHT", "ENV_TELEMETRY"]
