"""Serving resilience: fault containment, graceful drain, admission control.

PR 5 made *training* preemption-tolerant; this module is the serving
tier's equivalent contract — a single engine that fails cleanly, drains
gracefully, and sheds load predictably (the per-engine failure unit the
replica router of ROADMAP item 2 composes). Three legs, all DISARMED by
default (``ServingEngine.resilience is None`` — every instrumented seam
costs one ``is None`` check, microbench-pinned like the obs plane):

  * **Step-fault containment** — the driver loop wraps ``step()`` so a
    raising step (chaos site ``serve.engine_step``, device errors, or
    NaN/garbage logits caught by the StepGuard-style finite check on the
    sampled batch) never escapes: the engine resets the KV pool/slot
    accounting to a consistent state, requeues every running request at
    the waiting front for prefix recompute (generated tokens ride along
    in ``seq`` — exactly the PR 6 preemption mechanics) with a bounded
    per-request retry budget, and past-budget requests FAIL with a clean
    terminal ``RequestFailed`` surfaced through ``result()``/``stream()``
    instead of hanging forever.

  * **Graceful drain + restart replay** — ``engine.drain(deadline_s)``
    stops admission, runs decode-only within the grace budget, then
    exports a drain manifest (prompt + generated tokens + SLO deadlines
    + submission order, atomic write). ``PreemptionGuard`` wires SIGTERM
    to the drain via ``serve_until_preempted``; ``tools/supervise.py``
    threads one SHARED manifest path across restart generations so the
    restarted engine replays it (``replay_manifest``; the AOT cache
    makes the restart cheap, the prefix cache makes recompute cheap).
    ``tools/chaos_drill.py --serve`` pins the whole
    kill→drain→restart→replay loop with greedy token-prefix consistency.

  * **Overload admission control** — the waiting queue becomes bounded
    (``max_waiting``) with pluggable backpressure (``block`` | ``reject``
    | ``shed``): rejection happens at ``submit()`` with a structured
    ``AdmissionRejected`` carrying a retry-after estimate derived from
    the engine's observed service time (PR 9 telemetry), and the
    SLO-aware ``shed`` policy refuses requests whose predicted queue
    wait already blows their ``ttft_deadline`` (goodput-protecting).

Arm per engine with ``EngineConfig(resilience=True | ResilienceConfig)``
or globally with ``PADDLE_SERVE_RESILIENCE=1``;
``PADDLE_SERVE_DRAIN_MANIFEST=<file>`` names the drain manifest (and
also arms — the env ``tools/supervise.py`` threads to serving workers).
"""
from __future__ import annotations

import json
import logging
import os
import time
from typing import List, Optional, Sequence

from ..profiler import instrument as _instr
from .obs import _atomic_json
from .wire import seal as _seal

logger = logging.getLogger(__name__)

ENV_RESILIENCE = "PADDLE_SERVE_RESILIENCE"
ENV_DRAIN_MANIFEST = "PADDLE_SERVE_DRAIN_MANIFEST"

_TRUTHY = ("1", "true", "on", "yes")

#: drain-manifest schema version (readers refuse what they don't know)
MANIFEST_VERSION = 1

_POLICIES = ("block", "reject", "shed")


class StepFault(RuntimeError):
    """An engine step produced output that cannot be trusted (NaN or
    non-finite logits caught by the sample guard). Raised INSIDE the
    step and contained by the engine when resilience is armed — it only
    escapes on a disarmed engine."""

    def __init__(self, kind: str, detail: str = ""):
        self.kind = kind
        super().__init__(f"serving step fault ({kind})"
                         + (f": {detail}" if detail else ""))


class RequestFailed(RuntimeError):
    """Terminal error of one serving request — raised by ``result()``
    and ``stream()`` of a request the engine gave up on (step-fault
    retry budget exhausted, or an explicit ``abort_all``). The request
    is cleanly evicted: pages released, slot freed, exactly one
    terminal lifecycle event recorded."""

    def __init__(self, rid: int, reason: str, retries: int = 0,
                 cause: Optional[BaseException] = None):
        self.rid = int(rid)
        self.reason = reason
        self.retries = int(retries)
        self.cause = cause
        msg = f"request {rid} failed ({reason}"
        if retries:
            msg += f" after {retries} retries"
        msg += ")"
        if cause is not None:
            msg += f": {cause!r}"
        super().__init__(msg)


class AdmissionRejected(RuntimeError):
    """``submit()`` refused a request under overload. Structured so a
    client can back off intelligently: ``reason`` is one of
    ``queue_full`` (bounded queue at capacity, policy reject),
    ``shed`` (predicted queue wait blows the request's ttft_deadline),
    ``block_timeout`` (policy block gave up waiting for room) or
    ``draining`` (the engine is shutting down); ``retry_after_s`` is the
    engine's estimate of when the queue will have room (None when it has
    no evidence yet); ``predicted_wait_s`` the queue-wait estimate that
    drove an SLO shed."""

    def __init__(self, reason: str, retry_after_s: Optional[float] = None,
                 queue_depth: int = 0,
                 predicted_wait_s: Optional[float] = None):
        self.reason = reason
        self.retry_after_s = retry_after_s
        self.queue_depth = int(queue_depth)
        self.predicted_wait_s = predicted_wait_s
        msg = f"admission rejected ({reason}, queue_depth={queue_depth}"
        if retry_after_s is not None:
            msg += f", retry_after~{retry_after_s:.3f}s"
        if predicted_wait_s is not None:
            msg += f", predicted_wait~{predicted_wait_s:.3f}s"
        super().__init__(msg + ")")


class ResilienceConfig:
    """Knobs for one engine's resilience plane.

    max_step_retries: per-REQUEST budget of contained step faults; a
    request requeued more often than this FAILS with ``RequestFailed``
    (bounded: a permanently broken engine converges to clean terminal
    errors, never a livelock). nan_guard: check the step's logits are
    finite before sampling (one fused jit reduce per step; a tripped
    guard is a ``nan_logits`` step fault). max_waiting: bound on the
    waiting queue (None = unbounded, the pre-resilience behavior).
    backpressure: what a full queue does to ``submit()`` — ``block``
    (wait for room, up to block_timeout_s), ``reject`` (raise
    ``AdmissionRejected`` with a retry-after estimate), ``shed`` (like
    reject, plus SLO-aware: refuse requests whose predicted queue wait
    already blows their ttft_deadline even when the queue has room).
    manifest_path: where ``drain()`` writes the restart-replay manifest
    (``PADDLE_SERVE_DRAIN_MANIFEST`` env twin)."""

    def __init__(self, max_step_retries: int = 2, nan_guard: bool = True,
                 max_waiting: Optional[int] = None,
                 backpressure: str = "reject",
                 block_timeout_s: Optional[float] = None,
                 manifest_path: Optional[str] = None):
        if max_step_retries < 0:
            raise ValueError(
                f"max_step_retries must be >= 0, got {max_step_retries}")
        if max_waiting is not None and max_waiting < 1:
            raise ValueError(
                f"max_waiting must be >= 1 (or None), got {max_waiting}")
        if backpressure not in _POLICIES:
            raise ValueError(
                f"unknown backpressure policy {backpressure!r} "
                f"(want one of {_POLICIES})")
        if block_timeout_s is not None and block_timeout_s < 0:
            raise ValueError(
                f"block_timeout_s must be >= 0, got {block_timeout_s}")
        self.max_step_retries = int(max_step_retries)
        self.nan_guard = bool(nan_guard)
        self.max_waiting = max_waiting if max_waiting is None \
            else int(max_waiting)
        self.backpressure = backpressure
        self.block_timeout_s = block_timeout_s
        self.manifest_path = manifest_path if manifest_path is not None \
            else (os.environ.get(ENV_DRAIN_MANIFEST, "").strip() or None)


def resolve_resilience(spec) -> Optional[ResilienceConfig]:
    """Normalize ``EngineConfig.resilience``: a config passes through,
    True arms the defaults, False disarms, None defers to the env
    (PADDLE_SERVE_RESILIENCE truthy, or a PADDLE_SERVE_DRAIN_MANIFEST
    path being named, arms)."""
    if spec is None:
        if os.environ.get(ENV_RESILIENCE, "").strip().lower() in _TRUTHY \
                or os.environ.get(ENV_DRAIN_MANIFEST, "").strip():
            return ResilienceConfig()
        return None
    if spec is False:
        return None
    if spec is True:
        return ResilienceConfig()
    if isinstance(spec, ResilienceConfig):
        return spec
    raise TypeError(
        f"EngineConfig.resilience wants None/bool/ResilienceConfig, "
        f"got {type(spec).__name__}")


# -- drain manifest ------------------------------------------------------------

def build_manifest(requests: Sequence, drain_seconds: float) -> dict:
    """The restart-replay manifest for the given UNFINISHED requests, in
    submission order: everything a fresh engine needs to finish them —
    prompt, the tokens already generated (they ride along through the
    PR 6 preemption mechanics, so clients keep their prefix), SLO
    deadlines and the opaque per-request ``tag``."""
    entries = []
    for i, req in enumerate(sorted(requests, key=lambda r: r.rid)):
        entries.append({
            "order": i,
            "rid": req.rid,
            "tag": req.tag,
            "prompt": list(req.prompt),
            "generated": list(req.output),
            "max_new_tokens": req.max_new_tokens,
            "eos_id": req.eos_id,
            "ttft_deadline": req.ttft_deadline,
            "tpot_deadline": req.tpot_deadline,
            "stream": req._stream is not None,
        })
    return _seal({
        "version": MANIFEST_VERSION,
        "unix_time": time.time(),
        "drain_seconds": round(drain_seconds, 6),
        "requests": entries,
    }, "drain_manifest")


def write_manifest(manifest: dict, path: str) -> None:
    """Atomic write (tmp + rename): a killed drain never leaves a torn
    manifest for the restarted generation to trip on."""
    _atomic_json(path, manifest, indent=1)


def load_manifest(path: str) -> dict:
    with open(path) as f:
        manifest = json.load(f)
    version = manifest.get("version")
    if version != MANIFEST_VERSION:
        raise ValueError(
            f"drain manifest {path} has version {version!r}, "
            f"this reader understands {MANIFEST_VERSION}")
    return _seal(manifest, "drain_manifest")


def replay_manifest(engine, manifest) -> List:
    """Resubmit every manifest request into ``engine`` in submission
    order; returns the live Request handles (plus already-complete
    entries as pre-finished requests). The generated tokens ride along
    for prefix recompute, so after the engine drains each request's
    final output is the greedy continuation of what the dead generation
    already delivered."""
    if isinstance(manifest, str):
        manifest = load_manifest(manifest)
    _seal(manifest, "drain_manifest")
    _instr.record_serve_engine_restart()
    handles = []
    for entry in sorted(manifest["requests"], key=lambda e: e["order"]):
        generated = list(entry.get("generated") or ())
        if len(generated) >= entry["max_new_tokens"]:
            # defensive: drain only exports unfinished requests, but a
            # hand-edited manifest must not make the engine decode past
            # a request's budget — synthesize the finished handle
            from .scheduler import Request
            req = Request(entry["prompt"],
                          max_new_tokens=entry["max_new_tokens"],
                          eos_id=entry.get("eos_id"),
                          stream=bool(entry.get("stream")),
                          tag=entry.get("tag"))
            req.seq.extend(int(t) for t in generated)
            req.output = [int(t) for t in generated]
            req.finish_reason = "max_new_tokens"
            # synthesized pre-finished handle: never submitted, so no
            # lifecycle trace exists for on_finish to terminate
            req.finish()  # tpu-lint: disable=CCY201
            handles.append(req)
            continue
        # _bypass_admission: the dead generation already admitted these —
        # a bounded-queue replay must not deadlock (block) or drop the
        # hand-over (reject/shed) before the driver even starts stepping
        handles.append(engine.submit(
            entry["prompt"], max_new_tokens=entry["max_new_tokens"],
            eos_id=entry.get("eos_id"),
            stream=bool(entry.get("stream")),
            ttft_deadline=entry.get("ttft_deadline"),
            tpot_deadline=entry.get("tpot_deadline"),
            generated=generated, tag=entry.get("tag"),
            _bypass_admission=True))
    return handles


# -- the canonical preemption-aware driver loop --------------------------------

def serve_until_preempted(engine, guard, manifest_path: Optional[str] = None,
                          idle_wait: float = 0.02,
                          stop_when_idle: bool = False,
                          max_steps: Optional[int] = None):
    """Drive ``engine.step()`` until preempted (or, with
    ``stop_when_idle``, until the engine runs out of work — the drill
    mode). On a preemption notice from ``guard``
    (``resilience.PreemptionGuard``: SIGTERM/SIGUSR1, notice file, chaos
    probe, peer consensus) the engine drains within the remaining grace
    budget and exports the restart-replay manifest. Returns
    ``("drained", manifest)`` after a preemption, ``("idle", None)``
    when stop_when_idle ended the loop."""
    path = manifest_path
    if path is None:
        res = engine.resilience
        path = res.manifest_path if res is not None else None
    steps = 0
    while True:
        if guard.should_stop():
            manifest = engine.drain(deadline_s=max(guard.remaining(), 0.0),
                                    manifest_path=path)
            return "drained", manifest
        if engine.has_work():
            engine.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                return "idle", None
        elif stop_when_idle:
            return "idle", None
        else:
            engine.wait_for_work(timeout=idle_wait)


__all__ = [
    "ResilienceConfig", "resolve_resilience", "StepFault", "RequestFailed",
    "AdmissionRejected", "build_manifest", "write_manifest",
    "load_manifest", "replay_manifest", "serve_until_preempted",
    "ENV_RESILIENCE", "ENV_DRAIN_MANIFEST", "MANIFEST_VERSION",
]
