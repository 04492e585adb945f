"""paddle_tpu.serving — continuous-batching LLM serving engine.

The production serving tier (ROADMAP item 2): a continuous-batching
scheduler over a shared, prefix-cached KV block pool, attending through
ragged paged attention (pure-JAX reference now, flag-gated Pallas kernel
for the TPU window), with streaming output and an
``inference.Predictor``-compatible front door.

    from paddle_tpu.serving import ServingEngine, EngineConfig
    eng = ServingEngine(model, EngineConfig(max_seqs=8, token_budget=64,
                                            block_size=16))
    req = eng.submit(prompt_ids, max_new_tokens=64, stream=True)
    while eng.step():
        pass                       # or drive from a server thread
    print(req.result())

Speculative decoding (``serving.speculative``) rides the same packed
batch: a drafter (model-free n-gram prompt-lookup, or a small draft
model) proposes k tokens per decode sequence, one ragged verify forward
scores all k+1 positions, longest-accepted-prefix greedy verification
keeps output bit-identical, and ``KVBlockPool.truncate`` rolls pages
back past the accepted frontier (copy-on-write on shared pages):

    eng = ServingEngine(model, EngineConfig(spec_method="ngram",
                                            num_draft_tokens=4))

Speed is measured on the chip by ``python bench/run.py`` (cells in
``BENCHMARK.json``, the driver's results in ``PERF_LEDGER.jsonl``).

Observability (``serving.obs``): per-request lifecycle tracing
(chrome-trace exportable, trace_merge-alignable with training traces),
a step-plan flight recorder that dumps to JSON on anomalies (driver
stall, pool exhaustion, chaos fault, SLO deadline blow) or on demand via
``engine.dump_flight_record()``, and SLO/goodput telemetry with bounded
streaming quantiles behind ``engine.telemetry()`` (rendered live by
``tools/serve_top.py``). Disarmed by default — arm with
``EngineConfig(obs=True)`` or ``PADDLE_SERVE_OBS=1``:

    eng = ServingEngine(model, EngineConfig(obs=ObsConfig(
        flight_steps=256, stall_threshold_s=30.0)))
    req = eng.submit(ids, max_new_tokens=64, ttft_deadline=0.5,
                     tpot_deadline=0.05)

Resilience (``serving.resilience``): step-fault containment (a raising
or NaN-logits step requeues its requests for recompute under a bounded
retry budget; past-budget requests fail with a clean terminal error),
graceful drain with an atomic restart-replay manifest
(``engine.drain`` / ``replay_manifest`` / ``serve_until_preempted``,
supervised by ``tools/supervise.py``), and bounded-queue admission
control (``block`` | ``reject`` | SLO-aware ``shed`` — overload becomes
a typed ``AdmissionRejected`` with a retry-after estimate). Disarmed by
default — arm with ``EngineConfig(resilience=True | ResilienceConfig)``
or ``PADDLE_SERVE_RESILIENCE=1``; drill with
``tools/chaos_drill.py --serve``:

    eng = ServingEngine(model, EngineConfig(resilience=ResilienceConfig(
        max_step_retries=2, max_waiting=64, backpressure="shed")))

Scale-out (``serving.router`` + ``EngineConfig(mesh=)``): the engine
step runs tensor-parallel under an ``mp`` mesh (weights column/row
split at the ``_qkv_proj``/``_post_attn`` seams, KV pools sharded
per-KV-head, greedy output bit-identical to ``generate()``), and
``ReplicaRouter`` puts N engines behind a prefix-affinity admission
tier — the affinity key is the KV pool's hash-chain prefix key, a
replica's ``AdmissionRejected`` fails over least-loaded-first, and a
dead or decommissioned replica's drain manifest (its ``tag`` carries
the affinity key) replays onto affinity-matched survivors:

    tp = ServingEngine(model, EngineConfig(mesh=4))     # 4-way TP
    router = ReplicaRouter([ServingEngine(model, EngineConfig())
                            for _ in range(4)], policy="affinity")
    req = router.submit(ids, max_new_tokens=64, tag="user-7")
    while router.step_all():
        pass

Drill replica death with ``python tools/chaos_drill.py --router``;
watch the fleet with ``python tools/serve_top.py --demo --replicas 4``.

Disaggregated serving (``EngineConfig(role=)`` + the router's pool
classes): ``role="prefill"`` engines give the whole token budget to
chunked prefill and never sample; at prefill completion the request's
KV pages — contents as device arrays plus hash-chain prefix
registrations (``KVBlockPool.export_pages``/``import_pages``) — hand
off to the affinity-matched ``role="decode"`` replica, where decode
resumes bit-identically on a token-thin step program. Unobtainable
imports and prefill-replica death degrade to prompt recompute on a
decode survivor; nothing parks:

    fleet = [ServingEngine(model, EngineConfig(role="prefill")),
             ServingEngine(model, EngineConfig(role="decode",
                                               token_budget=16))]
    router = ReplicaRouter(fleet, policy="affinity")

Drill prefill death with ``python tools/chaos_drill.py --disagg``;
watch the pools with ``python tools/serve_top.py --demo --disagg
--replicas 4``.

Fleet observability (``serving.fleet_obs``): the third observability
plane (training → engine → fleet). ``ReplicaRouter(fleet_obs=True |
FleetObsConfig)`` arms a ``FleetObserver`` that (a) rings a bounded,
time-aligned window of per-replica signals every ``step_all`` pass and
derives fleet signals — prefill:decode pressure ratio,
finished-weighted SLO attainment roll-up, ``mem_report``-priced
headroom — behind a stable ``signals()`` schema streamed atomically to
``PADDLE_FLEET_TELEMETRY``; (b) adds router-side spans (route decision,
hand-off dispatch/defer, failover) to the lifecycle trace that rides
each request, and exports one fleet chrome trace
(router→prefill→kv_handoff→decode per request, plus per-replica engine
tracks) on the shared clock anchor; (c) snapshots EVERY peer's signal
window into one correlated ``fleet_flight_<reason>.json`` when any
replica's flight trigger latches or a replica dies — latched once per
reason, never raising into ``step_all``:

    router = ReplicaRouter(fleet, fleet_obs=FleetObsConfig(window=64))
    sig = router.signals()              # the item-2(c) autoscaler feed
    router.export_chrome_trace("fleet_trace.json")

Drill it with ``python tools/chaos_drill.py --fleet-obs``; watch with
``python tools/serve_top.py --demo --fleet``.

Elastic control plane (``serving.autoscaler``): the actuator that
closes the item-2(c) loop. ``FleetAutoscaler`` reads one ``signals()``
snapshot per control interval and fires at most one rule — spawn a
replica of the hottest role (``engine_factory`` → ``add_replica``,
gated fits-first on the ``mem_report`` headroom signal), retire the
least-affinity-loaded replica through ``decommission`` (its drain
manifest replays onto survivors: zero parked requests by
construction), or flip a replica between prefill/decode roles
(``router.set_role``: drain → re-validate → re-admit) when the
prefill:decode pressure ratio drifts out of band — under hysteresis
bands, per-action cooldowns, a min/max replica envelope and a
chaos-probed actuation path (``elastic.spawn``/``elastic.retire``)
whose faults degrade to backoff-and-hold, never a raise into
``step_all``. Every decision lands as a structured ``AutoscaleEvent``
on the fleet-obs signal ring:

    scaler = FleetAutoscaler(router, engine_factory=make_engine,
                             config=AutoscalerConfig(max_replicas=4))
    while router.step_all():
        scaler.control()                # at most one action per pass

Drill faulted spawns and mid-burst retires over a 10x traffic swing
with ``python tools/chaos_drill.py --elastic``.

Fault-domain fabric (``serving.transport`` + ``serving.membership``):
the router's three cross-replica channels — KV-page hand-off,
drain-manifest replay, lease heartbeats — pushed through a
chaos-injectable, tick-based message transport with idempotency-keyed
dedup, per-link re-sequencing, and ack-tracked sends retransmitted on
``RetryPolicy``'s seeded backoff. Liveness becomes a lease state
machine (live → suspect → dead): a quiet replica loses dispatch
immediately but is salvaged only at lease expiry, so a healed
partition never double-decodes. The KV hand-off becomes two-phase —
the exporter retains pages until the importer's ``kv_transfer_ack``
commits or aborts, so a torn transfer leaves neither pool holding
garbage and every request finishes exactly once:

    router = ReplicaRouter(fleet, transport=True, membership=True)

Disarmed (the default) the synchronous in-process paths are untouched,
bit-identically. Drill with ``python tools/chaos_drill.py --partition``
(partition-then-heal vs lease expiry) and ``--lossy`` (5% drop + dup +
delay).

Lock discipline (``serving.locking``): every serving-plane lock is an
``OrderedLock`` ranked by the declared ``LOCK_ORDER`` (fleet_obs →
router → transport → membership → engine → observer, outermost
first). Disarmed it is a plain
``threading.RLock`` (sub-microsecond acquire); armed — via
``PADDLE_LOCKCHECK=1`` or ``locking.arm(True)`` — any out-of-order
acquisition raises ``LockOrderViolation`` *before* blocking, so
inversions surface deterministically on a single thread instead of as
a once-a-week fleet deadlock. The same ``LOCK_ORDER`` literal is the
ground truth for the static CCY1xx analyzer
(``paddle_tpu.analysis.concur_rules``); ``analysis.concurcheck``
cross-checks that the static table and this runtime twin never drift.
Drill the armed path with ``python tools/chaos_drill.py --lockcheck``.
"""
from .autoscaler import AutoscaleEvent, AutoscalerConfig, FleetAutoscaler
from .engine import (EngineConfig, EnginePredictor, ServingEngine,
                     engine_from_config)
from .kv_pool import KVBlockPool, PoolExhausted, prefix_chain_keys
from .locking import LOCK_ORDER, LockOrderViolation, OrderedLock
from .router import ReplicaRouter
from .obs import ObsConfig, RequestTrace, ServingObserver, resolve_observer
from .fleet_obs import FleetObsConfig, FleetObserver, resolve_fleet_obs
from .ragged import ragged_paged_attention
from .resilience import (AdmissionRejected, RequestFailed, ResilienceConfig,
                         StepFault, load_manifest, replay_manifest,
                         resolve_resilience, serve_until_preempted)
from .scheduler import Request, Scheduler
from .speculative import (Drafter, DraftModelDrafter, NgramDrafter,
                          make_drafter, verify_greedy)
from .transport import (ReplicaTransport, TransportConfig,
                        resolve_transport)
from .membership import (MembershipConfig, MembershipTable,
                         resolve_membership)

__all__ = [
    "EngineConfig", "EnginePredictor", "ServingEngine",
    "engine_from_config", "KVBlockPool", "PoolExhausted",
    "prefix_chain_keys", "ReplicaRouter",
    "LOCK_ORDER", "LockOrderViolation", "OrderedLock",
    "AutoscaleEvent", "AutoscalerConfig", "FleetAutoscaler",
    "ragged_paged_attention", "Request", "Scheduler",
    "Drafter", "NgramDrafter", "DraftModelDrafter", "make_drafter",
    "verify_greedy",
    "ObsConfig", "RequestTrace", "ServingObserver", "resolve_observer",
    "FleetObsConfig", "FleetObserver", "resolve_fleet_obs",
    "ResilienceConfig", "resolve_resilience", "AdmissionRejected",
    "RequestFailed", "StepFault", "load_manifest", "replay_manifest",
    "serve_until_preempted",
    "ReplicaTransport", "TransportConfig", "resolve_transport",
    "MembershipConfig", "MembershipTable", "resolve_membership",
]
