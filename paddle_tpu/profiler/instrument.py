"""Framework built-in metrics: the stable, greppable catalog.

Every instrumented call site in the framework funnels through one
``record_*`` helper here, each of which starts with the single-boolean
enabled check (``metrics._ENABLED[0]``) so disabled runs pay nothing
beyond that check. Metric families live on the default registry and are
created lazily on first record.

Catalog (names are a stable API — see README "Observability"):

  ops_dispatch_total{op}                 ops/dispatch.py, per dispatched op
  jit_compile_total{fn}                  jit/ — fresh traces (cache misses)
  jit_cache_hits_total{fn}               jit/ — compiled calls reusing a trace
  jit_compile_seconds                    wall time of calls that traced
  collective_calls_total{op,tier}        distributed/communication.py
  collective_bytes_total{op,tier}        payload bytes (tier: ici|host|identity)
  host_collective_rounds_total{op}       distributed/host_collectives.py
  host_collective_bytes_total{op}        store-routed payload bytes
  checkpoint_save_seconds                distributed/checkpoint.py
  checkpoint_load_seconds                distributed/checkpoint.py
  watchdog_ticks_total                   distributed/watchdog.py StepWatchdog
  watchdog_fires_total                   hang events fired
  train_steps_total                      engine/hapi training steps
  dataloader_batches_total               hapi fit/eval loader batches
  resilience_faults_injected_total{site,kind}  resilience/chaos.py probes
  resilience_retries_total{site}         resilience/retry.py retried attempts
  resilience_giveups_total{site}         retry budget exhausted (raise)
  resilience_ckpt_events_total{event}    corrupt_detected|fallback|gc
  resilience_guard_events_total{kind,action}   StepGuard nan/spike events
  resilience_preemptions_total{source}   resilience/preempt.py notices
  resilience_emergency_save_seconds      preemption emergency-save wall time
  checkpoint_async_queue_depth           in-flight async writer threads
  checkpoint_async_join_seconds          async writer join (drain) latency
  serve_queue_depth                      serving/engine.py waiting requests
  serve_running_seqs                     sequences in the continuous batch
  serve_admitted_total                   requests admitted to the batch
  serve_finished_total                   requests finished and evicted
  serve_preempted_total                  requests preempted under pool pressure
  serve_steps_total                      engine steps (device calls)
  serve_tokens_total                     tokens sampled across all requests
  serve_kv_pool_utilization              live KV pages / pool size (0..1)
  serve_prefix_cache_queries_total       serving/kv_pool.py prefix lookups
  serve_prefix_cache_hits_total          lookups that reused >= 1 page
  serve_ttft_seconds                     submit -> first token latency
  serve_token_seconds                    per-token (step) latency
  serve_spec_proposed_tokens_total       draft tokens fed to verify steps
  serve_spec_accepted_tokens_total       drafts confirmed by greedy verify
  serve_spec_accept_rate                 per-step accepted/proposed ratio
  serve_spec_rollback_pages_total        KV pages released rolling back drafts
  serve_slo_violations_total{kind}       serving/obs.py deadline misses (ttft|tpot)
  serve_slo_attainment                   SLO-tracked requests meeting deadlines (0..1)
  serve_goodput_tokens_total             tokens from requests that met their SLOs
  serve_flight_dumps_total{trigger}      flight-recorder dumps by trigger reason
  serve_ttft_quantile_seconds{q}         streaming TTFT sketch quantiles (p50|p95|p99)
  serve_tpot_quantile_seconds{q}         streaming per-output-token quantiles
  serve_e2e_quantile_seconds{q}          streaming end-to-end latency quantiles
  aot_cache_hits_total{program}          aot/cache.py artifact deserialized
  aot_cache_misses_total{program}        traced+exported fresh (published)
  aot_cache_load_seconds                 deserialize+ready wall time on a hit
  aot_cache_export_seconds               trace+export+publish wall time
  aot_cache_fallbacks_total{reason}      corrupt|chaos|io|deserialize|export|run
  perf_evidence_rows_total{source}       profiler/evidence.py ledger ingests
  perf_resolver_decisions_total{flag,status}  flags.apply_perf_config outcomes
  perf_step_fraction{component}          step-time anatomy (compute|collective|data|host)
  perf_program_roofline_ratio{program}   intensity / machine balance per program
  mem_bytes_in_use{pool}                 profiler/memwatch.py pool split + total
  mem_peak_bytes{pool}                   per-pool high watermarks (resettable)
  mem_watermark_fraction                 bytes_in_use / bytes_limit (0..1)
  mem_pressure_dumps_total{trigger}      memwatch ring dumps (near_oom|manual)
  serve_kv_pool_bytes                    device bytes of live sequences' KV pages
  serve_step_faults_total{kind}          serving/resilience.py contained step faults
  serve_request_retries_total{reason}    requests requeued for recompute after a fault
  serve_shed_total{policy}               submissions refused by admission control
  serve_drain_seconds                    graceful-drain wall time (notice -> manifest)
  serve_engine_restarts_total            drain manifests replayed into a fresh engine
  serve_router_routed_total{policy}      serving/router.py routing decisions by policy
  serve_router_affinity_hits_total       submissions routed to a prefix-affine replica
  serve_router_replica_queue_depth{replica}  per-replica waiting requests
  serve_router_failover_total{reason}    requests re-routed off a replica (backpressure|death|drain)
  serve_kv_handoff_pages_total           KV pages moved prefill->decode across the pool boundary
  serve_disagg_handoffs_total{outcome}   disaggregated hand-offs by outcome (pages|recompute|failed)
  serve_role_queue_depth{role}           waiting requests per engine-pool role (prefill|decode)
  serve_router_dispatch_seconds          route decision -> replica placement wall time
  fleet_slo_attainment                   finished-weighted fleet SLO attainment roll-up (0..1)
  fleet_pressure_ratio{role}             per-role demand / capacity from the fleet signal bus
  fleet_replica_signal{name,replica}     sampled per-replica fleet-bus signals (queue_depth|tok_per_s)
  fleet_flight_dumps_total{trigger}      correlated fleet flight dumps by latch reason
  fleet_replicas{role}                   live replicas per role in the autoscaled fleet
  fleet_scale_events_total{action,outcome}  autoscale actuations (spawn|retire|rebalance x ok|fault|skipped)
  fleet_autoscale_decision_seconds       signal read -> decision -> actuation wall time
  transport_messages_total{kind,outcome} serving/transport.py messages by kind and terminal outcome
  transport_retries_total{site}          transport retransmissions by send site
  fleet_lease_transitions_total{from,to} serving/membership.py lease transitions (live|suspect|dead)
  serve_handoff_aborts_total{reason}     two-phase KV hand-offs aborted/salvaged by reason
"""
from __future__ import annotations

from . import metrics as _m

# The built-in metric-name catalog: every framework-emitted family, by its
# stable name. The analysis linter (paddle_tpu/analysis, rule TPU301) reads
# this tuple STATICALLY and flags any registry.counter/gauge/histogram call
# in the package whose literal name is absent — adding an instrumented call
# site means adding its family here (and to the docstring table above).
CATALOG = (
    "ops_dispatch_total",
    "jit_compile_total",
    "jit_cache_hits_total",
    "jit_compile_seconds",
    "collective_calls_total",
    "collective_bytes_total",
    "host_collective_rounds_total",
    "host_collective_bytes_total",
    "checkpoint_save_seconds",
    "checkpoint_load_seconds",
    "watchdog_ticks_total",
    "watchdog_fires_total",
    "train_steps_total",
    "dataloader_batches_total",
    "resilience_faults_injected_total",
    "resilience_retries_total",
    "resilience_giveups_total",
    "resilience_ckpt_events_total",
    "resilience_guard_events_total",
    "resilience_preemptions_total",
    "resilience_emergency_save_seconds",
    "checkpoint_async_queue_depth",
    "checkpoint_async_join_seconds",
    "serve_queue_depth",
    "serve_running_seqs",
    "serve_admitted_total",
    "serve_finished_total",
    "serve_preempted_total",
    "serve_steps_total",
    "serve_tokens_total",
    "serve_kv_pool_utilization",
    "serve_prefix_cache_queries_total",
    "serve_prefix_cache_hits_total",
    "serve_ttft_seconds",
    "serve_token_seconds",
    "serve_spec_proposed_tokens_total",
    "serve_spec_accepted_tokens_total",
    "serve_spec_accept_rate",
    "serve_spec_rollback_pages_total",
    "serve_slo_violations_total",
    "serve_slo_attainment",
    "serve_goodput_tokens_total",
    "serve_flight_dumps_total",
    "serve_ttft_quantile_seconds",
    "serve_tpot_quantile_seconds",
    "serve_e2e_quantile_seconds",
    "aot_cache_hits_total",
    "aot_cache_misses_total",
    "aot_cache_load_seconds",
    "aot_cache_export_seconds",
    "aot_cache_fallbacks_total",
    "perf_evidence_rows_total",
    "perf_resolver_decisions_total",
    "perf_step_fraction",
    "perf_program_roofline_ratio",
    "mem_bytes_in_use",
    "mem_peak_bytes",
    "mem_watermark_fraction",
    "mem_pressure_dumps_total",
    "serve_kv_pool_bytes",
    "serve_step_faults_total",
    "serve_request_retries_total",
    "serve_shed_total",
    "serve_drain_seconds",
    "serve_engine_restarts_total",
    "serve_router_routed_total",
    "serve_router_affinity_hits_total",
    "serve_router_replica_queue_depth",
    "serve_router_failover_total",
    "serve_kv_handoff_pages_total",
    "serve_disagg_handoffs_total",
    "serve_role_queue_depth",
    "serve_router_dispatch_seconds",
    "fleet_slo_attainment",
    "fleet_pressure_ratio",
    "fleet_replica_signal",
    "fleet_flight_dumps_total",
    "fleet_replicas",
    "fleet_scale_events_total",
    "fleet_autoscale_decision_seconds",
    "transport_messages_total",
    "transport_retries_total",
    "fleet_lease_transitions_total",
    "serve_handoff_aborts_total",
)

_enabled = _m._ENABLED  # bind the cell once: hot-path guard is _enabled[0]

_TIME_BUCKETS = (0.001, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0,
                 300.0, 1800.0)


def _reg() -> "_m.MetricsRegistry":
    return _m.get_registry()


def enabled() -> bool:
    return _enabled[0]


def record_op_dispatch(op: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("ops_dispatch_total",
                   "eager/traced op dispatches by op name",
                   labelnames=("op",)).labels(op=op).inc()


def record_jit_compile(fn: str, seconds: float) -> None:
    if not _enabled[0]:
        return
    r = _reg()
    r.counter("jit_compile_total", "to_static fresh traces (cache misses)",
              labelnames=("fn",)).labels(fn=fn).inc()
    r.histogram("jit_compile_seconds",
                "wall seconds of to_static calls that traced "
                "(trace+compile+first run)", buckets=_TIME_BUCKETS
                ).observe(seconds)


def record_jit_cache_hit(fn: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("jit_cache_hits_total",
                   "to_static calls served from the compile cache",
                   labelnames=("fn",)).labels(fn=fn).inc()


def record_collective(op: str, nbytes: int, tier: str) -> None:
    if not _enabled[0]:
        return
    r = _reg()
    lbl = {"op": op, "tier": tier}
    r.counter("collective_calls_total", "collective API calls",
              labelnames=("op", "tier")).labels(**lbl).inc()
    r.counter("collective_bytes_total", "collective payload bytes",
              labelnames=("op", "tier")).labels(**lbl).inc(max(int(nbytes), 0))


def record_host_collective(op: str, nbytes: int) -> None:
    if not _enabled[0]:
        return
    r = _reg()
    r.counter("host_collective_rounds_total",
              "store-routed host collective rounds",
              labelnames=("op",)).labels(op=op).inc()
    r.counter("host_collective_bytes_total",
              "store-routed host collective payload bytes",
              labelnames=("op",)).labels(op=op).inc(max(int(nbytes), 0))


def record_checkpoint(kind: str, seconds: float) -> None:
    if not _enabled[0]:
        return
    _reg().histogram(f"checkpoint_{kind}_seconds",
                     f"distributed checkpoint {kind} wall seconds",
                     buckets=_TIME_BUCKETS).observe(seconds)


def record_watchdog_tick() -> None:
    if not _enabled[0]:
        return
    _reg().counter("watchdog_ticks_total",
                   "StepWatchdog step completions observed").inc()


def record_watchdog_fire() -> None:
    if not _enabled[0]:
        return
    _reg().counter("watchdog_fires_total",
                   "StepWatchdog hang events fired").inc()


def record_train_step() -> None:
    if not _enabled[0]:
        return
    _reg().counter("train_steps_total", "training steps completed").inc()


def record_dataloader_batch() -> None:
    if not _enabled[0]:
        return
    _reg().counter("dataloader_batches_total",
                   "batches yielded to fit/evaluate loops").inc()


def record_fault_injected(site: str, kind: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("resilience_faults_injected_total",
                   "chaos faults fired by probe site and kind",
                   labelnames=("site", "kind")).labels(
        site=site, kind=kind).inc()


def record_resilience_retry(site: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("resilience_retries_total",
                   "RetryPolicy retried attempts by call site",
                   labelnames=("site",)).labels(site=site).inc()


def record_resilience_giveup(site: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("resilience_giveups_total",
                   "RetryPolicy exhaustions (exception re-raised)",
                   labelnames=("site",)).labels(site=site).inc()


def record_ckpt_event(event: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("resilience_ckpt_events_total",
                   "checkpoint lifecycle events "
                   "(corrupt_detected|fallback|gc)",
                   labelnames=("event",)).labels(event=event).inc()


def record_guard_event(kind: str, action: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("resilience_guard_events_total",
                   "StepGuard anomalies by kind and action taken",
                   labelnames=("kind", "action")).labels(
        kind=kind, action=action).inc()


def record_preemption(source: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("resilience_preemptions_total",
                   "preemption notices by source "
                   "(signal|file|env|chaos|peer|api)",
                   labelnames=("source",)).labels(source=source).inc()


def record_emergency_save(seconds: float) -> None:
    if not _enabled[0]:
        return
    _reg().histogram("resilience_emergency_save_seconds",
                     "deadline-driven emergency checkpoint wall seconds",
                     buckets=_TIME_BUCKETS).observe(seconds)


def record_async_queue_depth(depth: int) -> None:
    if not _enabled[0]:
        return
    _reg().gauge("checkpoint_async_queue_depth",
                 "async checkpoint writer threads not yet joined"
                 ).set(float(depth))


def record_async_join(seconds: float) -> None:
    if not _enabled[0]:
        return
    _reg().histogram("checkpoint_async_join_seconds",
                     "wall seconds spent joining async checkpoint "
                     "writers", buckets=_TIME_BUCKETS).observe(seconds)


def record_serve_queue_depth(depth: int) -> None:
    if not _enabled[0]:
        return
    _reg().gauge("serve_queue_depth",
                 "serving requests waiting for admission").set(float(depth))


def record_serve_step(admitted: int, finished: int, preempted: int,
                      queue_depth: int, running: int,
                      pool_utilization: float, launched: bool = True) -> None:
    """One continuous-batching engine step's worth of scheduler events
    (``launched`` False: the call only read back the step in flight)."""
    if not _enabled[0]:
        return
    r = _reg()
    if launched:
        r.counter("serve_steps_total",
                  "serving engine steps (device calls)").inc()
    if admitted:
        r.counter("serve_admitted_total",
                  "requests admitted into the continuous batch") \
            .inc(admitted)
    if finished:
        r.counter("serve_finished_total",
                  "requests finished and evicted from the batch") \
            .inc(finished)
    if preempted:
        r.counter("serve_preempted_total",
                  "requests preempted under KV-pool pressure") \
            .inc(preempted)
    r.gauge("serve_queue_depth",
            "serving requests waiting for admission").set(float(queue_depth))
    r.gauge("serve_running_seqs",
            "sequences live in the continuous batch").set(float(running))
    r.gauge("serve_kv_pool_utilization",
            "KV pages held by live sequences / pool size") \
        .set(float(pool_utilization))


def record_serve_prefix(queries: int, hits: int) -> None:
    if not _enabled[0]:
        return
    r = _reg()
    if queries:
        r.counter("serve_prefix_cache_queries_total",
                  "KV prefix-cache lookups at admission").inc(queries)
    if hits:
        r.counter("serve_prefix_cache_hits_total",
                  "prefix-cache lookups reusing >= 1 cached page") \
            .inc(hits)


def record_serve_ttft(seconds: float) -> None:
    if not _enabled[0]:
        return
    _reg().histogram("serve_ttft_seconds",
                     "submit -> first sampled token latency",
                     buckets=_TIME_BUCKETS).observe(seconds)


def record_serve_spec_tokens(proposed: int, accepted: int) -> None:
    """One verify step's speculative outcome: ``proposed`` draft tokens
    fed, ``accepted`` confirmed by longest-prefix greedy verification."""
    if not _enabled[0]:
        return
    r = _reg()
    if proposed:
        r.counter("serve_spec_proposed_tokens_total",
                  "draft tokens fed to speculative verify steps") \
            .inc(proposed)
        r.gauge("serve_spec_accept_rate",
                "accepted/proposed draft ratio of the last verify step") \
            .set(accepted / proposed)
    if accepted:
        r.counter("serve_spec_accepted_tokens_total",
                  "draft tokens confirmed by greedy verification") \
            .inc(accepted)


def record_serve_spec_rollback(pages: int) -> None:
    if not _enabled[0] or not pages:
        return
    _reg().counter("serve_spec_rollback_pages_total",
                   "KV pages released rolling back rejected drafts") \
        .inc(pages)


def record_serve_slo_violation(kind: str) -> None:
    """One SLO deadline miss (kind: ttft | tpot)."""
    if not _enabled[0]:
        return
    _reg().counter("serve_slo_violations_total",
                   "serving SLO deadline misses by kind (ttft|tpot)",
                   labelnames=("kind",)).labels(kind=kind).inc()


def record_serve_slo_attainment(fraction: float) -> None:
    if not _enabled[0]:
        return
    _reg().gauge("serve_slo_attainment",
                 "fraction of SLO-tracked finished requests that met "
                 "every deadline").set(float(fraction))


def record_serve_goodput(tokens: int) -> None:
    """Tokens from a finished request that met its SLO deadlines (0 for
    a request that blew one — those tokens are throughput, not goodput)."""
    if not _enabled[0] or not tokens:
        return
    _reg().counter("serve_goodput_tokens_total",
                   "output tokens from requests that met their SLO "
                   "deadlines").inc(tokens)


def record_serve_flight_dump(trigger: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("serve_flight_dumps_total",
                   "flight-recorder dumps by trigger "
                   "(stall|pool_exhausted|chaos_fault|slo_blow|manual)",
                   labelnames=("trigger",)).labels(trigger=trigger).inc()


def record_serve_quantiles(kind: str, p50: float, p95: float,
                           p99: float) -> None:
    """Streaming latency sketch quantiles (kind: ttft | tpot | e2e) —
    gauges so dashboards read the engine's bounded-sketch estimates
    without scraping histograms."""
    if not _enabled[0]:
        return
    g = _reg().gauge(f"serve_{kind}_quantile_seconds",
                     "bounded-sketch streaming latency quantile by q "
                     "(p50|p95|p99)", labelnames=("q",))
    g.labels(q="p50").set(float(p50))
    g.labels(q="p95").set(float(p95))
    g.labels(q="p99").set(float(p99))


def record_aot_cache_hit(program: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("aot_cache_hits_total",
                   "AOT program artifacts deserialized (trace skipped)",
                   labelnames=("program",)).labels(program=program).inc()


def record_aot_cache_miss(program: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("aot_cache_misses_total",
                   "AOT programs traced+exported fresh (published)",
                   labelnames=("program",)).labels(program=program).inc()


def record_aot_load(seconds: float) -> None:
    if not _enabled[0]:
        return
    _reg().histogram("aot_cache_load_seconds",
                     "artifact deserialize + program-ready wall seconds "
                     "on a cache hit", buckets=_TIME_BUCKETS) \
        .observe(seconds)


def record_aot_export(seconds: float) -> None:
    if not _enabled[0]:
        return
    _reg().histogram("aot_cache_export_seconds",
                     "trace + export + publish wall seconds on a cache "
                     "miss", buckets=_TIME_BUCKETS).observe(seconds)


def record_aot_fallback(reason: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("aot_cache_fallbacks_total",
                   "AOT cache degraded to fresh/uncached compile "
                   "(corrupt|chaos|io|deserialize|export|run)",
                   labelnames=("reason",)).labels(reason=reason).inc()


def record_perf_evidence_rows(source: str, n: int = 1) -> None:
    """n rows ingested into the perf-evidence ledger from one source."""
    if not _enabled[0] or not n:
        return
    _reg().counter("perf_evidence_rows_total",
                   "perf-evidence ledger rows ingested by source "
                   "(probe|bench|bench_serve|bench_session|mfu_lab|"
                   "autotune|aot_stats|runlog|flight)",
                   labelnames=("source",)).labels(source=source).inc(n)


def record_perf_resolver_decision(flag: str, status: str) -> None:
    """One apply_perf_config outcome for one flag (status: applied|
    deferred|env_override|stale|device_mismatch|corrupt)."""
    if not _enabled[0]:
        return
    _reg().counter("perf_resolver_decisions_total",
                   "perf-config resolver decisions by flag and apply "
                   "outcome",
                   labelnames=("flag", "status")).labels(
        flag=flag, status=status).inc()


def record_perf_step_fraction(component: str, fraction: float) -> None:
    """Step-time anatomy: the fraction of the last attributed step spent
    in one component (compute|collective|data|host)."""
    if not _enabled[0]:
        return
    _reg().gauge("perf_step_fraction",
                 "fraction of the last attributed step's wall time by "
                 "component (compute|collective|data|host)",
                 labelnames=("component",)).labels(
        component=component).set(float(fraction))


def record_perf_roofline(program: str, ratio: float) -> None:
    """Roofline position of one program: arithmetic intensity over the
    device's machine balance (>=1 compute-bound, <1 memory-bound)."""
    if not _enabled[0]:
        return
    _reg().gauge("perf_program_roofline_ratio",
                 "program arithmetic intensity / device machine balance "
                 "(>=1: compute-bound)",
                 labelnames=("program",)).labels(
        program=program).set(float(ratio))


def record_mem_bytes_in_use(pool: str, nbytes: int) -> None:
    """Current device bytes attributed to one memwatch pool (params|
    optimizer|kv_pages|workspace|other|total)."""
    if not _enabled[0]:
        return
    _reg().gauge("mem_bytes_in_use",
                 "device bytes currently attributed to a memwatch pool "
                 "(params|optimizer|kv_pages|workspace|other|total)",
                 labelnames=("pool",)).labels(pool=pool).set(float(nbytes))


def record_mem_peak_bytes(pool: str, nbytes: int) -> None:
    if not _enabled[0]:
        return
    _reg().gauge("mem_peak_bytes",
                 "high-watermark device bytes per memwatch pool "
                 "(resettable via reset_watermarks)",
                 labelnames=("pool",)).labels(pool=pool).set(float(nbytes))


def record_mem_watermark_fraction(fraction: float) -> None:
    if not _enabled[0]:
        return
    _reg().gauge("mem_watermark_fraction",
                 "bytes_in_use / bytes_limit of the last memory snapshot "
                 "(near-OOM trigger input, 0..1)").set(float(fraction))


def record_mem_pressure_dump(trigger: str) -> None:
    if not _enabled[0]:
        return
    _reg().counter("mem_pressure_dumps_total",
                   "memwatch ring dumps by trigger (near_oom|manual)",
                   labelnames=("trigger",)).labels(trigger=trigger).inc()


def record_serve_kv_pool_bytes(nbytes: int) -> None:
    """Device bytes held by live sequences' KV pages (used pages x
    per-page bytes across both K and V pools)."""
    if not _enabled[0]:
        return
    _reg().gauge("serve_kv_pool_bytes",
                 "device bytes of KV pages held by live sequences "
                 "(used pages x per-page K+V bytes)").set(float(nbytes))


def record_serve_step_fault(kind: str) -> None:
    """One contained engine-step fault (kind: chaos | nan_logits | the
    escaping exception's class name)."""
    if not _enabled[0]:
        return
    _reg().counter("serve_step_faults_total",
                   "serving engine steps that raised and were contained "
                   "by the resilience plane (by fault kind)",
                   labelnames=("kind",)).labels(kind=kind).inc()


def record_serve_request_retry(reason: str) -> None:
    """One request requeued for prefix recompute after a contained
    fault (reason: step_fault)."""
    if not _enabled[0]:
        return
    _reg().counter("serve_request_retries_total",
                   "serving requests requeued for recompute by reason",
                   labelnames=("reason",)).labels(reason=reason).inc()


def record_serve_shed(policy: str) -> None:
    """One submission refused by admission control under the named
    backpressure policy (block | reject | shed)."""
    if not _enabled[0]:
        return
    _reg().counter("serve_shed_total",
                   "serving submissions refused by admission control "
                   "(by backpressure policy)",
                   labelnames=("policy",)).labels(policy=policy).inc()


def record_serve_drain(seconds: float) -> None:
    if not _enabled[0]:
        return
    _reg().histogram("serve_drain_seconds",
                     "graceful-drain wall seconds (stop admission -> "
                     "manifest exported)", buckets=_TIME_BUCKETS) \
        .observe(seconds)


def record_serve_engine_restart() -> None:
    """One drain manifest replayed into a (re)started engine."""
    if not _enabled[0]:
        return
    _reg().counter("serve_engine_restarts_total",
                   "drain manifests replayed into a fresh serving "
                   "engine after a restart").inc()


def record_router_routed(policy: str, affinity_hit: bool = False) -> None:
    """One replica-router routing decision. ``policy`` names what
    actually decided the placement (affinity | least_loaded | random |
    round_robin); ``affinity_hit`` marks submissions that landed on a
    replica already holding their prefix."""
    if not _enabled[0]:
        return
    r = _reg()
    r.counter("serve_router_routed_total",
              "replica-router routing decisions by deciding policy",
              labelnames=("policy",)).labels(policy=policy).inc()
    if affinity_hit:
        r.counter("serve_router_affinity_hits_total",
                  "submissions routed to a replica already holding "
                  "their prompt prefix").inc()


def record_router_queue_depth(replica: int, depth: int) -> None:
    """One replica's waiting-queue depth (refreshed per router step)."""
    if not _enabled[0]:
        return
    _reg().gauge("serve_router_replica_queue_depth",
                 "waiting requests per router replica",
                 labelnames=("replica",)) \
        .labels(replica=str(replica)).set(float(depth))


def record_router_failover(reason: str) -> None:
    """One request re-routed off its chosen replica (reason:
    backpressure | death | drain)."""
    if not _enabled[0]:
        return
    _reg().counter("serve_router_failover_total",
                   "requests re-routed off a replica by reason",
                   labelnames=("reason",)).labels(reason=reason).inc()


def record_kv_handoff(pages: int) -> None:
    """One prefill->decode KV-page export: ``pages`` physical pages'
    contents moved across the pool boundary (0 for a 1-token prompt)."""
    if not _enabled[0] or not pages:
        return
    _reg().counter("serve_kv_handoff_pages_total",
                   "KV pages moved prefill->decode across the "
                   "disaggregated pool boundary").inc(pages)


def record_disagg_handoff(outcome: str) -> None:
    """One disaggregated hand-off resolved (outcome: pages = KV import
    landed, recompute = fallback to prompt recompute on the decode
    replica, failed = no decode survivor — terminal error)."""
    if not _enabled[0]:
        return
    _reg().counter("serve_disagg_handoffs_total",
                   "prefill->decode hand-offs by outcome "
                   "(pages|recompute|failed)",
                   labelnames=("outcome",)).labels(outcome=outcome).inc()


def record_role_queue_depth(role: str, depth: int) -> None:
    """Aggregate waiting-queue depth of one engine-pool role."""
    if not _enabled[0]:
        return
    _reg().gauge("serve_role_queue_depth",
                 "waiting requests per engine-pool role "
                 "(prefill|decode)",
                 labelnames=("role",)).labels(role=role).set(float(depth))


def record_router_dispatch(seconds: float) -> None:
    """Wall time of one router dispatch: route decision through replica
    placement (including any backpressure fail-over hops)."""
    if not _enabled[0]:
        return
    _reg().histogram("serve_router_dispatch_seconds",
                     "route decision -> replica placement wall time",
                     buckets=_TIME_BUCKETS).observe(seconds)


def record_fleet_slo_attainment(value: float) -> None:
    """The fleet signal bus's finished-weighted SLO attainment roll-up
    (replicas with no tracked finishes carry zero weight)."""
    if not _enabled[0]:
        return
    _reg().gauge("fleet_slo_attainment",
                 "finished-weighted fleet SLO attainment roll-up") \
        .set(float(value))


def record_fleet_pressure(role: str, value: float) -> None:
    """One role pool's pressure (demand / capacity) from the bus."""
    if not _enabled[0]:
        return
    _reg().gauge("fleet_pressure_ratio",
                 "per-role demand / capacity from the fleet signal bus",
                 labelnames=("role",)).labels(role=role).set(float(value))


def record_fleet_replica_signal(name: str, replica: int,
                                value: float) -> None:
    """One sampled per-replica signal from the fleet bus ring."""
    if not _enabled[0]:
        return
    _reg().gauge("fleet_replica_signal",
                 "sampled per-replica fleet-bus signals",
                 labelnames=("name", "replica")) \
        .labels(name=name, replica=str(replica)).set(float(value))


def record_fleet_flight_dump(trigger: str) -> None:
    """One correlated fleet flight dump latched (by reason)."""
    if not _enabled[0]:
        return
    _reg().counter("fleet_flight_dumps_total",
                   "correlated fleet flight dumps by latch reason",
                   labelnames=("trigger",)).labels(trigger=trigger).inc()


def record_fleet_scale_replicas(role: str, n: int) -> None:
    """Live replica count for one role pool of the autoscaled fleet
    (role "unified" for role-less fleets)."""
    if not _enabled[0]:
        return
    _reg().gauge("fleet_replicas",
                 "live replicas per role in the autoscaled fleet",
                 labelnames=("role",)).labels(role=role).set(float(n))


def record_fleet_scale_event(action: str, outcome: str) -> None:
    """One autoscale actuation: action spawn|retire|rebalance, outcome
    ok|fault|skipped."""
    if not _enabled[0]:
        return
    _reg().counter("fleet_scale_events_total",
                   "autoscale actuations by action and outcome",
                   labelnames=("action", "outcome")) \
        .labels(action=action, outcome=outcome).inc()


def record_fleet_scale_decision(seconds: float) -> None:
    """Wall time of one autoscaler control pass: signal read through
    decision and (possibly chaos-probed) actuation."""
    if not _enabled[0]:
        return
    _reg().histogram("fleet_autoscale_decision_seconds",
                     "signal read -> decision -> actuation wall time",
                     buckets=_TIME_BUCKETS).observe(seconds)


def record_transport_message(kind: str, outcome: str) -> None:
    """One transport message reaching a terminal outcome (delivered |
    dropped | deduped | partitioned | torn | expired | unroutable)."""
    if not _enabled[0]:
        return
    _reg().counter("transport_messages_total",
                   "replica-transport messages by kind and terminal "
                   "outcome",
                   labelnames=("kind", "outcome")) \
        .labels(kind=kind, outcome=outcome).inc()


def record_transport_retry(site: str) -> None:
    """One transport retransmission of an unacked message (site names
    the sending channel, e.g. transport.kv_prepare)."""
    if not _enabled[0]:
        return
    _reg().counter("transport_retries_total",
                   "transport retransmissions by send site",
                   labelnames=("site",)).labels(site=site).inc()


def record_lease_transition(frm: str, to: str) -> None:
    """One membership lease transition (live|suspect|dead)."""
    if not _enabled[0]:
        return
    _reg().counter("fleet_lease_transitions_total",
                   "membership lease state transitions",
                   labelnames=("from", "to")) \
        .labels(**{"from": frm, "to": to}).inc()


def record_handoff_abort(reason: str) -> None:
    """One two-phase KV hand-off aborted (reason: the importer's nack
    cause, ack_timeout for a retry give-up, ack_lost for a hand-off
    that committed without its ack ever arriving)."""
    if not _enabled[0]:
        return
    _reg().counter("serve_handoff_aborts_total",
                   "two-phase KV hand-offs aborted by reason",
                   labelnames=("reason",)).labels(reason=reason).inc()


def record_serve_tokens(n: int, step_seconds: float) -> None:
    """n tokens sampled by one step of step_seconds wall time."""
    if not _enabled[0]:
        return
    r = _reg()
    if n:
        r.counter("serve_tokens_total",
                  "tokens sampled across all serving requests").inc(n)
    h = r.histogram("serve_token_seconds",
                    "per-token latency (wall time of the step that "
                    "produced it)", buckets=_TIME_BUCKETS)
    for _ in range(n):
        h.observe(step_seconds)
