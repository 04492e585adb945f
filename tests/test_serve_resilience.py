"""Serving resilience plane (paddle_tpu.serving.resilience).

The serving twin of the PR 5 preemption contract, test-pinned: a
raising/NaN engine step is CONTAINED (bounded per-request retries,
clean terminal errors past budget, pool/slot accounting consistent),
``drain()`` exports a restart-replay manifest honoring its deadline,
the bounded waiting queue backpressures per policy (block | reject |
SLO-aware shed), the PR 9 lifecycle traces still end in exactly ONE
terminal event on every new path, and the disarmed plane costs one
``is None`` check (microbench-pinned like the obs plane).
"""
import functools
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import (AdmissionRejected, EngineConfig,
                                RequestFailed, ResilienceConfig,
                                ServingEngine, StepFault, load_manifest,
                                replay_manifest, resolve_resilience)

pytestmark = pytest.mark.serve


@functools.lru_cache(maxsize=None)
def _model(kv_heads=2, seed=3, vocab=61):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab_size=vocab, hidden_size=32, layers=2,
                           heads=4, kv_heads=kv_heads, seq=64)
    cfg.use_flash_attention = False
    return LlamaForCausalLM(cfg)


def _prompts(n, lens=(7, 4, 11, 5, 9, 3, 8, 6), vocab=61, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (lens[i % len(lens)],)).tolist()
            for i in range(n)]


_oracle_memo = {}


def _oracle(model, prompts, max_new):
    key = (id(model), tuple(tuple(p) for p in prompts), max_new)
    if key not in _oracle_memo:
        eng = ServingEngine(model, EngineConfig(max_seqs=4,
                                                token_budget=32,
                                                block_size=8))
        _oracle_memo[key] = eng.generate_batch(prompts,
                                               max_new_tokens=max_new)
    return [list(o) for o in _oracle_memo[key]]


def _engine(model, resilience=True, **kw):
    kw.setdefault("max_seqs", 2)
    kw.setdefault("token_budget", 16)
    kw.setdefault("block_size", 8)
    return ServingEngine(model, EngineConfig(resilience=resilience, **kw))


# -- config / arming -----------------------------------------------------------

def test_resilience_disarmed_by_default_and_env_arming(monkeypatch):
    model = _model()
    assert _engine(model, resilience=None).resilience is None
    assert _engine(model, resilience=False).resilience is None
    assert _engine(model, resilience=True).resilience is not None
    monkeypatch.setenv("PADDLE_SERVE_RESILIENCE", "1")
    assert _engine(model, resilience=None).resilience is not None
    monkeypatch.delenv("PADDLE_SERVE_RESILIENCE")
    monkeypatch.setenv("PADDLE_SERVE_DRAIN_MANIFEST", "/tmp/m.json")
    res = resolve_resilience(None)
    assert res is not None and res.manifest_path == "/tmp/m.json"
    with pytest.raises(ValueError, match="backpressure"):
        ResilienceConfig(backpressure="drop")
    with pytest.raises(ValueError, match="max_waiting"):
        ResilienceConfig(max_waiting=0)
    with pytest.raises(TypeError, match="resilience"):
        resolve_resilience("yes")


# -- step-fault containment ----------------------------------------------------

def test_step_fault_contained_bit_identical_parity():
    """One injected serve.engine_step fault: the driver never sees it,
    affected requests are requeued for recompute (generated tokens ride
    along), and output stays bit-identical to a fault-free run."""
    model = _model()
    prompts = _prompts(4)
    want = _oracle(model, prompts, max_new=6)
    plan = chaos.FaultPlan(seed=0).add("serve.engine_step", "error",
                                       at=(3,))
    eng = _engine(model, ResilienceConfig(max_step_retries=2))
    chaos.install_plan(plan)
    try:
        got = eng.generate_batch(prompts, max_new_tokens=6)
    finally:
        chaos.clear_plan()
    assert got == want
    assert eng.step_faults == 1
    assert eng.request_retries >= 1
    assert eng.requests_failed == 0
    assert ("serve.engine_step", "error", 3) in plan.fired
    # pool/slot consistency after the reset: everything drained
    assert eng.pool.used_blocks() == 0
    assert len(eng.sched._free_slots) == eng.config.max_seqs


def test_step_fault_budget_exhaustion_fails_cleanly_and_recovers():
    """Past the per-request retry budget the engine gives up CLEANLY:
    result() raises RequestFailed (never hangs), the driver loop ends,
    pages/slots are reclaimed, and once the fault clears the same
    engine serves again."""
    model = _model()
    prompts = _prompts(3)
    want = _oracle(model, prompts, max_new=4)
    eng = _engine(model, ResilienceConfig(max_step_retries=1))
    chaos.install_plan(chaos.FaultPlan(seed=0).add(
        "serve.engine_step", "error", prob=1.0))
    try:
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        steps = eng.run_until_idle(max_steps=200)
    finally:
        chaos.clear_plan()
    assert steps < 200                          # no livelock
    for r in reqs:
        assert r.done
        with pytest.raises(RequestFailed) as ei:
            r.result(0)
        assert ei.value.rid == r.rid
        assert "step_fault" in ei.value.reason
        assert ei.value.retries == 1            # the budget, spent
    assert eng.requests_failed == 3
    assert eng.pool.used_blocks() == 0
    assert len(eng.sched._free_slots) == eng.config.max_seqs
    # recovery: the SAME engine, fault gone, serves the oracle tokens
    assert eng.generate_batch(prompts, max_new_tokens=4) == want


def test_step_fault_terminal_error_reaches_stream():
    """A streaming client of a failed request gets the terminal error
    raised out of the stream iterator instead of blocking forever."""
    model = _model()
    eng = _engine(model, ResilienceConfig(max_step_retries=0))
    chaos.install_plan(chaos.FaultPlan(seed=0).add(
        "serve.engine_step", "error", prob=1.0))
    try:
        req = eng.submit(_prompts(1)[0], max_new_tokens=4, stream=True)
        got, errs = [], []

        def consume():
            try:
                got.extend(req.stream())
            except RequestFailed as e:
                errs.append(e)
        t = threading.Thread(target=consume)
        t.start()
        eng.run_until_idle(max_steps=50)
        t.join(timeout=30)
    finally:
        chaos.clear_plan()
    assert not t.is_alive()
    assert errs and errs[0].rid == req.rid


def test_nan_guard_contains_garbage_logits():
    """NaN weights => non-finite logits => the sample guard turns the
    step into a nan_logits fault BEFORE any garbage token reaches a
    client; retries burn the budget (the NaN is persistent) and the
    requests fail terminally — drained, not wedged."""
    import jax.numpy as jnp
    model = _model()
    eng = _engine(model, ResilienceConfig(max_step_retries=1,
                                          nan_guard=True))
    k = eng.dec.embed_key
    eng._w = dict(eng._w)
    eng._w[k] = jnp.asarray(eng._w[k]).at[0, 0].set(jnp.nan)
    reqs = [eng.submit(p, max_new_tokens=4) for p in _prompts(2)]
    steps = eng.run_until_idle(max_steps=100)
    assert steps < 100
    for r in reqs:
        assert r.done and len(r.output) == 0    # nothing garbage emitted
        with pytest.raises(RequestFailed, match="nan_logits"):
            r.result(0)
    assert eng.step_faults >= 1
    assert eng.pool.used_blocks() == 0


def test_disarmed_engine_step_fault_escapes():
    """The pre-resilience contract is unchanged when disarmed: the
    exception escapes step() (and the BatchingServer test below pins
    what a front door must then do)."""
    model = _model()
    eng = _engine(model, resilience=False)
    chaos.install_plan(chaos.FaultPlan(seed=0).add(
        "serve.engine_step", "error", at=(1,)))
    try:
        req = eng.submit(_prompts(1)[0], max_new_tokens=2)
        with pytest.raises(chaos.FaultInjected):
            eng.step()
        assert not req.done                   # parked: nobody requeues it
    finally:
        chaos.clear_plan()


def test_drop_cache_frees_parked_pages_and_keys():
    from paddle_tpu.serving import KVBlockPool
    pool = KVBlockPool(8, 4)
    toks = list(range(100, 108))
    pages = pool.allocate(2)
    pool.register_prefix(toks, pages)
    pool.release(pages)
    assert pool.cached_blocks() == 2
    assert pool.drop_cache() == 2
    assert pool.cached_blocks() == 0
    assert pool.free_blocks() == pool.num_blocks
    assert pool.match_prefix(toks + [1]) == ([], 0)


# -- graceful drain + restart replay -------------------------------------------

def test_drain_manifest_roundtrip_and_replay_parity(tmp_path):
    """drain() mid-flight exports every unfinished request (generated
    tokens + deadlines + order + tag); replay into a FRESH engine
    finishes them with outputs bit-identical to a never-interrupted
    run, each drained request's pre-drain tokens a prefix."""
    model = _model()
    prompts = _prompts(4)
    want = _oracle(model, prompts, max_new=8)
    eng = _engine(model, ResilienceConfig())
    reqs = [eng.submit(p, max_new_tokens=8, tag=i,
                       ttft_deadline=60.0)
            for i, p in enumerate(prompts)]
    for _ in range(4):
        eng.step()
    path = str(tmp_path / "manifest.json")
    manifest = eng.drain(deadline_s=0.0, manifest_path=path)
    assert manifest["requests"], "nothing in flight at drain time"
    roundtrip = load_manifest(path)
    assert roundtrip["requests"] == manifest["requests"]
    orders = [e["order"] for e in manifest["requests"]]
    assert orders == sorted(orders)             # submission order kept
    assert all(e["ttft_deadline"] == 60.0 for e in manifest["requests"])
    eng2 = _engine(model, ResilienceConfig())
    handles = replay_manifest(eng2, path)
    eng2.run_until_idle(max_steps=500)
    finals = {r.tag: r.result(0) for r in reqs
              if r.done and r.error is None}
    finals.update({h.tag: h.result(0) for h in handles})
    assert [finals[i] for i in range(4)] == want
    for e in manifest["requests"]:
        assert finals[e["tag"]][:len(e["generated"])] == e["generated"]


def test_drain_honors_deadline_and_blocks_admission():
    """A zero grace budget drains immediately (running requests go to
    the manifest as-is); a drained engine refuses new submissions with
    a typed 'draining' rejection."""
    model = _model()
    eng = _engine(model, ResilienceConfig())
    eng.submit(_prompts(1)[0], max_new_tokens=8)
    eng.step()
    t0 = time.monotonic()
    manifest = eng.drain(deadline_s=0.0)
    assert time.monotonic() - t0 < 5.0          # did not run to completion
    assert len(manifest["requests"]) == 1
    with pytest.raises(AdmissionRejected) as ei:
        eng.submit(_prompts(1)[0], max_new_tokens=2)
    assert ei.value.reason == "draining"
    assert eng.drains == 1


def test_drain_completes_within_generous_deadline():
    """With grace to spare, drain finishes the running set (decode-only)
    and only never-admitted requests remain in the manifest."""
    model = _model()
    prompts = _prompts(2, lens=(5, 4))
    want = _oracle(model, prompts, max_new=4)
    eng = _engine(model, ResilienceConfig())
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.step()                                  # admit into the batch
    manifest = eng.drain(deadline_s=60.0)
    assert manifest["requests"] == []           # everything finished
    assert [r.result(0) for r in reqs] == want


def test_replay_skips_already_complete_entries(tmp_path):
    import json
    from paddle_tpu.serving.resilience import MANIFEST_VERSION
    model = _model()
    eng = _engine(model, ResilienceConfig())
    manifest = {"version": MANIFEST_VERSION, "requests": [
        {"order": 0, "rid": 0, "tag": "done", "prompt": [1, 2],
         "generated": [5, 6], "max_new_tokens": 2, "eos_id": None,
         "ttft_deadline": None, "tpot_deadline": None, "stream": False}]}
    (handle,) = replay_manifest(eng, manifest)
    assert handle.done and handle.result(0) == [5, 6]
    assert not eng.has_work()                   # nothing was enqueued
    # a manifest from a future schema is refused, not misread
    bad = tmp_path / "future.json"
    bad.write_text(json.dumps({"version": 99, "requests": []}))
    with pytest.raises(ValueError, match="version"):
        load_manifest(str(bad))


def test_replay_bypasses_bounded_queue_and_keeps_stream_flag(tmp_path):
    """Replay is a hand-over of ALREADY-admitted work: it must land
    every manifest entry even when the restarted engine's bounded queue
    is smaller than the manifest (no deadlock under block, no silent
    drop under reject/shed), and a stream=True request replays
    streamable."""
    model = _model()
    prompts = _prompts(4)
    want = _oracle(model, prompts, max_new=6)
    eng = _engine(model, ResilienceConfig())
    reqs = [eng.submit(p, max_new_tokens=6, tag=i,
                       stream=(i == 0))
            for i, p in enumerate(prompts)]
    path = str(tmp_path / "m.json")
    manifest = eng.drain(deadline_s=0.0, manifest_path=path)
    assert len(manifest["requests"]) == 4
    assert manifest["requests"][0]["stream"] is True
    eng2 = _engine(model, ResilienceConfig(max_waiting=1,
                                           backpressure="reject"))
    handles = replay_manifest(eng2, path)
    assert len(handles) == 4                    # nothing dropped
    streamed = []
    t = threading.Thread(
        target=lambda: streamed.extend(handles[0].stream()))
    t.start()
    eng2.run_until_idle(max_steps=500)
    t.join(timeout=30)
    assert [h.result(0) for h in handles] == want
    assert streamed == want[0]
    del reqs


def test_submit_generated_validation():
    model = _model()
    eng = _engine(model, ResilienceConfig())
    with pytest.raises(ValueError, match="nothing left to decode"):
        eng.submit([1, 2, 3], max_new_tokens=2, generated=[4, 5])


# -- overload admission control ------------------------------------------------

def test_backpressure_reject_structured_retry_after():
    model = _model()
    prompts = _prompts(4)
    eng = _engine(model, ResilienceConfig(max_waiting=2,
                                          backpressure="reject"))
    eng._e2e_sum, eng._e2e_n = 4.0, 1           # 4s mean service time
    eng.submit(prompts[0], max_new_tokens=2)
    eng.submit(prompts[1], max_new_tokens=2)
    with pytest.raises(AdmissionRejected) as ei:
        eng.submit(prompts[2], max_new_tokens=2)
    err = ei.value
    assert err.reason == "queue_full"
    assert err.queue_depth == 2
    assert err.retry_after_s == pytest.approx(4.0 / eng.config.max_seqs)
    assert eng.shed_total == 1
    eng.run_until_idle()                        # accepted ones still finish


def test_backpressure_block_waits_for_room():
    model = _model()
    prompts = _prompts(2, lens=(5, 4))
    eng = _engine(model, ResilienceConfig(max_waiting=1,
                                          backpressure="block"))
    eng.submit(prompts[0], max_new_tokens=3)
    admitted = []

    def bg():
        admitted.append(eng.submit(prompts[1], max_new_tokens=3))
    t = threading.Thread(target=bg)
    t.start()
    time.sleep(0.1)
    assert not admitted                         # blocked: queue is full
    eng.run_until_idle()                        # driver frees the queue
    t.join(timeout=30)
    assert admitted
    eng.run_until_idle()
    assert admitted[0].done and admitted[0].error is None


def test_backpressure_block_timeout_rejects():
    model = _model()
    eng = _engine(model, ResilienceConfig(max_waiting=1,
                                          backpressure="block",
                                          block_timeout_s=0.1))
    eng.submit(_prompts(1)[0], max_new_tokens=2)
    t0 = time.monotonic()
    with pytest.raises(AdmissionRejected) as ei:
        eng.submit(_prompts(1)[0], max_new_tokens=2)
    assert ei.value.reason == "block_timeout"
    assert 0.05 < time.monotonic() - t0 < 10.0


def test_backpressure_shed_is_slo_aware():
    """The shedder refuses a request whose PREDICTED queue wait blows
    its ttft_deadline — and admits a deadline-free request at the same
    depth (shedding is targeted, not a blanket queue cap)."""
    model = _model()
    prompts = _prompts(4)
    eng = _engine(model, ResilienceConfig(max_waiting=50,
                                          backpressure="shed"))
    eng._e2e_sum, eng._e2e_n = 10.0, 1          # 10s mean service time
    eng.submit(prompts[0], max_new_tokens=2)
    eng.submit(prompts[1], max_new_tokens=2)
    with pytest.raises(AdmissionRejected) as ei:
        eng.submit(prompts[2], max_new_tokens=2, ttft_deadline=0.5)
    err = ei.value
    assert err.reason == "shed"
    assert err.predicted_wait_s > 0.5
    # generous deadline or none: admitted at the same queue depth
    eng.submit(prompts[2], max_new_tokens=2, ttft_deadline=1e6)
    eng.submit(prompts[3], max_new_tokens=2)
    eng.run_until_idle()
    tel = eng.telemetry()
    assert tel["resilience"]["shed_total"] == 1
    assert tel["resilience"]["policy"] == "shed"


def test_no_estimate_no_shed():
    """Before the engine has finished a single request it has no service
    evidence — the SLO shedder must not refuse on a guess."""
    model = _model()
    eng = _engine(model, ResilienceConfig(max_waiting=50,
                                          backpressure="shed"))
    assert eng._service_estimate() is None
    r = eng.submit(_prompts(1)[0], max_new_tokens=2, ttft_deadline=1e-9)
    eng.run_until_idle()
    assert r.done and r.error is None


# -- lifecycle traces on the new paths -----------------------------------------

def test_single_terminal_event_on_requeue_fail_and_shed_paths():
    from paddle_tpu.serving.obs import TERMINAL_EVENT
    model = _model()
    prompts = _prompts(3)
    # (a) requeue: contained fault, request finishes later — ONE finish,
    # and the trace records the non-terminal step_fault_requeue
    eng = _engine(model, ResilienceConfig(max_step_retries=2), obs=True)
    chaos.install_plan(chaos.FaultPlan(seed=0).add(
        "serve.engine_step", "error", at=(2,)))
    try:
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_idle(max_steps=200)
    finally:
        chaos.clear_plan()
    requeued = [r for r in reqs if r.step_retries]
    assert requeued, "fault did not touch a running request"
    for r in reqs:
        assert r.done and r.error is None
        assert len(r.trace.terminal_events()) == 1
    kinds = [e["kind"] for e in requeued[0].trace.events]
    assert "step_fault_requeue" in kinds
    assert eng.obs.counters["requeued"] == sum(r.step_retries
                                               for r in reqs)
    # the fault landed a step record + a latched step_fault flight dump
    assert [d for d in eng.obs.dumps if d["reason"] == "step_fault"]
    faulted = [s for s in eng.obs._steps if s.get("fault")]
    assert faulted and faulted[0]["fault"]["kind"] == "chaos"

    # (b) terminal failure past budget — ONE finish, reason "error"
    eng2 = _engine(model, ResilienceConfig(max_step_retries=0), obs=True)
    chaos.install_plan(chaos.FaultPlan(seed=0).add(
        "serve.engine_step", "error", prob=1.0))
    try:
        r2 = eng2.submit(prompts[0], max_new_tokens=4)
        eng2.run_until_idle(max_steps=50)
    finally:
        chaos.clear_plan()
    assert r2.done and r2.error is not None
    terms = r2.trace.terminal_events()
    assert len(terms) == 1 and terms[0]["reason"] == "error"
    assert eng2.obs.counters["failed"] == 1

    # (c) shed at submit — the refused request still has a complete
    # lifecycle: submit + ONE terminal finish, reason "shed"
    eng3 = _engine(model, ResilienceConfig(max_waiting=1,
                                           backpressure="reject"),
                   obs=True)
    eng3.submit(prompts[0], max_new_tokens=2)
    with pytest.raises(AdmissionRejected):
        eng3.submit(prompts[1], max_new_tokens=2)
    shed_lives = [l for l in eng3.obs._done if l["reason"] == "shed"]
    assert len(shed_lives) == 1
    ev_kinds = [e["kind"] for e in shed_lives[0]["events"]]
    assert ev_kinds[0] == "submit"
    assert ev_kinds.count(TERMINAL_EVENT) == 1
    assert eng3.obs.counters["shed"] == 1
    eng3.run_until_idle()


def test_armed_resilience_keeps_engine_generate_parity():
    """Acceptance: arming the resilience plane (no faults) changes no
    tokens — engine-vs-generate parity stays bit-identical."""
    model = _model()
    prompts = _prompts(5)
    want = _oracle(model, prompts, max_new=6)
    eng = _engine(model, ResilienceConfig(max_waiting=64,
                                          backpressure="shed"),
                  max_seqs=3, obs=True)
    got = eng.generate_batch(prompts, max_new_tokens=6)
    assert got == want
    assert eng.step_faults == 0 and eng.shed_total == 0


# -- BatchingServer wedge fix (satellite) --------------------------------------

def test_batching_server_survives_engine_fault():
    """The silent-wedge bug: an exception escaping the engine-driver
    step loop used to kill the thread and park queued Futures forever.
    Now every pending request fails through the terminal-error path,
    the Futures raise, the thread survives, and the server keeps
    serving once the fault clears."""
    from paddle_tpu.inference import BatchingServer, create_llm_predictor
    model = _model()
    prompts = _prompts(3)
    want = _oracle(model, prompts, max_new=4)
    pred = create_llm_predictor(model, max_new_tokens=4)
    assert pred.engine.resilience is None       # disarmed: step() raises
    server = BatchingServer(pred)
    try:
        chaos.install_plan(chaos.FaultPlan(seed=0).add(
            "serve.engine_step", "error", prob=1.0))
        try:
            futs = [server.submit([np.asarray(p, np.int32)])
                    for p in prompts]
            for f in futs:
                with pytest.raises(RequestFailed):
                    f.result(timeout=120)       # resolves, never parks
        finally:
            chaos.clear_plan()
        assert server._worker.is_alive()        # the driver survived
        assert pred.engine.pool.used_blocks() == 0
        # same server, fault gone: full service
        futs2 = [server.submit([np.asarray(p, np.int32)])
                 for p in prompts]
        got = [f.result(timeout=120)[0].tolist() for f in futs2]
        assert got == want
    finally:
        server.close()


# -- chaos drill (fast mode) ---------------------------------------------------

def test_chaos_drill_serve_inprocess_deterministic():
    """The --serve drill's in-process phase, twice with one seed: the
    stable subset is bit-identical (replayable containment drills)."""
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    chaos_drill = importlib.import_module("chaos_drill")
    a = chaos_drill.run_serve_drill(seed=91, verbose=False,
                                    supervised=False)
    b = chaos_drill.run_serve_drill(seed=91, verbose=False,
                                    supervised=False)
    assert a["ok"] and a["stable"] == b["stable"]
    assert a["stable"]["contained_faults"] == 1
    assert a["stable"]["budget_failures"] == 6


def test_chaos_drill_serve_supervised_kill_restart_replay():
    """Acceptance: the supervised kill→drain→restart→replay loop —
    every in-flight request finishes after the restart with greedy
    token-prefix consistency, zero requests parked."""
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    chaos_drill = importlib.import_module("chaos_drill")
    rep = chaos_drill.run_serve_drill(seed=1234, verbose=False,
                                      supervised=True)
    assert rep["ok"]
    assert rep["stable"]["manifest_requests"] > 0
    assert rep["stable"]["replay_crc"] == rep["stable"]["oracle_crc"]
    assert rep["supervised"]["generations"] == 2


# -- disarmed-path overhead ----------------------------------------------------

def test_resilience_disabled_path_overhead_microbench():
    """The disarm contract: resilience off means one `is None` check on
    the hot seams and the disabled record_* helpers cost a single
    boolean check (same 20us/call budget the obs plane pins)."""
    import time as _time

    from paddle_tpu.profiler import instrument, metrics as _metrics
    model = _model()
    eng = _engine(model, resilience=False)
    assert eng.resilience is None
    req = eng.submit(_prompts(1)[0], max_new_tokens=3)
    eng.run_until_idle()
    assert req.result(0) is not None
    assert not _metrics.metrics_enabled()
    n = 20_000
    budgets = []
    for fn in (lambda: instrument.record_serve_step_fault("chaos"),
               lambda: instrument.record_serve_request_retry("step_fault"),
               lambda: instrument.record_serve_shed("shed"),
               lambda: instrument.record_serve_drain(0.5),
               lambda: instrument.record_serve_engine_restart()):
        t0 = _time.perf_counter()
        for _ in range(n):
            fn()
        budgets.append((_time.perf_counter() - t0) / n)
    for per in budgets:
        assert per < 20e-6, f"disabled resilience record {per:.2e}s/call"


def test_new_metric_families_land_in_registry():
    from paddle_tpu.profiler import instrument, metrics as _metrics
    for name in ("serve_step_faults_total", "serve_request_retries_total",
                 "serve_shed_total", "serve_drain_seconds",
                 "serve_engine_restarts_total"):
        assert name in instrument.CATALOG
    _metrics.reset_registry()
    _metrics.enable_metrics()
    try:
        instrument.record_serve_step_fault("nan_logits")
        instrument.record_serve_request_retry("step_fault")
        instrument.record_serve_shed("shed")
        instrument.record_serve_drain(0.25)
        instrument.record_serve_engine_restart()
        snap = _metrics.get_registry().snapshot()
        assert snap["serve_step_faults_total"]["kind=nan_logits"] == 1
        assert snap["serve_request_retries_total"]["reason=step_fault"] \
            == 1
        assert snap["serve_shed_total"]["policy=shed"] == 1
        assert snap["serve_engine_restarts_total"] == 1
    finally:
        _metrics.disable_metrics()
        _metrics.reset_registry()
