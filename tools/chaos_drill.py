#!/usr/bin/env python
"""Seeded end-to-end chaos drill for the resilience layer.

Injects three faults into a short real ``Model.fit`` run — one store
timeout (retried), one corrupted checkpoint shard (detected at load,
falls back to last-good), one NaN loss (step skipped by the guard) — and
asserts all three events land in the ``resilience_*`` metrics. The whole
drill is driven by one integer seed: run it twice with the same seed and
every fault fires at the same probe hit, so flake reports are replayable
bit-for-bit.

    JAX_PLATFORMS=cpu python tools/chaos_drill.py [--seed 1234] [--json]

``--preempt`` runs the preemption drill instead: a supervised training
worker (tools/supervise.py wrapping tests/preempt_worker.py) gets a
seeded chaos preemption notice at an exact step boundary, lands its
emergency checkpoint, exits with PREEMPTED_EXIT_CODE, is restarted by
the supervisor, resumes at the saved step (not zero), and finishes —
deterministically per seed (same resumed step, same final weight hash).
By default the worker trains through the compiled SpmdTrainer step with
a persistent AOT program cache (paddle_tpu.aot) threaded across the
generations: the drill additionally asserts generation 0 exported the
step program, the restarted generation deserialized it (cache hit, no
re-trace) and reported a LOWER cold start. ``--no-aot`` restores the
eager PR-5 worker.

    JAX_PLATFORMS=cpu python tools/chaos_drill.py --preempt [--seed 1234]

``--flight`` runs the serving flight-recorder drill: a seeded
``serve.kv_alloc`` exhaustion against an armed observability plane
(paddle_tpu.serving.obs) must produce EXACTLY one well-formed flight
dump whose last step-plan record names the exhaustion — and the
armed-but-quiet control run (same engine, same workload, no fault) must
produce none. Deterministic per seed: two runs yield the same stable
dump subset (reason, exhaustion site/phase, step/request ids).

    JAX_PLATFORMS=cpu python tools/chaos_drill.py --flight [--seed 1234]

``--serve`` runs the serving-resilience drill
(paddle_tpu.serving.resilience), two phases. In-process: a seeded
``serve.engine_step`` fault against an armed resilience plane must be
contained — exactly one fault, every affected request retried once
(requeued for prefix recompute), final outputs BIT-IDENTICAL to a
fault-free run, driver never sees the exception; an always-faulting
plan must converge to clean terminal ``RequestFailed`` errors (bounded
retry budget, no hang) and leave the engine reusable. Supervised: a
serving worker (tools/supervise.py wrapping tests/serve_worker.py)
takes a seeded preemption notice mid-serving, drains its in-flight
requests into the shared drain manifest within the grace window, exits
PREEMPTED_EXIT_CODE, is restarted, REPLAYS the manifest and finishes
every request — with greedy token-prefix consistency across the
restart (the final outputs equal the fault-free oracle, and each
drained request's pre-kill tokens are a prefix of its final output).
Deterministic per seed: the ``stable`` report subset is bit-identical
across runs.

    JAX_PLATFORMS=cpu python tools/chaos_drill.py --serve [--seed 1234]

``--disagg`` runs the prefill-replica-death drill for the
disaggregated fleet: 1 prefill + 2 decode replicas serve a
shared-prefix workload; once the first KV-page hand-off has landed, a
seeded ``serve.engine_step`` fault kills the PREFILL replica. With no
prefill survivor the salvage manifest replays onto decode survivors
via prompt recompute (the manifest fallback) — zero parked, outputs
equal the fault-free oracle, and the headless fleet still serves fresh
requests. Deterministic per seed.

    JAX_PLATFORMS=cpu python tools/chaos_drill.py --disagg [--seed 1234]

``--mem`` runs the memory-pressure drill: an armed memory watcher
(paddle_tpu.profiler.memwatch) with a seeded growth workload filling the
``kv_pages`` pool must produce EXACTLY one well-formed pressure dump
whose detail names ``kv_pages`` as the pool that crossed the high
watermark — and a below-watermark control run must produce none; a
seeded ``mem.snapshot`` chaos fault must be swallowed (snapshot returns
None, never raises into the driver). Deterministic per seed.

    JAX_PLATFORMS=cpu python tools/chaos_drill.py --mem [--seed 1234]

``--lockcheck`` runs the armed ordered-lock drill
(paddle_tpu.serving.locking, the runtime twin of the CCY101 lint
rule): a real engine serves a seeded workload with PADDLE_LOCKCHECK
enforcement armed — zero violations, tokens bit-identical to the
disarmed run — and then a planted observer->engine lock inversion must
raise ``LockOrderViolation`` deterministically, naming the planted
edge. Stable per seed.

    JAX_PLATFORMS=cpu python tools/chaos_drill.py --lockcheck [--seed 1234]

``--partition`` runs the fault-domain partition drill
(paddle_tpu.serving.transport + membership): a 1 prefill + 2 decode
fleet on the armed transport serves a seeded workload through BOTH
lease verdicts. Phase A partitions a decode replica and heals it
INSIDE its lease: the replica goes live -> suspect -> live, dispatch
avoids it while suspect, and NO salvage ever runs — the healed
partition cannot double-decode. Phase B partitions it past the lease:
exactly one suspect -> dead transition, exactly one salvage record
(reason ``lease_expired``), zero parked, merged outputs equal the
fault-free oracle. Deterministic per seed.

    JAX_PLATFORMS=cpu python tools/chaos_drill.py --partition [--seed 1234]

``--lossy`` runs the fault-domain lossy-link drill: the same fleet
under a seeded 5% drop + 5% dup + 5% delay plan at the
``transport.send`` seam. The dedup window and ack-tracked retransmits
must absorb every fault: the fleet converges, zero requests park, no
request ever receives a token twice (per-request callback counts equal
output lengths), outputs equal the fault-free oracle, and a second run
from the same seed reproduces the report bit-identically.

    JAX_PLATFORMS=cpu python tools/chaos_drill.py --lossy [--seed 1234]

``--wirecheck`` runs the armed wire-contract drill
(paddle_tpu.serving.wire, the runtime twin of the WIR1xx lint rules):
the fleet-obs and elastic drills run twice each — sealing twin
disarmed, then armed via ``wire.arm`` — and their stable reports
(including the replayed tokens-crc) must be bit-identical; then a
planted corrupt ``kv_export_record`` (one undeclared key smuggled in,
one hash-chain prefix key degraded to a float) must die in a child
process with exit code 1 and a byte-stable ``WireContractViolation``
message, twice. Stable per seed.

    JAX_PLATFORMS=cpu python tools/chaos_drill.py --wirecheck [--seed 1234]

Exit code 0 = every exercised recovery path verified.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def run_drill(seed: int = 1234, verbose: bool = True):
    """Returns the drill report dict (also asserted internally)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.hapi import Model
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.profiler import metrics as _metrics
    from paddle_tpu.resilience import (CheckpointManager, FaultPlan,
                                       RetryPolicy, StepGuard, chaos)
    from paddle_tpu.distributed.store import TCPStore

    _metrics.reset_registry()
    _metrics.enable_metrics()
    paddle.seed(seed)
    np.random.seed(seed % (2 ** 31))

    # one plan, three faults, every trigger hit-indexed => deterministic
    plan = FaultPlan(seed=seed)
    plan.add("store.get", "error", "TimeoutError", at=(1,))
    plan.add("ckpt.shard_bytes", "corrupt", at=(3,))  # 2nd save's 1st shard
    plan.add("train.loss", "nan", at=(4,))
    chaos.install_plan(plan)

    report = {"seed": seed}
    try:
        # -- pillar 2: a store op that times out once, then succeeds ------
        store = TCPStore(is_master=True, world_size=1, rank=0,
                         timeout=5.0,
                         retry_policy=RetryPolicy(max_attempts=3,
                                                  base_delay=0.01,
                                                  seed=seed))
        try:
            store.set("drill/key", b"payload")
            assert store.get("drill/key", timeout=1.0) == b"payload"
        finally:
            store.stop()

        # -- pillars 1+3: fit with guard + chaos, checkpoint with fallback
        x = np.random.randn(8, 4).astype(np.float32)
        y = (x @ np.random.randn(4, 1)).astype(np.float32)
        net = nn.Linear(4, 1)
        model = Model(net)
        model.prepare(optimizer.SGD(learning_rate=0.01,
                                    parameters=net.parameters()),
                      nn.MSELoss())
        guard = StepGuard(nan_action="skip")

        with tempfile.TemporaryDirectory() as ckpt_root:
            mgr = CheckpointManager(ckpt_root, keep=2)
            ds = TensorDataset([paddle.to_tensor(x), paddle.to_tensor(y)])
            # save after each epoch; chaos corrupts a shard of save #2
            for epoch_step in range(2):
                model.fit(ds, batch_size=4, epochs=1, verbose=0,
                          step_guard=guard)
                mgr.save({"w": net.weight, "b": net.bias},
                         step=epoch_step)
            model.fit(ds, batch_size=4, epochs=1, verbose=0,
                      step_guard=guard)

            # load falls back: newest (step 1) is corrupt, step 0 is good
            target = {"w": net.weight, "b": net.bias}
            loaded = mgr.load_latest(target)
            report["loaded_step"] = loaded
            assert loaded == 0, f"expected fallback to step 0, got {loaded}"

        snap = _metrics.get_registry().snapshot()
        retries = sum(snap.get("resilience_retries_total", {}).values())
        faults = snap.get("resilience_faults_injected_total", {})
        ckpt_ev = snap.get("resilience_ckpt_events_total", {})
        guard_ev = snap.get("resilience_guard_events_total", {})
        report.update({
            "retries_total": retries,
            "faults_injected": faults,
            "ckpt_events": ckpt_ev,
            "guard_events": guard_ev,
            "fired": [list(f) for f in plan.fired],
        })
        assert retries >= 1, "store retry never happened"
        assert ckpt_ev.get("event=fallback", 0) >= 1, "no ckpt fallback"
        assert ckpt_ev.get("event=corrupt_detected", 0) >= 1
        assert guard_ev.get("kind=nan,action=skip", 0) >= 1, \
            "guard never skipped the NaN step"
        assert len(guard.events) == 1 and guard.events[0].kind == "nan"
        report["ok"] = True
        if verbose:
            print(f"chaos drill (seed={seed}): store retry x{int(retries)}, "
                  f"ckpt fallback -> step {report['loaded_step']}, "
                  "NaN step skipped — all three recovery paths verified")
        return report
    finally:
        chaos.clear_plan()
        _metrics.disable_metrics()
        _metrics.reset_registry()


def run_preempt_drill(seed: int = 1234, steps: int = 8, preempt_at: int = 4,
                      persist_every: int = 2, verbose: bool = True,
                      work_dir: str = None, aot: bool = False):
    """The kill→restart→resume loop, end to end, under the supervisor.

    Generation 0 of tests/preempt_worker.py takes a seeded chaos
    preemption notice at the step-`preempt_at` boundary, emergency-saves,
    and exits PREEMPTED_EXIT_CODE; tools/supervise.py restarts it;
    generation 1 resumes at the saved step and finishes. Asserts the
    resumed step, the exit-cause classification, and (per seed) the
    deterministic final weight hash. Returns the report dict.

    aot=True additionally trains through the compiled SpmdTrainer step
    with a persistent AOT program cache threaded across generations
    (supervise.py --aot-cache): asserts generation 0 exported the step
    program (a miss), the restarted generation deserialized it (>= 1
    hit, NO fresh export), and the restart's cold start — supervisor
    spawn to first program ready — beat generation 0's, which paid the
    full trace+compile+export."""
    import re
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ctx = tempfile.TemporaryDirectory() if work_dir is None else None
    root = work_dir if work_dir is not None else ctx.name
    try:
        ckpt = os.path.join(root, "ckpt")
        markers = os.path.join(root, "markers")
        reports = os.path.join(root, "reports")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PADDLE_CHAOS_PLAN", None)  # the worker arms its own plan
        sup_args = ["--max-restarts", "2", "--seed", str(seed),
                    "--report-dir", reports]
        worker_args = []
        if aot:
            sup_args += ["--aot-cache", os.path.join(root, "aot_cache")]
            worker_args += ["--aot"]
        r = subprocess.run(
            [_sys.executable, os.path.join(repo, "tools", "supervise.py"),
             *sup_args, "--",
             _sys.executable, os.path.join(repo, "tests",
                                           "preempt_worker.py"),
             ckpt, "--steps", str(steps), "--persist-every",
             str(persist_every), "--preempt-at", str(preempt_at),
             "--mode", "chaos", "--seed", str(seed),
             "--marker-dir", markers, *worker_args],
            capture_output=True, timeout=300, env=env, cwd=repo)
        err = r.stderr.decode()
        assert r.returncode == 0, \
            f"supervised run failed rc={r.returncode}:\n{err}"
        got = sorted(os.listdir(markers))
        assert f"emergency.{preempt_at}" in got, \
            f"no emergency checkpoint marker: {got}"
        assert "gen0.resume0" in got and \
            f"gen1.resume{preempt_at}" in got, \
            f"generation 1 did not resume at step {preempt_at}: {got}"
        done = [m for m in got if m.startswith("done.")]
        assert done, f"run never finished: {got}"
        final_step, w_hash = re.match(r"done\.(\d+)\.w(\d+)",
                                      done[0]).groups()
        with open(os.path.join(reports, "crash_report_0.json")) as f:
            rep0 = json.load(f)
        assert rep0["cause"] == "preempted" and rep0["exit_code"] == 84, \
            f"generation 0 misclassified: {rep0['cause']}"
        assert not os.path.exists(
            os.path.join(reports, "crash_report_2.json")), \
            "more than one restart — resume did not stick"
        # the good ledger must contain the emergency step
        with open(os.path.join(ckpt, "_GOOD.json")) as f:
            good = json.load(f)
        assert preempt_at in good, f"emergency step not in ledger: {good}"
        report = {"seed": seed, "resumed_step": preempt_at,
                  "final_step": int(final_step), "w_hash": int(w_hash),
                  "generations": 2, "ok": True}
        if aot:
            with open(os.path.join(reports,
                                   "crash_report_1.json")) as f:
                rep1 = json.load(f)
            aot0, aot1 = rep0.get("aot"), rep1.get("aot")
            assert aot0 and aot0["misses"] >= 1 and \
                aot0["fallbacks"] == 0, \
                f"generation 0 never exported the step program: {aot0}"
            assert aot1 and aot1["hits"] >= 1 and \
                aot1["misses"] == 0 and aot1["fallbacks"] == 0, \
                f"restarted generation did not hit the AOT cache: {aot1}"
            # the deterministic timing signal: gen1's deserialize must
            # beat gen0's trace+export (both measured INSIDE each
            # process, immune to jax-import and machine-load noise that
            # dominates toy-config wall clocks)
            load1 = sum(p.get("load_seconds", 0.0)
                        for p in aot1["programs"].values())
            export0 = sum(p.get("export_seconds", 0.0)
                          for p in aot0["programs"].values())
            assert 0 < load1 < export0, \
                f"restart deserialize ({load1:.3f}s) did not beat " \
                f"generation 0's trace+export ({export0:.3f}s)"
            # wall-clock cold start: asserted with a noise budget —
            # on the toy config both generations' cold starts are
            # dominated by the shared interpreter+jax startup, so a
            # loaded machine can legitimately wobble the difference
            cold0 = aot0["cold_start_seconds"]
            cold1 = aot1["cold_start_seconds"]
            assert cold0 is not None and cold1 is not None and \
                cold1 < cold0 * 1.5 + 2.0, \
                f"restart cold start {cold1}s blew past " \
                f"generation 0's {cold0}s beyond any startup noise"
            report["aot"] = {"gen0": aot0, "gen1": aot1,
                             "cold_start_gen0_s": cold0,
                             "cold_start_gen1_s": cold1}
        if verbose:
            print(f"preempt drill (seed={seed}): notice at step "
                  f"{preempt_at} -> emergency ckpt -> supervisor restart "
                  f"-> resumed at {preempt_at} -> finished at "
                  f"{final_step} (w_hash={w_hash}) — kill/restart/resume "
                  "verified")
            if aot:
                print(f"  aot: gen0 exported (cold start {cold0}s), gen1 "
                      f"hit x{report['aot']['gen1']['hits']} (cold start "
                      f"{cold1}s) — restart skipped the re-trace")
        return report
    finally:
        if ctx is not None:
            ctx.cleanup()


def run_flight_drill(seed: int = 1234, verbose: bool = True):
    """Seeded serving flight-recorder drill (see module docstring).

    Phase 1 (armed-but-quiet): the observability plane is on, no fault
    is installed — asserts ZERO dumps (an idle postmortem layer that
    dumps on healthy traffic would be noise nobody reads). Phase 2: a
    hit-indexed ``serve.kv_alloc`` error (the deterministic
    pool-exhaustion drill) — asserts exactly ONE well-formed dump whose
    LAST step record carries the exhaustion in its plan, so the
    postmortem always contains the step that explains itself. Returns a
    report whose ``stable`` subset is bit-identical per seed."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import EngineConfig, ObsConfig, ServingEngine

    paddle.seed(seed % (2 ** 31))
    cfg = LlamaConfig.tiny(vocab_size=61, hidden_size=32, layers=2,
                           heads=4, kv_heads=2, seq=64)
    cfg.use_flash_attention = False
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 61, (6 + i % 4,)).tolist() for i in range(4)]

    def run(fault: bool, dump_path: str):
        eng = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8,
            enable_prefix_cache=False,
            obs=ObsConfig(flight_steps=32, flight_requests=16,
                          dump_path=dump_path)))
        if fault:
            chaos.install_plan(chaos.FaultPlan(seed=seed).add(
                "serve.kv_alloc", "error", at=(2,)))
        try:
            reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            eng.run_until_idle(max_steps=400)
        finally:
            chaos.clear_plan()
        assert all(r.done for r in reqs), "drill workload never drained"
        # request ids are process-global; the determinism contract is on
        # SUBMISSION order, so the stable report normalizes through this
        return eng, {r.rid: i for i, r in enumerate(reqs)}

    with tempfile.TemporaryDirectory() as root:
        quiet_path = os.path.join(root, "quiet_flight.json")
        quiet, _ = run(fault=False, dump_path=quiet_path)
        assert quiet.obs.dumps == [], \
            f"armed-but-quiet run dumped: {quiet.obs.dumps}"
        assert not os.path.exists(quiet_path), \
            "armed-but-quiet run wrote a flight file"

        dump_path = os.path.join(root, "flight.json")
        faulted, rid_of = run(fault=True, dump_path=dump_path)
        assert len(faulted.obs.dumps) == 1, \
            f"expected exactly one flight dump, got {faulted.obs.dumps}"
        with open(dump_path) as f:
            dump = json.load(f)
        for key in ("version", "reason", "steps", "requests",
                    "live_requests", "telemetry", "unix_time"):
            assert key in dump, f"flight dump missing {key!r}"
        assert dump["reason"] == "pool_exhausted", dump["reason"]
        last = dump["steps"][-1]
        exh = last["plan"]["exhaustion"]
        assert exh and exh[0]["site"] == "serve.kv_alloc", \
            f"last step record does not name the exhaustion: {last}"
        report = {
            "seed": seed, "ok": True,
            "stable": {
                "reason": dump["reason"],
                "exhaustion": [{"site": e["site"],
                                "req": rid_of[e["rid"]],
                                "phase": e["phase"], "kind": e["kind"],
                                "need_pages": e["need_pages"]}
                               for e in exh],
                "exhaustion_step": last["step"],
                "steps_in_dump": len(dump["steps"]),
                "finished_requests": [rid_of[r["rid"]]
                                      for r in dump["requests"]],
            },
        }
    if verbose:
        print(f"flight drill (seed={seed}): quiet run 0 dumps; seeded "
              f"serve.kv_alloc exhaustion -> 1 dump at step "
              f"{report['stable']['exhaustion_step']} naming "
              f"{report['stable']['exhaustion'][0]['site']} — flight "
              "recorder verified")
    return report


def run_mem_drill(seed: int = 1234, verbose: bool = True):
    """Seeded memory-pressure drill (see module docstring).

    Phase 1 (armed-but-quiet): pools grow but stay under the watermark —
    ZERO dumps. Phase 2: the kv_pages pool grows past the limit fraction
    — exactly ONE well-formed dump whose detail names kv_pages as the
    growth culprit, latched (further pressure snapshots do not re-dump).
    Phase 3: a seeded ``mem.snapshot`` chaos error is swallowed — the
    snapshot returns None and the driver loop it models never sees an
    exception. Returns a report whose ``stable`` subset is bit-identical
    per seed."""
    import numpy as np

    from paddle_tpu.profiler.memwatch import MemoryWatcher, MemWatchConfig
    from paddle_tpu.resilience import chaos

    rng = np.random.default_rng(seed)
    base = np.ones((64, 64), np.float32)          # 16 KiB of "params"

    def run(grow_pages: int, dump_path: str, limit: int):
        # stats_fn pins bytes_in_use to the tagged pools: the drill's
        # pressure curve depends only on its own seeded growth, not on
        # whatever the host process happens to have live
        w = MemoryWatcher(MemWatchConfig(
            ring_steps=32, watermark=0.9, dump_path=dump_path,
            limit_bytes=limit, stats_fn=lambda: {"bytes_in_use": 0}))
        pages = []
        w.register_pool("params", lambda: base)
        w.register_pool("kv_pages", lambda: pages)
        for i in range(grow_pages):
            pages.append(np.full((256,), float(rng.integers(1, 9)),
                                 np.float32))  # 1 KiB per page
            w.snapshot(step=i)
        return w

    limit = base.nbytes + 64 * 1024  # params + 64 pages of headroom
    with tempfile.TemporaryDirectory() as root:
        quiet_path = os.path.join(root, "quiet_memwatch.json")
        quiet = run(grow_pages=8, dump_path=quiet_path, limit=limit)
        assert quiet.dumps == [], \
            f"below-watermark run dumped: {quiet.dumps}"
        assert not os.path.exists(quiet_path), \
            "below-watermark run wrote a dump file"

        dump_path = os.path.join(root, "memwatch.json")
        hot = run(grow_pages=80, dump_path=dump_path, limit=limit)
        assert len(hot.dumps) == 1, \
            f"expected exactly one pressure dump, got {hot.dumps}"
        with open(dump_path) as f:
            dump = json.load(f)
        for key in ("version", "kind", "reason", "detail", "steps",
                    "watermarks", "counters", "unix_time"):
            assert key in dump, f"memwatch dump missing {key!r}"
        assert dump["kind"] == "memwatch" and \
            dump["reason"] == "near_oom", dump["reason"]
        detail = dump["detail"]
        assert detail["pool"] == "kv_pages", \
            f"dump blamed {detail['pool']!r}, expected kv_pages"
        assert detail["fraction"] >= 0.9
        cross_step = dump["steps"][-1]["step"]

        # phase 3: a chaos fault on the snapshot path is swallowed
        chaos.install_plan(chaos.FaultPlan(seed=seed).add(
            "mem.snapshot", "error", at=(1,)))
        try:
            got = hot.snapshot(step=999)
        finally:
            chaos.clear_plan()
        assert got is None and hot.snapshot_failures == 1, \
            "chaos-faulted snapshot leaked instead of being swallowed"
        assert len(hot.dumps) == 1, "latched near_oom re-dumped"

    report = {
        "seed": seed, "ok": True,
        "stable": {
            "reason": dump["reason"],
            "pool": detail["pool"],
            "watermark": detail["watermark"],
            "cross_step": cross_step,
            "steps_in_dump": len(dump["steps"]),
            "pools_at_cross": {k: v for k, v in
                               sorted(detail["pools"].items())},
        },
    }
    if verbose:
        print(f"mem drill (seed={seed}): quiet run 0 dumps; kv_pages "
              f"growth crossed the {detail['watermark']:.0%} watermark at "
              f"step {cross_step} -> 1 dump naming kv_pages; chaos "
              "snapshot fault swallowed — memory pressure plane verified")
    return report


def run_serve_drill(seed: int = 1234, verbose: bool = True,
                    supervised: bool = True, work_dir: str = None):
    """Seeded serving-resilience drill (see module docstring).

    Phase 1 (in-process): containment — one injected ``serve.engine_step``
    fault is absorbed (bit-identical outputs, exactly one contained
    retry round), and an always-faulting plan converges to clean
    terminal errors within the retry budget. Phase 2 (supervised,
    ``supervised=True``): the kill→drain→restart→replay loop through
    tools/supervise.py and tests/serve_worker.py, asserting every
    request finishes after the restart with greedy token-prefix
    consistency. Returns a report whose ``stable`` subset is
    bit-identical per seed."""
    import subprocess
    import sys as _sys
    import zlib

    import numpy as np

    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import (EngineConfig, ResilienceConfig,
                                    RequestFailed, ServingEngine)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tests"))
    import serve_worker

    model = serve_worker.build_model(seed)
    prompts = serve_worker.build_prompts(seed, 6)
    max_new = 8

    def run(fault_plan, retries=2):
        eng = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8,
            resilience=ResilienceConfig(max_step_retries=retries)))
        if fault_plan is not None:
            chaos.install_plan(fault_plan)
        try:
            reqs = [eng.submit(p, max_new_tokens=max_new, tag=i)
                    for i, p in enumerate(prompts)]
            eng.run_until_idle(max_steps=400)
        finally:
            chaos.clear_plan()
        return eng, reqs

    # -- phase 1a: fault-free oracle, then one contained fault ----------------
    _, oracle_reqs = run(None)
    oracle = [r.result(0) for r in oracle_reqs]
    plan = chaos.FaultPlan(seed=seed).add("serve.engine_step", "error",
                                          at=(2,))
    eng, reqs = run(plan)
    got = [r.result(0) for r in reqs]
    assert got == oracle, "contained fault changed tokens"
    assert eng.step_faults == 1, \
        f"expected exactly one contained fault, got {eng.step_faults}"
    assert [f[0] for f in plan.fired] == ["serve.engine_step"]
    assert eng.requests_failed == 0
    assert eng.pool.used_blocks() == 0, "containment leaked pages"
    retried = eng.request_retries

    # -- phase 1b: past-budget => clean terminal errors, engine reusable ------
    always = chaos.FaultPlan(seed=seed).add("serve.engine_step", "error",
                                            prob=1.0)
    eng2, reqs2 = run(always, retries=1)
    failures = 0
    for r in reqs2:
        assert r.done, "past-budget request left hanging"
        try:
            r.result(0)
        except RequestFailed:
            failures += 1
    assert failures == len(reqs2), \
        f"only {failures}/{len(reqs2)} requests failed cleanly"
    assert eng2.pool.used_blocks() == 0
    # the driver survived: with chaos cleared the SAME engine serves again
    again = eng2.submit(prompts[0], max_new_tokens=max_new)
    eng2.run_until_idle(max_steps=200)
    assert again.result(0) == oracle[0], "engine unusable after failures"

    report = {
        "seed": seed, "ok": True,
        "stable": {
            "oracle_crc": zlib.crc32(np.asarray(
                [t for o in oracle for t in o], np.int64).tobytes()),
            "contained_faults": eng.step_faults,
            "contained_retries": retried,
            "budget_failures": failures,
        },
    }
    if verbose:
        print(f"serve drill (seed={seed}): 1 injected engine-step fault "
              f"contained ({retried} requests requeued, outputs "
              f"bit-identical); always-faulting plan -> {failures} clean "
              "terminal errors, engine reusable — containment verified")
    if not supervised:
        return report

    # -- phase 2: supervised kill -> drain -> restart -> replay ---------------
    ctx = tempfile.TemporaryDirectory() if work_dir is None else None
    root = work_dir if work_dir is not None else ctx.name
    try:
        markers = os.path.join(root, "markers")
        reports = os.path.join(root, "reports")
        results = os.path.join(root, "results.json")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PADDLE_CHAOS_PLAN", None)  # the worker arms its own plan
        env.pop("PADDLE_SERVE_DRAIN_MANIFEST", None)  # supervisor threads it
        r = subprocess.run(
            [_sys.executable, os.path.join(repo, "tools", "supervise.py"),
             "--max-restarts", "2", "--seed", str(seed),
             "--report-dir", reports, "--",
             _sys.executable, os.path.join(repo, "tests",
                                           "serve_worker.py"),
             "--seed", str(seed), "--requests", str(len(prompts)),
             "--max-new", str(max_new), "--preempt-at", "3",
             "--results", results, "--marker-dir", markers],
            capture_output=True, timeout=600, env=env, cwd=repo)
        err = r.stderr.decode()
        assert r.returncode == 0, \
            f"supervised serving run failed rc={r.returncode}:\n{err}"
        got_markers = sorted(os.listdir(markers))
        drained = [m for m in got_markers if m.startswith("drained.")]
        assert drained, f"generation 0 never drained: {got_markers}"
        n_manifest = int(drained[0].split(".", 1)[1])
        assert n_manifest > 0, "drain exported zero requests (kill " \
            "landed after the workload finished — preempt-at too late)"
        assert f"gen1.replay{n_manifest}" in got_markers, \
            f"generation 1 did not replay the manifest: {got_markers}"
        with open(os.path.join(reports, "crash_report_0.json")) as f:
            rep0 = json.load(f)
        assert rep0["cause"] == "preempted" and rep0["exit_code"] == 84, \
            f"generation 0 misclassified: {rep0['cause']}"
        assert rep0.get("drain") and \
            rep0["drain"]["requests"] == n_manifest, \
            f"crash report missed the drain hand-off: {rep0.get('drain')}"
        assert not os.path.exists(
            os.path.join(reports, "crash_report_2.json")), \
            "more than one restart — replay did not stick"
        with open(results) as f:
            finals = json.load(f)
        assert len(finals) == len(prompts), \
            f"requests parked across the restart: {sorted(finals)}"
        # greedy token-prefix consistency: the post-restart outputs ARE
        # the fault-free outputs (replayed tokens rode along as the
        # prefix, the restarted engine greedily continued them)
        got_final = [finals[str(i)] for i in range(len(prompts))]
        assert got_final == oracle, \
            "restart replay diverged from the fault-free oracle"
        report["stable"]["manifest_requests"] = n_manifest
        report["stable"]["replay_crc"] = zlib.crc32(np.asarray(
            [t for o in got_final for t in o], np.int64).tobytes())
        report["supervised"] = {
            "generations": 2,
            "drain_seconds": rep0["drain"]["drain_seconds"],
            "handed_over_tokens": rep0["drain"]["generated_tokens"],
        }
        if verbose:
            print(f"  supervised: kill at step boundary 3 -> drained "
                  f"{n_manifest} requests -> restart replayed -> all "
                  f"{len(prompts)} finished, outputs == fault-free "
                  "oracle — kill/drain/restart/replay verified")
        return report
    finally:
        if ctx is not None:
            ctx.cleanup()


def run_router_drill(seed: int = 1234, verbose: bool = True):
    """Seeded replica-death drill for the prefix-affinity router
    (serving/router.py): N=3 DISARMED replicas serve a shared-prefix
    workload mid-load when an injected ``serve.engine_step`` fault
    escapes one replica's step — to the router that IS replica death
    (the PR 13 failure contract composed: a replica either serves or
    hands its work back as a unit). Asserts:

      * exactly one replica died and its drain manifest replayed onto
        survivors GROUPED by the tag's affinity key (every request of
        one prefix lands on ONE affinity-matched survivor);
      * zero requests parked: every original handle resolved (finished,
        or terminally failed with its replacement carrying on) and
        every replacement finished;
      * merged outputs (originals where they finished, replacements
        where the death interrupted) equal the FAULT-FREE oracle —
        generated tokens rode the manifest, greedy decode continued
        exactly where the dead replica stopped;
      * the ``stable`` report subset is bit-identical per seed.
    """
    import zlib

    import numpy as np

    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import EngineConfig, ReplicaRouter, ServingEngine

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tests"))
    import serve_worker

    model = serve_worker.build_model(seed)
    rng = np.random.default_rng(seed)
    # shared-prefix workload: 3 page-aligned 16-token prefixes (block
    # size 8), 3 requests each with unique tails — the affinity signal
    # the hand-off must preserve
    prefixes = [rng.integers(1, 61, (16,)).tolist() for _ in range(3)]
    prompts = [prefixes[i % 3]
               + rng.integers(1, 61, (int(rng.integers(2, 5)),)).tolist()
               for i in range(9)]
    max_new = 6

    def mk_router():
        engines = [ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8))
            for _ in range(3)]
        return ReplicaRouter(engines, policy="affinity", seed=seed)

    def run(fault_plan):
        router = mk_router()
        if fault_plan is not None:
            chaos.install_plan(fault_plan)
        try:
            handles = [router.submit(p, max_new_tokens=max_new, tag=i)
                       for i, p in enumerate(prompts)]
            router.run_until_idle(max_steps=600)
        finally:
            chaos.clear_plan()
        return router, handles

    # -- fault-free oracle ----------------------------------------------------
    oracle_router, oracle_handles = run(None)
    oracle = {h.tag["tag"]: h.result(0) for h in oracle_handles}
    assert not oracle_router.handoffs, "fault-free run handed off work"

    # -- the death run: one escaped engine-step fault mid-load ----------------
    plan = chaos.FaultPlan(seed=seed).add("serve.engine_step", "error",
                                          at=(3,))
    router, handles = run(plan)
    assert [f[0] for f in plan.fired] == ["serve.engine_step"], \
        "the death fault never fired — drill lost its teeth"
    dead = [i for i, a in enumerate(router._alive) if not a]
    assert len(dead) == 1, f"expected exactly one dead replica: {dead}"
    assert len(router.handoffs) == 1
    handoff = router.handoffs[0]
    assert handoff["replica"] == dead[0] and handoff["reason"] == "death"
    assert handoff["requests"] > 0, \
        "death landed after the workload drained — fault index too late"
    # affinity-matched hand-off: every group names ONE surviving target
    for g in handoff["groups"]:
        assert g["target"] != dead[0], "hand-off routed to the corpse"
    replacements = handoff["handles"]

    # zero parked: originals all resolved, replacements all finished
    merged = {}
    parked = 0
    for h in list(handles) + list(replacements):
        if not h.done:
            parked += 1
        elif h.error is None:
            merged[h.tag["tag"]] = h.result(0)
    assert parked == 0, f"{parked} requests parked across the death"
    assert merged == oracle, \
        "post-death outputs diverged from the fault-free oracle"
    # the survivor inherited the affinity: a fresh same-prefix request
    # routes to the hand-off target, not the corpse
    from paddle_tpu.serving import prefix_chain_keys
    probe_prefix = None
    for g in handoff["groups"]:
        if g["affinity"]:
            probe_prefix = g
            break
    if probe_prefix is not None:
        probe_prompt = next(
            p for p in prompts
            if prefix_chain_keys(p, 8)
            and prefix_chain_keys(p, 8)[-1]
            == tuple(probe_prefix["affinity"]))
        probe = router.submit(probe_prompt, max_new_tokens=2,
                              tag="probe")
        target = probe_prefix["target"]
        with router.replicas[target]._lock:
            owned = probe in router.replicas[target].sched.waiting \
                or probe in router.replicas[target].sched.running
        assert owned, "affinity did not follow the hand-off target"
        router.run_until_idle(max_steps=200)

    report = {
        "seed": seed, "ok": True,
        "stable": {
            "oracle_crc": zlib.crc32(np.asarray(
                [t for i in sorted(oracle) for t in oracle[i]],
                np.int64).tobytes()),
            "dead_replica": dead[0],
            "manifest_requests": handoff["requests"],
            "handoff_groups": [
                {"affinity": g["affinity"], "target": g["target"],
                 "orders": g["orders"]} for g in handoff["groups"]],
            "replay_crc": zlib.crc32(np.asarray(
                [t for i in sorted(merged) for t in merged[i]],
                np.int64).tobytes()),
        },
    }
    if verbose:
        print(f"router drill (seed={seed}): replica {dead[0]} died at "
              f"engine-step fault #3 -> {handoff['requests']} requests "
              f"handed off in {len(handoff['groups'])} affinity "
              f"group(s), 0 parked, outputs == fault-free oracle — "
              "replica-death failover verified")
    return report


def run_disagg_drill(seed: int = 1234, verbose: bool = True):
    """Seeded prefill-replica death drill for the disaggregated fleet
    (serving/router.py pool classes): 1 prefill + 2 decode replicas
    serve a shared-prefix workload when an injected
    ``serve.engine_step`` fault kills the PREFILL replica mid-stream —
    some requests already handed their KV pages to the decode pool,
    the rest are mid-prefill or queued. With no prefill survivor, the
    salvage manifest replays onto DECODE survivors via prompt recompute
    (the manifest fallback: a decode engine is a full engine). Asserts:

      * the dead replica is the prefill one, and every hand-off group
        in the manifest replay targets a decode survivor;
      * at least one KV-page hand-off landed BEFORE the death (the
        drill kills mid-handoff, not before the machinery engaged);
      * zero requests parked: originals resolved, replacements
        finished, merged outputs equal the fault-free disaggregated
        oracle (which itself equals the single-engine oracle);
      * the headless fleet still serves: a fresh post-death submit
        recomputes on the decode pool and completes;
      * the ``stable`` report subset is bit-identical per seed.
    """
    import zlib

    import numpy as np

    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import (EngineConfig, ReplicaRouter,
                                    ServingEngine)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tests"))
    import serve_worker

    model = serve_worker.build_model(seed)
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(1, 61, (16,)).tolist() for _ in range(3)]
    prompts = [prefixes[i % 3]
               + rng.integers(1, 61, (int(rng.integers(2, 5)),)).tolist()
               for i in range(9)]
    max_new = 6

    def mk_router():
        pre = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8, role="prefill"))
        dec = [ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=8, block_size=8, role="decode"))
            for _ in range(2)]
        return ReplicaRouter([pre] + dec, policy="affinity", seed=seed)

    def run(fault: bool):
        router = mk_router()
        handles = [router.submit(p, max_new_tokens=max_new, tag=i)
                   for i, p in enumerate(prompts)]
        if not fault:
            router.run_until_idle(max_steps=800)
            return router, handles, None
        # drive until the FIRST KV-page hand-off has landed on the
        # decode pool, then arm the fault: the very next engine step to
        # run is the prefill replica's (it steps first in the round and
        # its queue is still deep), so the death strikes the prefill
        # replica MID-handoff — some pages already moved, the rest of
        # the work mid-prefill or queued. Deterministic per seed.
        rounds = 0
        while router.kv_handoffs["pages"] < 1 and rounds < 50:
            router.step_all()
            rounds += 1
        plan = chaos.FaultPlan(seed=seed).add("serve.engine_step",
                                              "error", at=(1,))
        chaos.install_plan(plan)
        try:
            router.run_until_idle(max_steps=800)
        finally:
            chaos.clear_plan()
        return router, handles, plan

    # -- fault-free disaggregated oracle --------------------------------------
    oracle_router, oracle_handles, _ = run(fault=False)
    oracle = {h.tag["tag"]: h.result(0) for h in oracle_handles}
    assert not oracle_router.handoffs, "fault-free run replayed a manifest"
    assert oracle_router.kv_handoffs["pages"] > 0, \
        "fault-free run never exercised the KV-page hand-off"

    # -- the death run: the prefill replica dies mid-handoff ------------------
    router, handles, plan = run(fault=True)
    assert [f[0] for f in plan.fired] == ["serve.engine_step"], \
        "the death fault never fired — drill lost its teeth"
    dead = [i for i, a in enumerate(router._alive) if not a]
    assert dead == [0], f"expected the prefill replica dead, got {dead}"
    assert router.kv_handoffs["pages"] >= 1, \
        "death landed before any KV hand-off — not a mid-handoff drill"
    assert len(router.handoffs) == 1
    handoff = router.handoffs[0]
    assert handoff["replica"] == 0 and handoff["reason"] == "death"
    assert handoff["requests"] > 0, \
        "death landed after the workload drained — fault index too late"
    for g in handoff["groups"]:
        # no prefill survivor exists: every group must land on a decode
        # survivor for prompt recompute
        assert g["target"] in router.decode_pool, \
            f"hand-off group landed outside the decode pool: {g}"
    replacements = handoff["handles"]

    merged, parked = {}, 0
    for h in list(handles) + list(replacements):
        if not h.done:
            parked += 1
        elif h.error is None:
            merged[h.tag["tag"]] = h.result(0)
    assert parked == 0, f"{parked} requests parked across the death"
    assert merged == oracle, \
        "post-death outputs diverged from the fault-free oracle"

    # the headless fleet still serves: a fresh submit recomputes on the
    # decode pool (no prefill replica remains to route to)
    probe = router.submit(prompts[0], max_new_tokens=max_new,
                          tag="probe")
    router.run_until_idle(max_steps=300)
    assert probe.result(0) == oracle[0], \
        "post-death fleet no longer serves fresh requests"

    report = {
        "seed": seed, "ok": True,
        "stable": {
            "oracle_crc": zlib.crc32(np.asarray(
                [t for i in sorted(oracle) for t in oracle[i]],
                np.int64).tobytes()),
            "dead_replica": dead[0],
            "pre_death_page_handoffs": router.kv_handoffs["pages"],
            "manifest_requests": handoff["requests"],
            "handoff_groups": [
                {"affinity": g["affinity"], "target": g["target"],
                 "orders": g["orders"]} for g in handoff["groups"]],
            "replay_crc": zlib.crc32(np.asarray(
                [t for i in sorted(merged) for t in merged[i]],
                np.int64).tobytes()),
        },
    }
    if verbose:
        print(f"disagg drill (seed={seed}): prefill replica died at the "
              f"first post-handoff engine step, after "
              f"{router.kv_handoffs['pages']} page hand-off(s) -> "
              f"{handoff['requests']} requests recomputed on decode "
              f"survivors in {len(handoff['groups'])} group(s), 0 "
              "parked, outputs == fault-free oracle — prefill-death "
              "manifest fallback verified")
    return report


def run_fleet_obs_drill(seed: int = 1234, verbose: bool = True):
    """Seeded correlated-fleet-flight-dump drill (serving/fleet_obs.py).
    Two phases over the PR 15 disaggregated workload (1 prefill + 2
    decode replicas, shared-prefix prompts):

      * ARMED BUT QUIET: a fault-free run with the fleet plane armed
        (signal bus sampling + telemetry streaming + dump dir set) must
        produce ZERO fleet dumps and zero dump failures — observability
        must not invent incidents;
      * REPLICA DEATH: an injected ``serve.engine_step`` fault kills
        the prefill replica mid-handoff; the router's death path must
        latch EXACTLY ONE well-formed correlated dump naming replica 0
        as the origin, with every surviving peer contributing a
        non-empty signal window — run TWICE per seed and the stable
        report subset must be bit-identical (the dump content is
        evidence, so it must be reproducible).
    """
    import tempfile
    import zlib

    import numpy as np

    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import (EngineConfig, FleetObsConfig,
                                    ReplicaRouter, ServingEngine)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tests"))
    import serve_worker

    model = serve_worker.build_model(seed)
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(1, 61, (16,)).tolist() for _ in range(3)]
    prompts = [prefixes[i % 3]
               + rng.integers(1, 61, (int(rng.integers(2, 5)),)).tolist()
               for i in range(9)]
    max_new = 6

    def mk_router(tmp):
        pre = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8, role="prefill",
            obs=True))
        dec = [ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=8, block_size=8, role="decode",
            obs=True)) for _ in range(2)]
        cfg = FleetObsConfig(
            window=16, dump_dir=tmp,
            telemetry_path=os.path.join(tmp, "fleet_signals.json"),
            telemetry_every=4)
        return ReplicaRouter([pre] + dec, policy="affinity", seed=seed,
                             fleet_obs=cfg)

    def run(fault: bool, tmp: str):
        router = mk_router(tmp)
        handles = [router.submit(p, max_new_tokens=max_new, tag=i)
                   for i, p in enumerate(prompts)]
        if not fault:
            router.run_until_idle(max_steps=800)
            return router, handles, None
        rounds = 0
        while router.kv_handoffs["pages"] < 1 and rounds < 50:
            router.step_all()
            rounds += 1
        plan = chaos.FaultPlan(seed=seed).add("serve.engine_step",
                                              "error", at=(1,))
        chaos.install_plan(plan)
        try:
            router.run_until_idle(max_steps=800)
        finally:
            chaos.clear_plan()
        return router, handles, plan

    # -- phase 1: armed but quiet — zero dumps on a healthy fleet -------------
    quiet_tmp = tempfile.mkdtemp(prefix="fleet_obs_quiet_")
    router, handles, _ = run(fault=False, tmp=quiet_tmp)
    fo = router.fleet_obs
    assert fo is not None and fo.samples > 0, "fleet plane never sampled"
    assert fo.dumps == [] and fo.dump_failures == 0, \
        f"healthy fleet produced dumps: {fo.dumps}"
    assert not [p for p in os.listdir(quiet_tmp)
                if p.startswith("fleet_flight_")], \
        "healthy fleet wrote a fleet_flight artifact"
    with open(os.path.join(quiet_tmp, "fleet_signals.json")) as f:
        streamed = json.load(f)
    assert streamed["schema"] == "fleet_signals", \
        "telemetry stream is not the documented signals() schema"
    oracle = {h.tag["tag"]: h.result(0) for h in handles}

    # -- phase 2: prefill death => exactly one correlated dump, twice ---------
    def death_run():
        tmp = tempfile.mkdtemp(prefix="fleet_obs_death_")
        router, handles, plan = run(fault=True, tmp=tmp)
        assert [f[0] for f in plan.fired] == ["serve.engine_step"], \
            "the death fault never fired — drill lost its teeth"
        dead = [i for i, a in enumerate(router._alive) if not a]
        assert dead == [0], f"expected the prefill replica dead: {dead}"
        fo = router.fleet_obs
        assert len(fo.dumps) == 1, \
            f"want exactly one correlated dump, got {fo.dumps}"
        assert fo.dump_failures == 0
        entry = fo.dumps[0]
        assert entry["reason"] == "death" and entry["origin"] == 0
        files = [p for p in os.listdir(tmp)
                 if p.startswith("fleet_flight_")]
        assert files == ["fleet_flight_death.json"], files
        with open(os.path.join(tmp, files[0])) as f:
            rec = json.load(f)            # well-formed: parses clean
        assert rec["origin_replica"] == 0, "dump must name the dead one"
        peers = [rec["replicas"][str(i)] for i in (1, 2)]
        assert all(len(p["signals"]) >= 1 for p in peers), \
            "a surviving peer contributed no signal window"
        assert all(p["role"] == "decode" and p["alive"] for p in peers)
        # resolve every request across the death (the PR 15 contract)
        merged = {}
        for h in list(handles) + list(router.handoffs[0]["handles"]):
            assert h.done, "a request parked across the death"
            if h.error is None:
                merged[h.tag["tag"]] = h.result(0)
        assert merged == oracle, "post-death outputs diverged"
        stable = {
            "reason": rec["reason"],
            "origin_replica": rec["origin_replica"],
            "dead": dead,
            "roles": {i: r["role"] for i, r in rec["replicas"].items()},
            "peer_window_passes": [
                [s["pass"] for s in p["signals"]] for p in peers],
            "peer_queue_series": [
                [s["queue_depth"] for s in p["signals"]] for p in peers],
            "router_kv_handoffs": rec["router"]["kv_handoffs"],
            "router_failovers": rec["router"]["failovers"],
            "replay_crc": zlib.crc32(np.asarray(
                [t for i in sorted(merged) for t in merged[i]],
                np.int64).tobytes()),
        }
        return stable

    first = death_run()
    second = death_run()
    assert first == second, \
        f"correlated dump not stable per seed:\n{first}\nvs\n{second}"

    report = {"seed": seed, "ok": True, "stable": first}
    if verbose:
        print(f"fleet-obs drill (seed={seed}): armed-quiet run sampled "
              f"{fo.samples if fo else 0}+ passes with 0 dumps; prefill "
              f"death latched exactly one correlated fleet_flight_death"
              f".json naming replica 0 with "
              f"{len(first['peer_window_passes'])} peer windows, "
              "bit-identical across a double run — correlated fleet "
              "flight recorder verified")
    return report


def run_elastic_drill(seed: int = 1234, verbose: bool = True):
    """Seeded elastic-control-plane drill (serving/autoscaler.py) over
    a 10x traffic ramp. One deterministic pass-indexed schedule (steady
    arrivals, then a 10x-rate swing window) drives a unified fleet that
    starts at the min envelope (1 replica, max 2) under a
    ``FleetAutoscaler`` whose cooldowns are tick-based — with
    ``round_robin`` routing and a zero-grace drain deadline there is NO
    wall-clock anywhere in the decision loop, so the whole run is
    bit-reproducible per seed. Three teeth:

      * SPAWN FAULT => BACKOFF-AND-HOLD: an ``elastic.spawn`` chaos
        fault kills the FIRST spawn attempt mid-ramp — the autoscaler
        must degrade to the current fleet (recorded ``fault`` event,
        fleet size unchanged, hold-down armed, ``backoff_hold`` events
        while it lasts), never raising into ``step_all``, and then
        spawn clean once the hold-down expires;
      * RETIRE-DURING-BURST IS LOSSLESS: as the swing subsides the
        autoscaler retires a replica while it still holds live work —
        the decommission manifest must replay onto the survivor
        (``replayed >= 1``), and every request (original or
        replacement) must finish with the fault-free oracle's exact
        greedy tokens: zero parked, zero lost;
      * STABLE PER SEED: the drill runs twice and the stable report
        subset — the full (tick, rule, action, outcome, replica) event
        sequence, the controller counters, the fired fault sites and
        both output crcs — must be bit-identical.
    """
    import zlib

    import numpy as np

    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import (AutoscalerConfig, EngineConfig,
                                    FleetAutoscaler, FleetObsConfig,
                                    ReplicaRouter, ServingEngine)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tests"))
    import serve_worker

    model = serve_worker.build_model(seed)
    rng = np.random.default_rng(seed)
    max_new = 6
    # pass-indexed arrival schedule: 1 request every other pass for 10
    # passes (the steady base), then 5 per pass for 6 passes (the 10x
    # swing) — fixed by the seed before either fleet runs
    schedule = {}
    tag = 0
    for p in range(0, 10, 2):
        schedule[p] = [tag]
        tag += 1
    for p in range(10, 22):
        schedule[p] = list(range(tag, tag + 5))
        tag += 5
    # post-swing steady tail: traffic settles back to the base rate, so
    # the drain-out retire fires while the victim still carries work
    for p in range(22, 100, 2):
        schedule[p] = [tag]
        tag += 1
    prompts = [rng.integers(1, 61, (int(rng.integers(8, 13)),)).tolist()
               for _ in range(tag)]

    def mk():
        return ServingEngine(model, EngineConfig(
            max_seqs=4, token_budget=24, block_size=8, num_blocks=64))

    def run(elastic: bool, fault: bool):
        n0 = 1 if elastic else 2
        router = ReplicaRouter([mk() for _ in range(n0)],
                               policy="round_robin", seed=seed,
                               fleet_obs=FleetObsConfig(window=64))
        scaler = None
        if elastic:
            scaler = FleetAutoscaler(router, engine_factory=lambda r: mk(),
                                     config=AutoscalerConfig(
                                         min_replicas=1, max_replicas=2,
                                         scale_up_pressure=4.0,
                                         scale_down_pressure=3.0,
                                         cooldown=1000, backoff=3,
                                         drain_deadline_s=0.0))
        plan = None
        if fault:
            plan = chaos.FaultPlan(seed=seed).add("elastic.spawn",
                                                  "error", at=(1,))
            chaos.install_plan(plan)
        handles = {}
        try:
            p = 0
            while p < 100 or router.has_work():
                for t in schedule.get(p, ()):
                    handles[t] = router.submit(prompts[t],
                                               max_new_tokens=max_new,
                                               tag=t)
                router.step_all()
                if scaler is not None:
                    scaler.control()
                p += 1
                assert p < 500, "elastic drill never drained"
        finally:
            if fault:
                chaos.clear_plan()
        return router, scaler, handles, plan, p

    # -- fault-free oracle: the fixed-max fleet's greedy tokens ---------------
    router, _, handles, _, _ = run(elastic=False, fault=False)
    oracle = {t: h.result(0) for t, h in handles.items()}
    oracle_crc = zlib.crc32(np.asarray(
        [tok for t in sorted(oracle) for tok in oracle[t]],
        np.int64).tobytes())

    def elastic_run():
        router, scaler, handles, plan, passes = run(elastic=True,
                                                    fault=True)
        # the spawn fault fired exactly once and degraded, not raised
        assert [f[0] for f in plan.fired] == ["elastic.spawn"], \
            "the spawn fault never fired — drill lost its teeth"
        outs = [(e.rule, e.action, e.outcome) for e in scaler.events]
        spawn_outs = [o for _, a, o in outs if a == "spawn"]
        assert spawn_outs[0] == "fault", \
            f"first spawn attempt should fault: {spawn_outs}"
        assert "backoff_hold" in spawn_outs, \
            f"no hold-down after the faulted spawn: {spawn_outs}"
        assert spawn_outs[-1] == "ok", \
            f"the fleet never scaled after backoff: {spawn_outs}"
        fault_evt = next(e for e in scaler.events
                         if e.outcome == "fault")
        assert fault_evt.signal["alive"] == 1, \
            "faulted spawn must leave the current fleet serving"
        assert scaler.spawns == 1 and scaler.faults == 1, \
            scaler.telemetry()
        # the retire fired during the drain-out and replayed live work
        assert scaler.retires == 1, scaler.telemetry()
        retire_evt = next(e for e in scaler.events
                          if e.action == "retire" and e.outcome == "ok")
        assert retire_evt.detail["replayed"] >= 1, \
            "retire-during-burst handed off no work — the lossless " \
            "claim went untested"
        assert len(router.handoffs) == 1 and \
            router.handoffs[0]["reason"] == "drain"
        # zero parked or lost: every request's FINAL handle finished
        # clean with the oracle's exact greedy tokens
        final = dict(handles)
        for rec in router.handoffs:
            for h in rec["handles"]:
                final[h.tag["tag"]] = h
        merged = {}
        for t, h in final.items():
            assert h.done, f"request {t} parked across the scale-down"
            assert h.error is None, f"request {t} lost: {h.error}"
            merged[t] = h.result(0)
        assert merged == oracle, "elastic outputs diverged from the " \
            "fixed-fleet oracle"
        return {
            "events": [[e.tick, e.rule, e.action, e.outcome, e.replica]
                       for e in scaler.events],
            "spawns": scaler.spawns, "retires": scaler.retires,
            "faults": scaler.faults,
            "fired": [list(f) for f in plan.fired],
            "retire_replayed": retire_evt.detail["replayed"],
            "alive_at_end": sum(router._alive),
            "passes": passes,
            "replay_crc": zlib.crc32(np.asarray(
                [tok for t in sorted(merged) for tok in merged[t]],
                np.int64).tobytes()),
            "oracle_crc": oracle_crc,
        }

    first = elastic_run()
    second = elastic_run()
    assert first == second, \
        f"elastic drill not stable per seed:\n{first}\nvs\n{second}"
    assert first["replay_crc"] == first["oracle_crc"]

    report = {"seed": seed, "ok": True, "stable": first}
    if verbose:
        print(f"elastic drill (seed={seed}): spawn #1 faulted and "
              f"degraded to backoff-and-hold ({first['faults']} fault, "
              f"fleet held at 1), spawn #2 scaled into the swing, "
              f"retire replayed {first['retire_replayed']} live "
              f"request(s) onto the survivor, all "
              f"{len(oracle)} requests finished with oracle-exact "
              f"tokens in {first['passes']} passes, bit-identical "
              "across a double run — elastic control plane verified")
    return report


def _mk_fabric_fleet(model, seed, membership_cfg):
    """1 prefill + 2 decode on the armed transport/membership planes —
    the fault-domain drills' shared fleet shape."""
    from paddle_tpu.serving import (EngineConfig, ReplicaRouter,
                                    ServingEngine)
    pre = ServingEngine(model, EngineConfig(
        max_seqs=2, token_budget=16, block_size=8, role="prefill"))
    dec = [ServingEngine(model, EngineConfig(
        max_seqs=2, token_budget=8, block_size=8, role="decode"))
        for _ in range(2)]
    return ReplicaRouter([pre] + dec, policy="affinity", seed=seed,
                         transport=True, membership=membership_cfg)


def _fabric_serve(router, prompts, max_new, hook=None, max_passes=900):
    """Drive a fabric fleet to convergence with per-request exactly-once
    token counting; returns (handles, counts)."""
    counts = {}
    handles = []
    for i, p in enumerate(prompts):
        counts[i] = 0

        def cb(tok, i=i):
            counts[i] += 1
        handles.append(router.submit(p, max_new_tokens=max_new,
                                     on_token=cb, tag=i))
    n = 0
    while True:
        more = router.step_all()
        n += 1
        if hook is not None:
            hook(n, router)
        if not more:
            return handles, counts
        assert n < max_passes, "fabric fleet did not converge"


def _merge_outputs(handles, extra=()):
    """Original + replacement handles -> {tag: tokens}; parked count."""
    merged, parked = {}, 0
    for h in list(handles) + list(extra):
        if not h.done:
            parked += 1
        elif h.error is None:
            merged[h.tag["tag"]] = h.result(0)
    return merged, parked


def run_partition_drill(seed: int = 1234, verbose: bool = True):
    """Seeded partition-then-heal drill for the fault-domain fabric
    (serving/transport.py + serving/membership.py): the lease machine's
    two verdicts, each taken exactly once.

    Phase A (healed inside the lease): a decode replica is partitioned
    mid-workload and healed before ``lease_ticks`` run out. Asserts the
    replica went live -> suspect -> live (and NEVER dead), no salvage
    record was written, outputs equal the fault-free oracle, and no
    request received a token twice — the healed-partition/double-decode
    hole the SUSPECT state exists to close.

    Phase B (lease expiry): the same partition never heals. Asserts
    exactly one suspect -> dead transition, exactly one salvage record
    with reason ``lease_expired``, every original handle resolved
    (replacements in the record finish the work), zero parked, and
    merged outputs equal the fault-free oracle. The ``stable`` report
    subset is bit-identical per seed."""
    import zlib

    import numpy as np

    from paddle_tpu.serving import MembershipConfig

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tests"))
    import serve_worker

    model = serve_worker.build_model(seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 61, (int(rng.integers(4, 12)),)).tolist()
               for _ in range(6)]
    max_new = 6

    # -- fault-free oracle (armed fabric, no partition) -----------------------
    oracle_router = _mk_fabric_fleet(
        model, seed, MembershipConfig(suspect_after=3, lease_ticks=12))
    oracle_handles, oracle_counts = _fabric_serve(
        oracle_router, prompts, max_new)
    oracle, parked = _merge_outputs(oracle_handles)
    assert parked == 0 and len(oracle) == len(prompts)
    assert not oracle_router.handoffs, \
        "fault-free fabric run replayed a manifest"
    assert oracle_counts == {i: len(oracle[i]) for i in oracle}

    # -- phase A: partition heals inside the lease ----------------------------
    def heal_hook(n, router):
        if n == 2:
            router.transport.partition(2)
        elif n == 10:
            router.transport.heal(2)

    r_a = _mk_fabric_fleet(
        model, seed, MembershipConfig(suspect_after=3, lease_ticks=12))
    handles_a, counts_a = _fabric_serve(r_a, prompts, max_new,
                                        hook=heal_hook)
    out_a, parked_a = _merge_outputs(handles_a)
    trans_a = r_a.membership.telemetry()["transition_counts"]
    assert parked_a == 0, f"{parked_a} requests parked across the heal"
    assert out_a == oracle, \
        "healed-partition outputs diverged from the fault-free oracle"
    assert counts_a == {i: len(out_a[i]) for i in out_a}, \
        "a request received tokens twice across the healed partition"
    assert trans_a.get("suspect->live", 0) >= 1, \
        f"partition never suspected/healed: {trans_a}"
    assert "suspect->dead" not in trans_a and "live->dead" not in trans_a
    assert not r_a.handoffs, \
        "healed partition was salvaged — the double-decode hole"

    # -- phase B: the partition outlives the lease. The node is frozen
    # AND unreachable (a crash, not a slow link): the moment it holds
    # live decode work, its step stops making progress and its links
    # go down — so real requests are stranded there at lease expiry
    cut = {"done": False}

    def kill_hook(n, router):
        eng = router.replicas[2]
        if not cut["done"] and (eng.sched.running or eng.sched.waiting):
            cut["done"] = True
            router.transport.partition(2)
            eng.step = lambda: False     # frozen: alive but inert

    r_b = _mk_fabric_fleet(
        model, seed, MembershipConfig(suspect_after=2, lease_ticks=5))
    handles_b, counts_b = _fabric_serve(r_b, prompts, max_new,
                                        hook=kill_hook)
    assert cut["done"], "no decode work ever landed on replica 2"
    trans_b = r_b.membership.telemetry()["transition_counts"]
    assert trans_b.get("suspect->dead", 0) == 1, \
        f"lease expiry fired {trans_b.get('suspect->dead', 0)} times"
    salvages = [rec for rec in r_b.handoffs
                if rec["reason"] == "lease_expired"]
    assert len(salvages) == 1 and len(r_b.handoffs) == 1, \
        f"expected exactly one lease-expiry salvage, got {r_b.handoffs}"
    assert salvages[0]["requests"] > 0, \
        "lease expired with nothing to salvage — drill lost its teeth"
    out_b, parked_b = _merge_outputs(handles_b,
                                     extra=salvages[0]["handles"])
    assert parked_b == 0, f"{parked_b} requests parked across expiry"
    assert out_b == oracle, \
        "post-expiry outputs diverged from the fault-free oracle"
    assert not r_b.transport.busy() and not r_b._inflight, \
        "fabric did not quiesce after the lease-expiry salvage"

    oracle_crc = zlib.crc32(np.asarray(
        [t for i in sorted(oracle) for t in oracle[i]],
        np.int64).tobytes())
    report = {
        "seed": seed, "ok": True,
        "stable": {
            "oracle_crc": oracle_crc,
            "heal_transitions": dict(sorted(trans_a.items())),
            "expiry_transitions": dict(sorted(trans_b.items())),
            "salvaged_requests": salvages[0]["requests"],
            "salvage_groups": [
                {"affinity": g["affinity"], "target": g["target"],
                 "orders": g["orders"]} for g in salvages[0]["groups"]],
        },
    }
    if verbose:
        print(f"partition drill (seed={seed}): healed partition "
              f"suspect->live with 0 salvages and outputs == oracle "
              f"(crc {oracle_crc}); unhealed partition expired its "
              f"lease exactly once -> {salvages[0]['requests']} "
              f"request(s) salvaged, 0 parked, merged outputs == "
              "oracle — lease machine verified on both verdicts")
    return report


def run_lossy_drill(seed: int = 1234, verbose: bool = True):
    """Seeded lossy-link drill: 5% drop + 5% dup + 5% delay at the
    ``transport.send`` seam over the full fabric fleet. The reliability
    mechanisms must make the loss invisible above the transport:
    convergence, zero parked, exactly-once token delivery, outputs
    equal to the fault-free oracle, and faults demonstrably FIRED
    (a lossy drill that loses nothing has no teeth). Runs the whole
    scenario twice from one seed and asserts the reports are
    bit-identical."""
    import zlib

    import numpy as np

    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import MembershipConfig

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tests"))
    import serve_worker

    model = serve_worker.build_model(seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 61, (int(rng.integers(4, 12)),)).tolist()
               for _ in range(6)]
    max_new = 6

    oracle_router = _mk_fabric_fleet(
        model, seed, MembershipConfig(suspect_after=3, lease_ticks=12))
    oracle_handles, _ = _fabric_serve(oracle_router, prompts, max_new)
    oracle, parked = _merge_outputs(oracle_handles)
    assert parked == 0
    assert oracle_router.transport.counters["retransmits"] == 0, \
        "fault-free fabric run retransmitted — the clean path regressed"

    def lossy_run():
        chaos.install_plan(
            chaos.FaultPlan(seed=seed)
            .add("transport.send", "error", "drop", prob=0.05)
            .add("transport.send", "error", "dup", prob=0.05)
            .add("transport.send", "delay", "1", prob=0.05))
        try:
            r = _mk_fabric_fleet(model, seed, MembershipConfig(
                suspect_after=3, lease_ticks=12))
            handles, counts = _fabric_serve(r, prompts, max_new)
        finally:
            chaos.clear_plan()
        merged, parked = _merge_outputs(handles)
        c = r.transport.counters
        assert parked == 0, f"{parked} requests parked on lossy links"
        assert merged == oracle, \
            "lossy-link outputs diverged from the fault-free oracle"
        assert counts == {i: len(merged[i]) for i in merged}, \
            "a request received tokens twice through the lossy links"
        assert c["dropped"] + c["duplicate"] + c["delayed"] > 0, \
            "no fault ever fired — the lossy drill has no teeth"
        assert c["duplicate"] == 0 or c["deduped"] >= 0
        assert not r.transport.busy() and not r._inflight, \
            "fabric did not quiesce after the lossy run"
        return {
            "outputs_crc": zlib.crc32(np.asarray(
                [t for i in sorted(merged) for t in merged[i]],
                np.int64).tobytes()),
            "counters": dict(c),
            "retries_by_site": dict(sorted(
                r.transport.retries_by_site.items())),
            "handoff_outcomes": dict(r.kv_handoffs),
        }

    first = lossy_run()
    second = lossy_run()
    assert first == second, \
        f"lossy run not bit-stable per seed:\n{first}\nvs\n{second}"
    assert first["outputs_crc"] == zlib.crc32(np.asarray(
        [t for i in sorted(oracle) for t in oracle[i]],
        np.int64).tobytes())

    report = {"seed": seed, "ok": True, "stable": first}
    if verbose:
        c = first["counters"]
        print(f"lossy drill (seed={seed}): 5% drop+dup+delay absorbed "
              f"— {c['dropped']} dropped / {c['duplicate']} duplicated "
              f"({c['deduped']} deduped) / {c['delayed']} delayed / "
              f"{c['retransmits']} retransmit(s), 0 parked, outputs == "
              f"fault-free oracle (crc {first['outputs_crc']}), "
              "double-run bit-identical — lossy-link fabric verified")
    return report


def run_lockcheck_drill(seed: int = 1234, verbose: bool = True):
    """Armed ordered-lock drill (serving/locking.py, PADDLE_LOCKCHECK).

    Phase 1 (armed-and-clean): a real engine serves a seeded workload
    with the runtime twin armed — the serving tier's own lock pairing
    (engine -> observer) must satisfy serving.locking.LOCK_ORDER end
    to end (zero violations), and the tokens must be bit-identical to
    the disarmed run (arming observes, never perturbs). Phase 2
    (planted inversion): a rogue maintenance thread grabs the armed
    engine's observer lock and then reaches back for the engine lock —
    the twin must raise LockOrderViolation deterministically (checked
    against the acquiring thread's own held stack BEFORE blocking, so
    the catch cannot depend on interleaving), naming the planted edge.
    The drill plants the same inversion twice and asserts the two
    violation messages are bit-identical (stable per seed)."""
    import threading
    import zlib

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import EngineConfig, ServingEngine
    from paddle_tpu.serving import locking

    paddle.seed(seed % (2 ** 31))
    cfg = LlamaConfig.tiny(vocab_size=61, hidden_size=32, layers=2,
                           heads=4, kv_heads=2, seq=64)
    cfg.use_flash_attention = False
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 61, (6 + i % 4,)).tolist() for i in range(4)]

    def serve(arm: bool):
        eng = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8,
            enable_prefix_cache=False, obs=True))
        locking.arm(arm)
        try:
            out = eng.generate_batch(prompts, max_new_tokens=6)
        finally:
            locking.arm(False)
        return eng, out

    _, out_off = serve(False)
    eng, out_on = serve(True)
    assert out_on == out_off, \
        "arming the lock twin perturbed the served tokens"
    crc = zlib.crc32(json.dumps(out_on).encode()) & 0xFFFFFFFF

    # the fault-domain fabric walks the longest armed lock chain in the
    # tree (router -> transport -> membership -> engine -> observer):
    # the partition drill under enforcement must change nothing
    locking.arm(True)
    try:
        fabric_on = run_partition_drill(seed=seed, verbose=False)
    finally:
        locking.arm(False)
    fabric_off = run_partition_drill(seed=seed, verbose=False)
    assert fabric_on["stable"] == fabric_off["stable"], \
        "arming the lock twin perturbed the partition drill"

    def plant():
        caught = []

        def rogue():
            try:
                with eng.obs._lock:       # observer held first...
                    with eng._lock:       # ...then the engine: inverted
                        pass
            except locking.LockOrderViolation as e:
                caught.append(str(e))

        t = threading.Thread(target=rogue, name="rogue-maintenance")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive(), "planted-inversion thread hung"
        return caught

    locking.arm(True)
    try:
        first, second = plant(), plant()
    finally:
        locking.arm(False)
    assert first, "planted observer->engine inversion escaped the twin"
    assert first == second, \
        f"violation not deterministic: {first} != {second}"
    assert "observer" in first[0] and "engine" in first[0], first[0]

    report = {
        "seed": seed, "ok": True,
        "stable": {
            "lock_order": list(locking.LOCK_ORDER),
            "tokens_crc": crc,
            "violation": first[0],
        },
    }
    if verbose:
        print(f"lockcheck drill (seed={seed}): armed clean run "
              f"bit-identical to disarmed (crc {crc}); planted "
              f"observer->engine inversion caught deterministically: "
              f"{first[0]!r} — ordered-lock twin verified")
    return report


def run_wire_plant():
    """Child-process half of ``--wirecheck`` phase 2: arm the sealing
    twin, seal a deliberately corrupt kv_export_record (one undeclared
    key, one float prefix-key) and exit 1 with the violation message on
    stderr — the parent drill asserts the code and that the message is
    byte-stable across two plants."""
    from paddle_tpu.serving import wire

    wire.arm(True)
    record = {
        "version": 1, "num_pages": 1, "n_tokens": 8, "block_size": 8,
        "keys": [(1.5, 5, 0)],          # float where ints must live
        "tokens": [5] * 8,
        "smuggled": "not-in-any-schema",  # undeclared key
    }
    try:
        wire.seal(record, "kv_export_record")
    except wire.WireContractViolation as e:
        print(str(e), file=sys.stderr)
        return 1
    print("planted corrupt record escaped the armed wire twin",
          file=sys.stderr)
    return 2


def run_wirecheck_drill(seed: int = 1234, verbose: bool = True):
    """Armed wire-contract drill (serving/wire.py, PADDLE_WIRECHECK).

    Phase 1 (armed transparency): the fleet-obs and elastic drills —
    together they exercise every adopted seam: KV export/import
    hand-offs, drain-manifest build/replay, fleet signals + telemetry
    streaming, autoscale ledger writes and correlated flight dumps —
    run twice each, sealing twin disarmed then armed, and their stable
    reports (including the replayed tokens-crc) must be bit-identical:
    arming validates every record at its producing seam without
    perturbing one token. Phase 2 (planted corruption): a corrupt
    kv_export_record carrying an undeclared key AND a float prefix-key
    is sealed in a child process; it must exit 1 with a byte-stable
    WireContractViolation message, twice. A second in-process plant
    with ONLY the float prefix-key pins the type-violation message
    too (the undeclared-key check fires first when both are present).
    """
    import subprocess

    from paddle_tpu.serving import wire

    def both(arm: bool):
        wire.arm(arm)
        try:
            fleet = run_fleet_obs_drill(seed=seed, verbose=False)
            elastic = run_elastic_drill(seed=seed, verbose=False)
            # the fault-domain fabric seals kv_transfer_ack +
            # membership_lease at rates no other drill reaches (every
            # heartbeat, every two-phase ack, every retransmitted dup)
            lossy = run_lossy_drill(seed=seed, verbose=False)
        finally:
            wire.arm(False)
        return {"fleet_obs": fleet["stable"],
                "elastic": elastic["stable"],
                "lossy": lossy["stable"]}

    off = both(False)
    on = both(True)
    assert on == off, \
        f"arming the wire twin perturbed a drill report:\n{on}\nvs\n{off}"

    # -- phase 2: planted corruption dies with exit 1, byte-stably ------------
    here = os.path.abspath(__file__)

    def plant() -> str:
        proc = subprocess.run(
            [sys.executable, here, "--wirecheck", "--plant-corruption"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 1, \
            (f"planted corruption must exit 1, got {proc.returncode}: "
             f"{proc.stderr}")
        return proc.stderr.strip().splitlines()[-1]

    first, second = plant(), plant()
    assert first == second, \
        f"violation not byte-stable: {first!r} != {second!r}"
    assert "wire[kv_export_record]" in first and "smuggled" in first, \
        first

    # the float prefix-key alone (undeclared-key check outranks it when
    # both corruptions ride one record): pin the type-violation message
    wire.arm(True)
    try:
        float_key = {
            "version": 1, "num_pages": 1, "n_tokens": 8,
            "block_size": 8, "keys": [(1.5, 5, 0)], "tokens": [5] * 8,
        }
        msgs = []
        for _ in range(2):
            try:
                wire.seal(float_key, "kv_export_record")
            except wire.WireContractViolation as e:
                msgs.append(str(e))
    finally:
        wire.arm(False)
    assert len(msgs) == 2 and msgs[0] == msgs[1], msgs
    assert "'keys'" in msgs[0] and "prefix_keys" in msgs[0], msgs[0]

    report = {
        "seed": seed, "ok": True,
        "stable": {
            "fleet_obs": on["fleet_obs"],
            "elastic": on["elastic"],
            "undeclared_key_violation": first,
            "float_prefix_key_violation": msgs[0],
        },
    }
    if verbose:
        print(f"wirecheck drill (seed={seed}): fleet-obs + elastic "
              f"drills bit-identical armed vs disarmed (elastic crc "
              f"{on['elastic'].get('replay_crc', '?')}); planted "
              f"corrupt kv_export_record exited 1 byte-stably: "
              f"{first!r}; float prefix-key pinned: {msgs[0]!r} — "
              f"wire sealing twin verified")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--json", action="store_true",
                    help="print the full report as JSON")
    ap.add_argument("--preempt", action="store_true",
                    help="run the supervised kill/restart/resume drill "
                         "(with the AOT program cache unless --no-aot)")
    ap.add_argument("--no-aot", action="store_true",
                    help="with --preempt: skip the AOT program-cache leg "
                         "(eager Model.fit worker, PR-5 behavior)")
    ap.add_argument("--flight", action="store_true",
                    help="run the serving flight-recorder drill (seeded "
                         "pool exhaustion => exactly one dump)")
    ap.add_argument("--serve", action="store_true",
                    help="run the serving-resilience drill (contained "
                         "engine-step fault + supervised kill/drain/"
                         "restart/replay)")
    ap.add_argument("--no-supervised", action="store_true",
                    help="with --serve: skip the supervised "
                         "kill/restart phase (in-process containment "
                         "only)")
    ap.add_argument("--mem", action="store_true",
                    help="run the memory-pressure drill (seeded pool "
                         "growth => exactly one dump naming the pool)")
    ap.add_argument("--router", action="store_true",
                    help="run the replica-death drill (one of N router "
                         "replicas dies mid-load; its manifest replays "
                         "onto affinity-matched survivors)")
    ap.add_argument("--disagg", action="store_true",
                    help="run the prefill-replica-death drill (the "
                         "prefill pool dies mid-handoff; requests land "
                         "on decode survivors via prompt recompute)")
    ap.add_argument("--fleet-obs", action="store_true",
                    help="run the correlated-fleet-flight-dump drill "
                         "(armed-quiet run => zero dumps; seeded "
                         "replica death => exactly one dump naming the "
                         "dead replica, stable per seed)")
    ap.add_argument("--elastic", action="store_true",
                    help="run the elastic-control-plane drill (spawn "
                         "fault during the 10x ramp degrades to "
                         "backoff-and-hold; retire-during-burst "
                         "replays its manifest onto survivors; stable "
                         "per seed)")
    ap.add_argument("--partition", action="store_true",
                    help="run the fault-domain partition drill "
                         "(partition-then-heal = suspect, no salvage; "
                         "lease expiry = exactly one salvage)")
    ap.add_argument("--lossy", action="store_true",
                    help="run the fault-domain lossy-link drill "
                         "(5%% drop+dup+delay absorbed bit-identically)")
    ap.add_argument("--lockcheck", action="store_true",
                    help="run the armed ordered-lock drill (armed "
                         "serving run bit-identical to disarmed; a "
                         "planted observer->engine inversion raises "
                         "LockOrderViolation deterministically)")
    ap.add_argument("--wirecheck", action="store_true",
                    help="run the armed wire-contract drill (fleet + "
                         "elastic drills bit-identical armed vs "
                         "disarmed; a planted corrupt record — extra "
                         "key + float prefix-key — dies with exit 1 "
                         "and a byte-stable message)")
    ap.add_argument("--plant-corruption", action="store_true",
                    help="with --wirecheck: child-process mode that "
                         "seals a corrupt record under the armed twin "
                         "and exits 1 (used by the drill itself)")
    args = ap.parse_args(argv)
    if args.wirecheck and args.plant_corruption:
        return run_wire_plant()
    if args.preempt:
        report = run_preempt_drill(seed=args.seed, verbose=not args.json,
                                   aot=not args.no_aot)
    elif args.flight:
        report = run_flight_drill(seed=args.seed, verbose=not args.json)
    elif args.serve:
        report = run_serve_drill(seed=args.seed, verbose=not args.json,
                                 supervised=not args.no_supervised)
    elif args.mem:
        report = run_mem_drill(seed=args.seed, verbose=not args.json)
    elif args.router:
        report = run_router_drill(seed=args.seed, verbose=not args.json)
    elif args.disagg:
        report = run_disagg_drill(seed=args.seed, verbose=not args.json)
    elif args.fleet_obs:
        report = run_fleet_obs_drill(seed=args.seed,
                                     verbose=not args.json)
    elif args.elastic:
        report = run_elastic_drill(seed=args.seed,
                                   verbose=not args.json)
    elif args.partition:
        report = run_partition_drill(seed=args.seed,
                                     verbose=not args.json)
    elif args.lossy:
        report = run_lossy_drill(seed=args.seed, verbose=not args.json)
    elif args.lockcheck:
        report = run_lockcheck_drill(seed=args.seed,
                                     verbose=not args.json)
    elif args.wirecheck:
        report = run_wirecheck_drill(seed=args.seed,
                                     verbose=not args.json)
    else:
        report = run_drill(seed=args.seed, verbose=not args.json)
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
