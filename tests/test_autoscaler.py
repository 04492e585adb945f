"""Elastic fleet control plane: signal-driven autoscaling that is
lossless by construction.

PR 17's acceptance pins live here:

  * the satellite fixes — the fleet-obs headroom cache NEVER survives a
    replica-count or role-set change (it used to be priced once and
    returned forever), a dead replica's slot is tombstone-REUSED by
    ``add_replica`` (a long-lived elastic fleet no longer grows its
    replica list without bound) with fleet telemetry counting live
    replicas only, and a ``decommission(deadline_s=)`` whose grace
    budget blows mid-drain still hands its partial manifest off
    losslessly — the late replica is forced dead, never half-alive;
  * the ``FleetAutoscaler`` policy: spawn above the up band / retire
    below the down band / role rebalance outside the ratio band, under
    hysteresis, per-action cooldowns and the min/max envelope — and
    scale-down rides the PR 13/15 drain-manifest machinery so nothing
    ever parks;
  * the actuation path is chaos-probed: a faulted spawn degrades to
    backoff-and-hold (recorded, fleet unchanged, NO raise into the
    ``step_all`` driver) and actuates clean once the hold-down expires;
  * every decision is evidence: structured ``AutoscaleEvent``s on the
    autoscaler ledger AND the ``signals()["autoscale"]`` ring
    (JSON-roundtrip-stable, rendered by ``serve_top``), and the
    ``fleet_scale_*`` instrument seams record when metrics are armed;
  * the ``chaos_drill --elastic`` double run: one spawn, one retire,
    the fleet back at its minimum, outputs equal to the fixed fleet's,
    stable subset bit-identical per seed.
"""
import functools
import importlib
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import instrument
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import (AutoscaleEvent, AutoscalerConfig,
                                EngineConfig, FleetAutoscaler,
                                FleetObsConfig, ReplicaRouter, ServingEngine)

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
sys.path.insert(0, TOOLS)

pytestmark = pytest.mark.elastic

VOCAB = 61


@functools.lru_cache(maxsize=None)
def _model(seed=3):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab_size=VOCAB, hidden_size=32, layers=2,
                           heads=4, kv_heads=2, seq=128)
    cfg.use_flash_attention = False
    return LlamaForCausalLM(cfg)


def _engine(model, role=None, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("token_budget", 24)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return ServingEngine(model, EngineConfig(role=role, **kw))


def _prompts(n, seed=0, lo=6, hi=12):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, (int(rng.integers(lo, hi)),)).tolist()
            for _ in range(n)]


def _drive(router, scaler=None, max_passes=500):
    passes = 0
    while router.has_work():
        router.step_all()
        if scaler is not None:
            scaler.control()
        passes += 1
        assert passes < max_passes, "fleet never drained"
    return passes


def _finished(handles, router):
    """Every request's FINAL handle (original, or its last hand-off
    replacement) — the lossless-by-construction merge."""
    final = dict(handles)
    for rec in router.handoffs:
        for h in rec["handles"]:
            final[h.tag["tag"]] = h
    return final


# -- satellite 1: the headroom cache must never go stale ----------------------

MODEL_CFG = {"hidden_size": 32, "num_hidden_layers": 2,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "intermediate_size": 64, "vocab_size": VOCAB,
             "max_position_embeddings": 128}


class TestHeadroomCacheStaleness:
    def _router(self, model, n=1):
        return ReplicaRouter(
            [_engine(model) for _ in range(n)], policy="round_robin",
            fleet_obs=FleetObsConfig(window=8, model_cfg=MODEL_CFG,
                                     hbm_gib=16.0))

    def _count_plans(self, monkeypatch):
        import mem_report
        calls = []
        real = mem_report.plan

        def counting(*a, **kw):
            calls.append(kw.get("role"))
            return real(*a, **kw)
        monkeypatch.setattr(mem_report, "plan", counting)
        return calls

    def test_cached_while_fleet_shape_stable(self, monkeypatch):
        model = _model()
        router = self._router(model)
        calls = self._count_plans(monkeypatch)
        router.step_all()
        first = router.signals()["fleet"]["headroom"]
        assert first is not None and "unified" in first["per_role"]
        n0 = len(calls)
        assert n0 >= 1
        router.step_all()
        router.signals()
        assert len(calls) == n0, "stable fleet must reuse the cache"

    def test_add_replica_invalidates(self, monkeypatch):
        model = _model()
        router = self._router(model)
        calls = self._count_plans(monkeypatch)
        router.step_all()
        router.signals()
        n0 = len(calls)
        router.add_replica(_engine(model))
        router.signals()
        assert len(calls) > n0, \
            "replica-count change must reprice headroom (stale-cache " \
            "satellite fix)"

    def test_role_set_change_invalidates(self, monkeypatch):
        model = _model()
        router = ReplicaRouter(
            [_engine(model, role="prefill"),
             _engine(model, role="decode"),
             _engine(model, role="decode")],
            policy="affinity",
            fleet_obs=FleetObsConfig(window=8, model_cfg=MODEL_CFG,
                                     hbm_gib=16.0))
        calls = self._count_plans(monkeypatch)
        router.step_all()
        before = router.signals()["fleet"]["headroom"]
        assert set(before["per_role"]) == {"prefill", "decode"}
        n0 = len(calls)
        router.signals()
        assert len(calls) == n0
        router.set_role(2, "prefill", deadline_s=0.0)
        router.signals()
        assert len(calls) > n0, "role-set change must reprice headroom"

    def test_on_fleet_change_clears_reused_slot_ring(self):
        model = _model()
        router = self._router(model, n=2)
        for i, p in enumerate(_prompts(4)):
            router.submit(p, max_new_tokens=3, tag=i)
        router.step_all()
        fo = router.fleet_obs
        assert 1 in fo._rings and len(fo._rings[1]) > 0
        router.fail_replica(1)
        router.add_replica(_engine(model))
        # the reused slot's new occupant must not inherit the dead
        # engine's sample history
        assert 1 not in fo._rings or len(fo._rings[1]) == 0
        assert fo._headroom_cache is None
        _drive(router)


# -- satellite 2: dead slots are tombstone-reused -----------------------------

class TestTombstoneReuse:
    def test_add_replica_reuses_dead_slot(self):
        model = _model()
        router = ReplicaRouter([_engine(model) for _ in range(2)],
                               policy="round_robin",
                               fleet_obs=FleetObsConfig(window=8))
        handles = {i: router.submit(p, max_new_tokens=4, tag=i)
                   for i, p in enumerate(_prompts(6))}
        router.step_all()
        router.fail_replica(1)
        tel = router.telemetry()["router"]
        assert tel["dead_slots"] == 1
        idx = router.add_replica(_engine(model))
        assert idx == 1, "add_replica must reuse the tombstoned slot"
        assert len(router.replicas) == 2, \
            "an elastic fleet must not grow its replica list unboundedly"
        tel = router.telemetry()["router"]
        assert tel["dead_slots"] == 0
        assert tel["reused_slots"] == 1 and tel["spawns"] == 1
        _drive(router)
        for t, h in _finished(handles, router).items():
            assert h.done and h.error is None, f"request {t} lost"

    def test_fresh_slot_when_none_dead(self):
        model = _model()
        router = ReplicaRouter([_engine(model)], policy="round_robin",
                               fleet_obs=FleetObsConfig(window=8))
        idx = router.add_replica(_engine(model))
        assert idx == 1 and len(router.replicas) == 2
        assert router.telemetry()["router"]["reused_slots"] == 0

    def test_telemetry_counts_live_only(self):
        model = _model()
        router = ReplicaRouter([_engine(model) for _ in range(2)],
                               policy="round_robin",
                               fleet_obs=FleetObsConfig(window=8))
        for i, p in enumerate(_prompts(6)):
            router.submit(p, max_new_tokens=3, tag=i)
        router.fail_replica(1)
        tel = router.telemetry()
        live_depth = len(router.replicas[0].sched.waiting)
        assert tel["fleet"]["queue_depth"] == live_depth, \
            "fleet queue_depth must not count tombstoned slots"
        _drive(router)

    def test_add_replica_validates_geometry(self):
        model = _model()
        router = ReplicaRouter([_engine(model)], policy="round_robin",
                               fleet_obs=FleetObsConfig(window=8))
        with pytest.raises(ValueError):
            router.add_replica(_engine(model, block_size=16))


# -- satellite 3: deadline blow mid-drain stays lossless ----------------------

class TestDecommissionDeadline:
    def test_blown_deadline_replays_partial_manifest(self):
        model = _model()
        router = ReplicaRouter([_engine(model) for _ in range(2)],
                               policy="round_robin",
                               fleet_obs=FleetObsConfig(window=8))
        handles = {i: router.submit(p, max_new_tokens=6, tag=i)
                   for i, p in enumerate(_prompts(8))}
        for _ in range(2):
            router.step_all()
        victim = router.replicas[0]
        live_before = (len(victim.sched.waiting)
                       + len(victim.sched.running))
        assert live_before >= 1, "drill needs mid-flight work"
        # deadline_s=0.0: the grace budget is blown before a single
        # drain step — the manifest is partial by construction
        replacements = router.decommission(0, deadline_s=0.0)
        assert len(replacements) == live_before, \
            "every unfinished request must hand off"
        # never half-alive: the slot is dead, the engine holds nothing
        assert router._alive[0] is False
        assert not victim.sched.waiting and not victim.sched.running
        assert 0 not in router._routable()
        assert router.handoffs and router.handoffs[-1]["reason"] == "drain"
        _drive(router)
        for t, h in _finished(handles, router).items():
            assert h.done and h.error is None, \
                f"request {t} parked across the blown deadline"

    def test_decommission_dead_slot_is_noop(self):
        model = _model()
        router = ReplicaRouter([_engine(model) for _ in range(2)],
                               policy="round_robin",
                               fleet_obs=FleetObsConfig(window=8))
        router.fail_replica(1)
        assert router.decommission(1, deadline_s=0.0) == []


# -- the autoscaler policy ----------------------------------------------------

class TestAutoscalerPolicy:
    def _scaled(self, model, n=1, **cfg_kw):
        router = ReplicaRouter([_engine(model) for _ in range(n)],
                               policy="round_robin",
                               fleet_obs=FleetObsConfig(window=16))
        cfg_kw.setdefault("min_replicas", 1)
        cfg_kw.setdefault("max_replicas", 3)
        cfg_kw.setdefault("cooldown", 1)
        scaler = FleetAutoscaler(
            router, engine_factory=lambda role: _engine(model, role=role),
            config=AutoscalerConfig(**cfg_kw))
        return router, scaler

    def test_config_validation(self):
        with pytest.raises(ValueError):
            AutoscalerConfig(min_replicas=0)
        with pytest.raises(ValueError):
            AutoscalerConfig(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError):
            AutoscalerConfig(scale_up_pressure=1.0,
                             scale_down_pressure=1.0)
        with pytest.raises(ValueError):
            AutoscalerConfig(rebalance_high=0.3, rebalance_low=0.5)
        with pytest.raises(ValueError):
            AutoscalerConfig(cooldown=0)

    def test_needs_the_signal_bus(self):
        model = _model()
        router = ReplicaRouter([_engine(model)], policy="round_robin")
        with pytest.raises(ValueError):
            FleetAutoscaler(router, engine_factory=lambda r: None)

    def test_spawn_on_pressure_and_envelope_ceiling(self):
        model = _model()
        router, scaler = self._scaled(model, max_replicas=2,
                                      scale_up_pressure=1.0,
                                      scale_down_pressure=0.1)
        handles = {i: router.submit(p, max_new_tokens=4, tag=i)
                   for i, p in enumerate(_prompts(12))}
        router.step_all()
        ev = scaler.control()
        assert ev is not None and (ev.rule, ev.action, ev.outcome) == \
            ("pressure_high", "spawn", "ok")
        assert sum(router._alive) == 2 and scaler.spawns == 1
        router.step_all()
        assert scaler.control() is None, \
            "at the envelope ceiling the spawn rule must not fire"
        _drive(router, scaler)
        for t, h in _finished(handles, router).items():
            assert h.done and h.error is None

    def test_cooldown_gates_refiring(self):
        model = _model()
        router, scaler = self._scaled(model, max_replicas=3,
                                      scale_up_pressure=0.5,
                                      scale_down_pressure=0.1,
                                      cooldown=1000)
        for i, p in enumerate(_prompts(12)):
            router.submit(p, max_new_tokens=4, tag=i)
        router.step_all()
        assert scaler.control().action == "spawn"
        router.step_all()
        assert scaler.control() is None, \
            "the cooldown must gate a second spawn"
        _drive(router)

    def test_retire_to_min_floor(self):
        model = _model()
        router, scaler = self._scaled(model, n=3, min_replicas=1,
                                      scale_up_pressure=1e9,
                                      scale_down_pressure=0.5)
        # an idle fleet is all-cold: pressure 0 < the down band
        router.step_all()
        ev = scaler.control()
        assert (ev.rule, ev.action, ev.outcome) == \
            ("pressure_low", "retire", "ok")
        assert sum(router._alive) == 2
        router.step_all()
        assert scaler.control().action == "retire"
        assert sum(router._alive) == 1
        router.step_all()
        assert scaler.control() is None, \
            "the min envelope must stop the retire rule"
        assert scaler.retires == 2

    def test_retire_is_lossless(self):
        model = _model()
        router, scaler = self._scaled(model, n=2, min_replicas=1,
                                      scale_up_pressure=1e9,
                                      scale_down_pressure=1e8,
                                      drain_deadline_s=0.0)
        handles = {i: router.submit(p, max_new_tokens=5, tag=i)
                   for i, p in enumerate(_prompts(8))}
        router.step_all()
        ev = scaler.control()
        assert ev.action == "retire" and ev.outcome == "ok"
        assert ev.detail["replayed"] >= 1, \
            "the retired replica held work — it must hand off"
        _drive(router, scaler)
        for t, h in _finished(handles, router).items():
            assert h.done and h.error is None, f"request {t} parked"

    def test_headroom_gate_skips_spawn(self):
        model = _model()
        router = ReplicaRouter(
            [_engine(model)], policy="round_robin",
            fleet_obs=FleetObsConfig(window=16, model_cfg=MODEL_CFG,
                                     hbm_gib=16.0))
        scaler = FleetAutoscaler(
            router, engine_factory=lambda role: _engine(model),
            config=AutoscalerConfig(max_replicas=3, cooldown=1,
                                    scale_up_pressure=0.5))
        for i, p in enumerate(_prompts(12)):
            router.submit(p, max_new_tokens=3, tag=i)
        router.step_all()
        # force the priced signal to say "does not fit"
        fo = router.fleet_obs
        head = fo._headroom(router)
        assert head is not None
        head["per_role"]["unified"]["fits"] = False
        ev = scaler.control()
        assert (ev.action, ev.outcome) == ("spawn", "skipped")
        assert ev.detail["skip"] == "no_headroom"
        assert sum(router._alive) == 1 and scaler.spawns == 0
        _drive(router)

    def test_control_never_raises_into_the_driver(self, monkeypatch):
        model = _model()
        router, scaler = self._scaled(model)
        monkeypatch.setattr(router, "signals",
                            lambda: (_ for _ in ()).throw(
                                RuntimeError("bus down")))
        assert scaler.control() is None   # fenced, not raised

    def test_telemetry_shape(self):
        model = _model()
        router, scaler = self._scaled(model)
        tel = scaler.telemetry()
        assert tel["envelope"] == {"min": 1, "max": 3}
        assert tel["ticks"] == 0 and tel["events"] == 0


# -- chaos: faulted actuation degrades to backoff-and-hold --------------------

class TestChaosActuation:
    def test_spawn_fault_degrades_then_recovers(self):
        model = _model()
        router = ReplicaRouter([_engine(model)], policy="round_robin",
                               fleet_obs=FleetObsConfig(window=16))
        scaler = FleetAutoscaler(
            router, engine_factory=lambda role: _engine(model),
            config=AutoscalerConfig(max_replicas=2, cooldown=1,
                                    backoff=3, scale_up_pressure=0.5,
                                    scale_down_pressure=0.1))
        plan = chaos.FaultPlan(seed=0).add("elastic.spawn", "error",
                                           at=(1,))
        chaos.install_plan(plan)
        try:
            handles = {i: router.submit(p, max_new_tokens=4, tag=i)
                       for i, p in enumerate(_prompts(12))}
            outcomes = []
            for _ in range(8):
                router.step_all()           # the fault must not reach here
                ev = scaler.control()
                if ev is not None:
                    outcomes.append(ev.outcome)
            assert outcomes[0] == "fault"
            assert "backoff_hold" in outcomes
            assert outcomes[-1] == "ok", outcomes
            assert plan.fired and plan.fired[0][0] == "elastic.spawn"
            assert scaler.faults == 1 and scaler.spawns == 1
            assert sum(router._alive) == 2
            fault_ev = next(e for e in scaler.events
                            if e.outcome == "fault")
            assert fault_ev.signal["alive"] == 1, \
                "a faulted spawn must leave the current fleet serving"
            _drive(router, scaler)
            for t, h in _finished(handles, router).items():
                assert h.done and h.error is None
        finally:
            chaos.clear_plan()

    def test_consecutive_faults_double_the_holddown(self):
        model = _model()
        router = ReplicaRouter([_engine(model)], policy="round_robin",
                               fleet_obs=FleetObsConfig(window=16))
        scaler = FleetAutoscaler(
            router, engine_factory=lambda role: _engine(model),
            config=AutoscalerConfig(max_replicas=4, cooldown=1,
                                    backoff=2, scale_up_pressure=0.5))
        plan = chaos.FaultPlan(seed=0).add("elastic.spawn", "error",
                                           at=(1, 2))
        chaos.install_plan(plan)
        try:
            for i, p in enumerate(_prompts(12)):
                router.submit(p, max_new_tokens=6, tag=i)
            holds = []
            for _ in range(12):
                router.step_all()
                ev = scaler.control()
                if ev is not None and ev.outcome == "fault":
                    holds.append(ev.detail["backoff_until"] - ev.tick)
            assert holds == [2, 4], \
                f"hold-down must double per consecutive fault: {holds}"
            _drive(router, scaler)
        finally:
            chaos.clear_plan()


# -- role rebalance (disaggregated) -------------------------------------------

class TestRebalance:
    def test_ratio_high_flips_a_decode_replica(self):
        model = _model()
        router = ReplicaRouter(
            [_engine(model, role="prefill"),
             _engine(model, role="decode", token_budget=16),
             _engine(model, role="decode", token_budget=16)],
            policy="affinity", fleet_obs=FleetObsConfig(window=16))
        scaler = FleetAutoscaler(
            router, engine_factory=lambda role: _engine(model, role=role),
            config=AutoscalerConfig(min_replicas=3, max_replicas=3,
                                    cooldown=1, rebalance_high=2.0,
                                    drain_deadline_s=0.0))
        handles = {i: router.submit(p, max_new_tokens=4, tag=i)
                   for i, p in enumerate(_prompts(12))}
        ev = None
        for _ in range(40):
            router.step_all()
            ev = scaler.control()
            if ev is not None and ev.action == "rebalance":
                break
        assert ev is not None and ev.action == "rebalance", \
            "a prefill-bound flood must trip the ratio band"
        assert (ev.rule, ev.outcome) == ("ratio_high", "ok")
        assert ev.detail["new_role"] == "prefill"
        assert len(router.prefill_pool) == 2
        assert len(router.decode_pool) == 1
        assert scaler.rebalances == 1
        _drive(router, scaler)
        for t, h in _finished(handles, router).items():
            assert h.done and h.error is None, f"request {t} parked"

    def test_rebalance_spares_the_last_replica_of_a_role(self):
        model = _model()
        router = ReplicaRouter(
            [_engine(model, role="prefill"),
             _engine(model, role="decode", token_budget=16)],
            policy="affinity", fleet_obs=FleetObsConfig(window=16))
        scaler = FleetAutoscaler(
            router, engine_factory=lambda role: _engine(model, role=role),
            config=AutoscalerConfig(min_replicas=2, max_replicas=2,
                                    cooldown=1, rebalance_high=1.5))
        for i, p in enumerate(_prompts(8)):
            router.submit(p, max_new_tokens=3, tag=i)
        for _ in range(6):
            router.step_all()
            ev = scaler.control()
            assert ev is None or ev.action != "rebalance", \
                "must never flip a role's LAST replica"
        _drive(router)

    def test_set_role_revalidates_spec_prefill(self):
        model = _model()
        eng = _engine(model, role="decode", spec_method="ngram",
                      num_draft_tokens=2)
        with pytest.raises(ValueError):
            eng.set_role("prefill")   # a prefill engine never samples

    def test_set_role_refuses_live_requests(self):
        model = _model()
        eng = _engine(model, role="decode")
        eng.submit(_prompts(1)[0], max_new_tokens=4, tag=0)
        with pytest.raises(RuntimeError):
            eng.set_role("prefill")

    def test_router_set_role_validates(self):
        model = _model()
        router = ReplicaRouter(
            [_engine(model, role="prefill"),
             _engine(model, role="decode", token_budget=16)],
            policy="affinity", fleet_obs=FleetObsConfig(window=16))
        with pytest.raises(ValueError):
            router.set_role(0, "draft")
        unified = ReplicaRouter([_engine(model)], policy="round_robin",
                                fleet_obs=FleetObsConfig(window=16))
        with pytest.raises(ValueError):
            unified.set_role(0, "prefill")


# -- evidence: events, signal ring, metrics, serve_top ------------------------

class TestEvidence:
    def test_events_on_the_signal_ring_roundtrip_json(self):
        model = _model()
        router = ReplicaRouter([_engine(model)], policy="round_robin",
                               fleet_obs=FleetObsConfig(window=16))
        scaler = FleetAutoscaler(
            router, engine_factory=lambda role: _engine(model),
            config=AutoscalerConfig(max_replicas=2, cooldown=2,
                                    scale_up_pressure=0.5,
                                    scale_down_pressure=0.2,
                                    drain_deadline_s=0.0))
        handles = {i: router.submit(p, max_new_tokens=4, tag=i)
                   for i, p in enumerate(_prompts(10))}
        _drive(router, scaler)
        assert scaler.spawns >= 1 and scaler.retires >= 1
        sig = router.signals()
        ring = sig["autoscale"]
        assert len(ring) == len(scaler.events)
        assert ring == json.loads(json.dumps(ring)), \
            "the autoscale ring must be JSON-roundtrip-stable"
        for raw, ev in zip(ring, scaler.events):
            assert isinstance(ev, AutoscaleEvent)
            assert raw == ev.to_dict()
            assert raw["outcome"] in ("ok", "fault", "skipped",
                                      "backoff_hold")
        for t, h in _finished(handles, router).items():
            assert h.done and h.error is None

        import serve_top
        panel = serve_top.render_fleet_signals(
            json.loads(json.dumps(sig)))
        assert "autoscale" in panel and "spawn" in panel

    def test_fleet_scale_metrics_recorded(self):
        from paddle_tpu.profiler import metrics
        model = _model()
        metrics.enable_metrics()
        try:
            metrics.reset_registry()
            router = ReplicaRouter([_engine(model)],
                                   policy="round_robin",
                                   fleet_obs=FleetObsConfig(window=16))
            scaler = FleetAutoscaler(
                router, engine_factory=lambda role: _engine(model),
                config=AutoscalerConfig(max_replicas=2, cooldown=1,
                                        scale_up_pressure=0.5))
            for i, p in enumerate(_prompts(10)):
                router.submit(p, max_new_tokens=3, tag=i)
            router.step_all()
            scaler.control()              # fires the spawn
            router.step_all()
            scaler.control()              # gauges the post-spawn fleet
            snap = metrics.get_registry().snapshot()
            gauges = {k: v for k, v in snap.items()
                      if k.startswith("fleet_replicas")}
            assert any(v == 2.0 for g in gauges.values()
                       for v in (g.values() if isinstance(g, dict)
                                 else [g]))
            events = {k: v for k, v in snap.items()
                      if k.startswith("fleet_scale_events_total")}
            assert events, "spawn must land on the events counter"
            assert any(k.startswith("fleet_autoscale_decision_seconds")
                       for k in snap)
            _drive(router, scaler)
        finally:
            metrics.disable_metrics()

    def test_catalog_lists_the_new_metrics(self):
        for name in ("fleet_replicas", "fleet_scale_events_total",
                     "fleet_autoscale_decision_seconds"):
            assert name in instrument.CATALOG

    def test_chaos_sites_registered(self):
        assert chaos.SITES.get("elastic.spawn") == "site"
        assert chaos.SITES.get("elastic.retire") == "site"


# -- the elastic drill (tier-1) -----------------------------------------------

class TestDrill:
    def test_chaos_drill_elastic_stable_per_seed(self):
        spec = importlib.util.spec_from_file_location(
            "chaos_drill", os.path.join(TOOLS, "chaos_drill.py"))
        drill = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(drill)
        r1 = drill.run_elastic_drill(seed=1234, verbose=False)
        r2 = drill.run_elastic_drill(seed=1234, verbose=False)
        assert r1["ok"] and r2["ok"]
        assert r1["stable"] == r2["stable"], \
            "the elastic drill's stable subset must be bit-identical " \
            "per seed"
        s = r1["stable"]
        assert s["spawns"] == 1 and s["retires"] == 1 and s["faults"] == 1
        assert s["retire_replayed"] >= 1
        assert s["alive_at_end"] == 1     # back at the minimum envelope
        assert s["replay_crc"] == s["oracle_crc"]
