"""Fault-domain serving fabric: transport + membership + two-phase handoff.

What this file pins, layer by layer:

  * ``serving/transport.py`` mechanics — per-link FIFO ordering, seeded
    chaos faults (drop/dup/delay/reorder/link-partition/torn-recv),
    idempotency-keyed dedup with cached-ack re-send (the torn-ack
    recovery), hold-back re-sequencing with gap expiry, ack-tracked
    retransmits on ``RetryPolicy``'s seeded tick backoff, give-up
    poisoning (a late copy can never deliver after the sender
    recovered), and bit-deterministic counters per seed;
  * ``serving/membership.py`` — the live → suspect → dead lease
    machine: quiet suspects, heartbeats heal, leases expire exactly
    once, dead members are fenced until an explicit re-join;
  * the router integration — armed fault-free byte-identical to the
    disarmed synchronous path, two-phase prepare/commit/abort leaving
    both pools garbage-free under any fault, SUSPECT stopping dispatch
    WITHOUT salvage (healed partition ⇒ no double-decode), lease
    expiry driving the one shared salvage path, and the two-failure
    composition regression (prefill dies mid-handoff AND the chosen
    decode target dies: third survivor serves, exactly one lifecycle
    finish, zero leaked in-flight state);
  * the registries — chaos SITES, instrument CATALOG, WIRE_SCHEMAS
    key-hash pins, LOCK_ORDER — tracking the new planes.
"""
import functools
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import (EngineConfig, MembershipConfig,
                                MembershipTable, ReplicaRouter,
                                ReplicaTransport, ServingEngine,
                                TransportConfig, resolve_membership,
                                resolve_transport)
from paddle_tpu.serving import membership as mem_mod
from paddle_tpu.serving import transport as tp_mod
from paddle_tpu.serving.resilience import AdmissionRejected

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
sys.path.insert(0, TOOLS)

pytestmark = pytest.mark.transport


# -- transport unit harness ----------------------------------------------------
def _tp(**kw):
    return ReplicaTransport(TransportConfig(**kw))


def _wire_two(t, a="a", b="b"):
    """Two endpoints with recording handlers; returns (log_a, log_b)."""
    la, lb = [], []
    t.register(a, la.append)
    t.register(b, lb.append)
    return la, lb


def _run(t, ticks):
    for _ in range(ticks):
        t.advance()
        t.pump()


@pytest.fixture(autouse=True)
def _no_chaos():
    chaos.clear_plan()
    yield
    chaos.clear_plan()


# -- transport: ordering & delivery --------------------------------------------
def test_send_delivers_in_order():
    t = _tp()
    _, lb = _wire_two(t)
    for i in range(5):
        t.send("a", "b", kind="k", family="f", record={"i": i})
    _run(t, 1)
    assert [m.record["i"] for m in lb] == [0, 1, 2, 3, 4]
    assert t.counters["delivered"] == 5 and not t.busy()


def test_per_link_sequences_are_independent():
    t = _tp()
    log = []
    for ep in ("x", "y", "z"):
        t.register(ep, log.append)
    t.send("x", "z", kind="k", family="f", record={"n": 1})
    t.send("y", "z", kind="k", family="f", record={"n": 2})
    t.send("x", "z", kind="k", family="f", record={"n": 3})
    _run(t, 1)
    assert [m.record["n"] for m in log] == [1, 2, 3]
    # link (x,z) advanced to 2, link (y,z) to 1 — no cross-link gaps
    assert t._send_seq[("x", "z")] == 2 and t._send_seq[("y", "z")] == 1


def test_unroutable_endpoint_counted_not_raised():
    t = _tp()
    t.send("a", "ghost", kind="k", family="f", record={})
    _run(t, 1)
    assert t.counters["unroutable"] == 1 and not t.busy()


def test_busy_tracks_queue_pending_and_holdback():
    t = _tp()
    _wire_two(t)
    assert not t.busy()
    t.send("a", "b", kind="k", family="f", record={}, needs_ack=True)
    assert t.busy()                      # in flight + pending ack
    _run(t, 1)
    assert t.busy()                      # delivered, still unacked
    t.resolve(list(t._pending)[0])
    assert not t.busy()


# -- transport: chaos faults ---------------------------------------------------
def test_chaos_drop_fault_drops_one_message():
    chaos.install_plan(chaos.FaultPlan(seed=1).add(
        "transport.send", "error", "drop", at=(1,)))
    t = _tp()
    _, lb = _wire_two(t)
    t.send("a", "b", kind="k", family="f", record={"n": 1})
    t.send("a", "b", kind="k", family="f", record={"n": 2})
    _run(t, 4)                           # past the reorder horizon
    assert [m.record["n"] for m in lb] == [2]
    assert t.counters["dropped"] == 1 and t.counters["gap_skips"] == 1


def test_chaos_dup_fault_delivers_exactly_once():
    chaos.install_plan(chaos.FaultPlan(seed=1).add(
        "transport.send", "error", "dup", at=(1,)))
    t = _tp()
    _, lb = _wire_two(t)
    t.send("a", "b", kind="k", family="f", record={"n": 1})
    _run(t, 2)
    assert [m.record["n"] for m in lb] == [1]
    assert t.counters["duplicate"] == 1 and t.counters["deduped"] == 1


def test_chaos_delay_fault_holds_n_ticks():
    chaos.install_plan(chaos.FaultPlan(seed=1).add(
        "transport.send", "delay", "3", at=(1,)))
    t = _tp()
    _, lb = _wire_two(t)
    t.send("a", "b", kind="k", family="f", record={"n": 1})
    _run(t, 2)
    assert lb == []                      # still held
    _run(t, 2)
    assert [m.record["n"] for m in lb] == [1]
    assert t.counters["delayed"] == 1


def test_chaos_reorder_fault_is_resequenced():
    chaos.install_plan(chaos.FaultPlan(seed=1).add(
        "transport.send", "error", "reorder", at=(1,)))
    t = _tp()
    _, lb = _wire_two(t)
    t.send("a", "b", kind="k", family="f", record={"n": 1})  # held 1 tick
    t.send("a", "b", kind="k", family="f", record={"n": 2})  # overtakes
    t.pump()                             # seq 1 lands first: held back
    _run(t, 2)
    # seq 1 arrived first, was held back, and released IN ORDER once
    # seq 0 landed — the wire reordered, the receiver did not
    assert [m.record["n"] for m in lb] == [1, 2]
    assert t.counters["reordered"] == 1


def test_gap_expiry_skips_a_hole_that_never_fills():
    chaos.install_plan(chaos.FaultPlan(seed=1).add(
        "transport.send", "error", "drop", at=(1,)))
    t = _tp(reorder_window=2)
    _, lb = _wire_two(t)
    t.send("a", "b", kind="k", family="f", record={"n": 1})  # dropped
    t.send("a", "b", kind="k", family="f", record={"n": 2})  # seq 1
    _run(t, 1)
    assert lb == []                      # held behind the hole
    _run(t, 2)                           # horizon passes: skip the gap
    assert [m.record["n"] for m in lb] == [2]
    assert t.counters["gap_skips"] == 1 and not t.busy()


def test_torn_recv_fault_recovers_via_retransmit():
    chaos.install_plan(chaos.FaultPlan(seed=1).add(
        "transport.recv", "error", None, at=(1,)))
    t = _tp()
    _, lb = _wire_two(t)
    t.send("a", "b", kind="k", family="f", record={"n": 1},
           needs_ack=True)
    _run(t, 6)
    assert [m.record["n"] for m in lb] == [1]     # second attempt landed
    assert t.counters["torn"] == 1 and t.counters["retransmits"] >= 1


def test_link_fault_partitions_the_link_for_n_ticks():
    chaos.install_plan(chaos.FaultPlan(seed=1).add(
        "transport.link", "error", "3", at=(1,)))
    t = _tp()
    _, lb = _wire_two(t)
    t.send("a", "b", kind="k", family="f", record={"n": 1})
    assert t.counters["partitioned"] == 1          # eaten at send
    t.send("b", "a", kind="k", family="f", record={"n": 2})
    assert t.counters["partitioned"] == 2          # bidirectional
    _run(t, 4)                                     # link back up
    t.send("a", "b", kind="k", family="f", record={"n": 3})
    _run(t, 4)
    assert [m.record["n"] for m in lb] == [3]


def test_programmatic_partition_and_heal():
    t = _tp()
    la, lb = _wire_two(t)
    t.partition("b")
    t.send("a", "b", kind="k", family="f", record={"n": 1})
    _run(t, 1)
    assert lb == [] and t.counters["partitioned"] == 1
    t.heal("b")
    assert not t.is_partitioned("b")
    t.send("a", "b", kind="k", family="f", record={"n": 2})
    _run(t, 4)
    assert [m.record["n"] for m in lb] == [2]


# -- transport: acks, retransmits, give-up -------------------------------------
def test_ack_ref_resolves_pending_without_retransmit():
    t = _tp()

    def b_handler(msg):
        ack = tp_mod.build_ack(msg.msg_id, "kv", None, "ok", None, 0)
        t.send("b", "a", kind="ack", family="kv_transfer_ack",
               record=ack, ack_ref=msg.msg_id)
    la = []
    t.register("a", la.append)
    t.register("b", b_handler)
    t.send("a", "b", kind="k", family="f", record={}, needs_ack=True)
    _run(t, 3)
    assert t.counters["acked"] == 1 and t.counters["retransmits"] == 0
    assert not t.busy() and len(la) == 1


def test_torn_ack_dedups_and_resends_cached_ack():
    """The torn-transfer case the two-phase design exists for: the
    import landed, the ACK died on the wire. The retransmitted prepare
    must be deduped (never re-delivered to the handler — no double
    admit) and the receiver must re-send the SAME cached ack."""
    handled = []
    t = _tp()

    def b_handler(msg):
        handled.append(msg)
        ack = tp_mod.build_ack(msg.msg_id, "kv", None, "ok", None, 0)
        t.send("b", "a", kind="ack", family="kv_transfer_ack",
               record=ack, ack_ref=msg.msg_id)
    la = []
    t.register("a", la.append)
    t.register("b", b_handler)
    # hit 1 = the prepare (delivered); hit 2 = the ack (torn at recv)
    chaos.install_plan(chaos.FaultPlan(seed=1).add(
        "transport.recv", "error", None, at=(2,)))
    t.send("a", "b", kind="k", family="f", record={}, needs_ack=True)
    _run(t, 10)
    assert len(handled) == 1             # never double-delivered
    assert t.counters["deduped"] >= 1    # the retransmit was suppressed
    assert t.counters["acked"] == 1 and not t.busy()


def test_giveup_fires_on_fail_and_poisons_late_copies():
    failures = []
    chaos.install_plan(chaos.FaultPlan(seed=1).add(
        "transport.send", "error", "drop", prob=1.0))
    t = _tp(max_attempts=3)
    _, lb = _wire_two(t)
    t.send("a", "b", kind="k", family="f", record={},
           needs_ack=True, on_fail=lambda m, why: failures.append(why),
           site="transport.kv_prepare")
    _run(t, 40)
    assert failures == ["ack_timeout"]
    assert t.counters["giveups"] == 1
    assert t.giveups_by_site == {"transport.kv_prepare": 1}
    assert lb == []                      # nothing ever landed
    # a late in-flight copy of the given-up message must die at delivery
    chaos.clear_plan()
    msg_id = next(iter(t._canceled))
    from paddle_tpu.serving.transport import Message
    late = Message("a", "b", "k", "f", {}, None, msg_id, 0, t.tick,
                   False, None, None, "transport.kv_prepare")
    with t._lock:
        t._queue.append(late)
    _run(t, 1)
    assert lb == [] and t.counters["canceled"] >= 1


def test_retransmit_reuses_msg_id_and_seq():
    chaos.install_plan(chaos.FaultPlan(seed=1).add(
        "transport.send", "error", "drop", at=(1,)))
    t = _tp()
    _, lb = _wire_two(t)
    mid = t.send("a", "b", kind="k", family="f", record={"n": 1},
                 needs_ack=True)
    _run(t, 6)
    assert [m.msg_id for m in lb] == [mid]
    assert [m.seq for m in lb] == [0]
    assert t.counters["retransmits"] >= 1
    assert t.retries_by_site.get("transport.k", 0) >= 1


def test_backoff_ticks_deterministic_per_seed():
    a = _tp(seed=11)
    b = _tp(seed=11)
    c = _tp(seed=12)
    sched_a = [a._backoff_ticks(i) for i in range(5)]
    sched_b = [b._backoff_ticks(i) for i in range(5)]
    sched_c = [c._backoff_ticks(i) for i in range(5)]
    assert sched_a == sched_b
    assert sched_a != sched_c or a.retry.jitter == 0
    # capped exponential in TICKS, never below one tick
    assert all(x >= 1 for x in sched_a)
    assert max(sched_a) <= int(round(a.config.backoff_max
                                     * (1 + a.config.backoff_jitter)))


def test_counters_deterministic_per_seed():
    def run_one():
        chaos.install_plan(
            chaos.FaultPlan(seed=5)
            .add("transport.send", "error", "drop", prob=0.2)
            .add("transport.send", "error", "dup", prob=0.1)
            .add("transport.recv", "delay", None, prob=0.1))
        t = _tp(seed=3)
        _, lb = _wire_two(t)
        for i in range(20):
            t.send("a", "b", kind="k", family="f", record={"n": i},
                   needs_ack=True)
            t.advance()
            t.pump()
        _run(t, 60)
        chaos.clear_plan()
        return dict(t.counters), [m.record["n"] for m in lb]
    c1, d1 = run_one()
    c2, d2 = run_one()
    assert c1 == c2 and d1 == d2


def test_transport_config_validation():
    with pytest.raises(ValueError):
        TransportConfig(dedup_window=-1)
    with pytest.raises(ValueError):
        TransportConfig(max_attempts=0)


def test_resolve_transport_conventions(monkeypatch):
    monkeypatch.delenv("PADDLE_SERVE_TRANSPORT", raising=False)
    assert resolve_transport(None) is None
    assert resolve_transport(False) is None
    assert isinstance(resolve_transport(True), ReplicaTransport)
    cfg = TransportConfig(max_attempts=2)
    t = resolve_transport(cfg)
    assert t.config is cfg
    assert resolve_transport(t) is t
    with pytest.raises(TypeError):
        resolve_transport(42)
    monkeypatch.setenv("PADDLE_SERVE_TRANSPORT", "1")
    assert isinstance(resolve_transport(None), ReplicaTransport)


# -- membership: the lease machine ---------------------------------------------
def test_membership_join_live_and_heartbeat_renews():
    m = MembershipTable(MembershipConfig(suspect_after=2, lease_ticks=6))
    m.join(0, tick=0, role="decode")
    assert m.state(0) == "live" and m.dispatchable(0)
    hb = mem_mod.build_heartbeat(0, 3, "decode", 6, 1, 7)
    assert m.heartbeat(hb) == "live"
    assert m.advance(5) == []            # lease renewed to 3+6=9
    tel = m.telemetry()
    assert tel["members"][0]["queue_depth"] == 1


def test_membership_quiet_suspect_then_lease_expiry():
    m = MembershipTable(MembershipConfig(suspect_after=2, lease_ticks=5))
    m.join(0, tick=0)
    out = m.advance(3)                   # quiet past suspect_after
    assert out == [(0, "live", "suspect", "quiet")]
    assert not m.dispatchable(0) and m.alive(0)
    out = m.advance(6)                   # past lease_until=5
    assert out == [(0, "suspect", "dead", "lease_expired")]
    assert not m.alive(0)
    assert m.advance(7) == []            # never re-reported


def test_membership_heartbeat_heals_suspect():
    m = MembershipTable(MembershipConfig(suspect_after=2, lease_ticks=8))
    m.join(0, tick=0)
    m.advance(3)
    assert m.state(0) == "suspect"
    m.heartbeat(mem_mod.build_heartbeat(0, 4, None, 8, 0, 0))
    assert m.state(0) == "live" and m.dispatchable(0)
    counts = m.telemetry()["transition_counts"]
    assert counts == {"live->suspect": 1, "suspect->live": 1}


def test_membership_dead_is_fenced_until_rejoin():
    m = MembershipTable(MembershipConfig(suspect_after=1, lease_ticks=3))
    m.join(0, tick=0)
    m.advance(10)
    assert m.state(0) == "dead"
    # an expired replica does NOT resurrect itself by talking again
    assert m.heartbeat(mem_mod.build_heartbeat(0, 11, None, 3, 0, 0)) \
        is None
    assert m.state(0) == "dead"
    m.join(0, tick=12)                   # the one authority that does
    assert m.state(0) == "live"
    assert m.telemetry()["transition_counts"]["dead->live"] == 1


def test_membership_kill_is_idempotent_and_reasoned():
    m = MembershipTable()
    m.join(0, tick=0)
    assert m.kill(0, tick=1, reason="autoscale_retire")
    assert not m.kill(0, tick=2, reason="death")
    assert m.kill(1, tick=2, reason="x") is False   # unknown member
    tick, rep, frm, to, why = m.transitions[-1]
    assert (rep, frm, to, why) == (0, "live", "dead", "autoscale_retire")


def test_membership_ledger_bounded():
    m = MembershipTable(MembershipConfig(suspect_after=1, lease_ticks=3))
    m.join(0, tick=0)
    for i in range(600):
        m.kill(0, tick=i, reason="r")
        m.join(0, tick=i)
    assert len(m.transitions) <= MembershipTable.LEDGER_CAP


def test_membership_config_validation():
    with pytest.raises(ValueError):
        MembershipConfig(suspect_after=0)
    with pytest.raises(ValueError):
        MembershipConfig(suspect_after=5, lease_ticks=5)


def test_resolve_membership_conventions(monkeypatch):
    monkeypatch.delenv("PADDLE_SERVE_MEMBERSHIP", raising=False)
    assert resolve_membership(None) is None
    assert isinstance(resolve_membership(True), MembershipTable)
    cfg = MembershipConfig(suspect_after=2, lease_ticks=9)
    assert resolve_membership(cfg).config is cfg
    with pytest.raises(TypeError):
        resolve_membership("yes")
    monkeypatch.setenv("PADDLE_SERVE_MEMBERSHIP", "1")
    assert isinstance(resolve_membership(None), MembershipTable)


def test_membership_requires_transport():
    eng = _mk_engine("prefill"), _mk_engine("decode")
    with pytest.raises(ValueError, match="transport"):
        ReplicaRouter(list(eng), membership=True)


# -- integration: the armed fleet ----------------------------------------------
@functools.lru_cache(maxsize=None)
def _model(seed=3, vocab=61):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab_size=vocab, hidden_size=32, layers=2,
                           heads=4, kv_heads=2, seq=128)
    cfg.use_flash_attention = False
    return LlamaForCausalLM(cfg)


def _mk_engine(role, seed=0, **kw):
    cfg = EngineConfig(max_seqs=2 if role == "prefill" else 4,
                       token_budget=16 if role == "prefill" else 8,
                       num_blocks=64, block_size=8, role=role, **kw)
    return ServingEngine(_model(), cfg, seed=seed)


def _prompts(n, vocab=61, seed=0):
    rng = np.random.default_rng(seed)
    lens = (7, 4, 11, 20, 9, 17)
    return [rng.integers(1, vocab, (lens[i % len(lens)],)).tolist()
            for i in range(n)]


def _drive(router, max_passes=600, hook=None):
    n = 0
    while True:
        more = router.step_all()
        n += 1
        if hook is not None:
            hook(n, router)
        if not more:
            return n
        assert n < max_passes, "fleet did not converge"


def _fleet(transport=None, membership=None, n_decode=2):
    engines = [_mk_engine("prefill")] + \
        [_mk_engine("decode") for _ in range(n_decode)]
    return ReplicaRouter(engines, seed=0, transport=transport,
                         membership=membership)


def _serve(router, n=4, max_new=4, hook=None):
    handles = [router.submit(p, max_new_tokens=max_new, tag=i)
               for i, p in enumerate(_prompts(n))]
    _drive(router, hook=hook)
    out = []
    for h in handles:
        try:
            out.append(tuple(h.result(timeout=10)))
        except Exception as exc:  # noqa: BLE001 — terminal is a result
            out.append((type(exc).__name__,))
    return out


_baseline_memo = {}


def _baseline(n=4, max_new=4):
    key = (n, max_new)
    if key not in _baseline_memo:
        _baseline_memo[key] = _serve(_fleet(), n, max_new)
    return _baseline_memo[key]


def test_armed_faultfree_bit_identical_to_disarmed():
    r = _fleet(transport=True, membership=True)
    out = _serve(r)
    assert out == _baseline()
    tel = r.telemetry()["router"]
    assert tel["transport"]["counters"]["retransmits"] == 0
    assert tel["transport"]["counters"]["giveups"] == 0
    assert tel["membership"]["states"] == {"live": 3, "suspect": 0,
                                           "dead": 0}
    assert tel["kv_handoffs"]["pages"] == 4
    assert not r.transport.busy() and r._inflight == {}


def test_two_phase_commit_leaves_pools_clean():
    r = _fleet(transport=True)
    out = _serve(r)
    assert out == _baseline()
    for eng in r.replicas:
        assert eng._pending_exports == {}
        tel = eng.telemetry()["pool"]
        # garbage-free: no page is parked (cached pages are reclaimable
        # prefix cache, not garbage — free + cached accounts for all)
        assert tel["used"] == 0
        assert tel["free"] + tel["cached"] == tel["size"]


def test_import_fault_aborts_and_recomputes_garbage_free():
    from paddle_tpu.serving import PoolExhausted
    r = _fleet(transport=True)
    armed = {"left": 1}
    for eng in r.replicas[1:]:           # first import refuses, once
        orig = eng.import_handoff

        def wrapped(req, record, _orig=orig):
            if armed["left"]:
                armed["left"] -= 1
                raise PoolExhausted("injected import refusal")
            _orig(req, record)
        eng.import_handoff = wrapped
    out = _serve(r)
    assert out == _baseline()            # degraded, never wrong
    kh = r.telemetry()["router"]["kv_handoffs"]
    assert kh["recompute"] >= 1
    assert kh["pages"] + kh["recompute"] == 4
    for eng in r.replicas:
        assert eng._pending_exports == {}
        assert eng.telemetry()["pool"]["used"] == 0


def test_duplicate_import_rejected_at_the_engine():
    """The no-dedup baseline's double-decode hole is closed at the
    engine too: an already-admitted hand-off refuses re-admission."""
    pre = _mk_engine("prefill")
    dec = _mk_engine("decode")
    pre.submit(_prompts(1)[0], max_new_tokens=3)
    pre.run_until_idle(max_steps=100)
    (req, record), = pre.pop_handoffs()
    dec.import_handoff(req, record)
    with pytest.raises(AdmissionRejected, match="duplicate_import"):
        dec.import_handoff(req, record)
    dec.run_until_idle(max_steps=100)
    assert len(req.result(timeout=10)) == 3
    assert dec.kv_handoffs_in == 1


@pytest.mark.parametrize("transport, membership, fired", [
    (True, True, ("dropped", "deduped")),
    # no dedup window, no retransmit, no leases: the duplicate IS
    # delivered, and what sits above the transport must absorb it
    (TransportConfig(max_attempts=1, dedup_window=0), None,
     ("duplicate",)),
], ids=["full_stack", "no_dedup_no_lease"])
def test_lossy_links_converge_to_faultfree_outputs(transport, membership,
                                                   fired):
    chaos.install_plan(
        chaos.FaultPlan(seed=9)
        .add("transport.send", "error", "drop", prob=0.05)
        .add("transport.send", "error", "dup", prob=0.05)
        .add("transport.send", "delay", "1", prob=0.05))
    counts = {}
    r = _fleet(transport=transport, membership=membership)
    handles = []
    for i, p in enumerate(_prompts(4)):
        counts[i] = 0

        def cb(tok, i=i):
            counts[i] += 1
        handles.append(r.submit(p, max_new_tokens=4, on_token=cb, tag=i))
    _drive(r)
    out = [tuple(h.result(timeout=10)) for h in handles]
    assert out == _baseline()
    # exactly-once token emission: no request ever decoded twice
    assert counts == {i: len(out[i]) for i in range(4)}
    assert r._pending_handoffs == [] and r._inflight == {}
    # the plan had teeth: a lossy run that loses nothing proves nothing
    assert all(r.transport.counters[k] > 0 for k in fired)


def test_suspect_replica_gets_no_new_dispatch():
    r = _fleet(transport=True,
               membership=MembershipConfig(suspect_after=2,
                                           lease_ticks=30))
    # starve replica 2's heartbeats via a one-sided partition
    r.transport.partition(2)
    for _ in range(5):
        r.step_all()
    assert r.membership.state(2) == "suspect"
    with r._lock:
        assert 2 not in r._routable(role="decode")
    assert len(r.handoffs) == 0          # and NOT salvaged
    r.transport.heal(2)
    for _ in range(3):
        r.step_all()
    assert r.membership.state(2) == "live"
    with r._lock:
        assert 2 in r._routable(role="decode")


def test_healed_partition_no_salvage_no_double_decode():
    token_log = {}

    def hook(n, router):
        if n == 2:
            router.transport.partition(2)
        if n == 8:
            router.transport.heal(2)
    r = _fleet(transport=True,
               membership=MembershipConfig(suspect_after=3,
                                           lease_ticks=12))
    handles = []
    for i, p in enumerate(_prompts(4)):
        token_log[i] = 0

        def cb(tok, i=i):
            token_log[i] += 1
        handles.append(r.submit(p, max_new_tokens=4, on_token=cb, tag=i))
    _drive(r, hook=hook)
    out = [tuple(h.result(timeout=10)) for h in handles]
    assert out == _baseline()
    assert len(r.handoffs) == 0          # healed => salvage never ran
    assert token_log == {i: len(out[i]) for i in range(4)}
    counts = r.membership.telemetry()["transition_counts"]
    assert counts.get("suspect->live", 0) >= 1
    assert "suspect->dead" not in counts and "live->dead" not in counts


def test_lease_expiry_salvages_exactly_once():
    def hook(n, router):
        if n == 2:
            router.transport.partition(2)
    r = _fleet(transport=True,
               membership=MembershipConfig(suspect_after=2,
                                           lease_ticks=5))
    out = _serve(r, hook=hook)
    counts = r.membership.telemetry()["transition_counts"]
    assert counts.get("suspect->dead") == 1
    salvages = [rec for rec in r.handoffs
                if rec["reason"] == "lease_expired"]
    assert len(salvages) == 1
    # every original handle resolved terminally or completed — and the
    # fleet fully converged with nothing in flight
    assert all(out)
    assert r._pending_handoffs == [] and r._inflight == {}
    assert not r.transport.busy()


def test_two_failure_composition_lands_on_third_survivor():
    """The regression this PR pins: the prefill replica dies with a
    hand-off IN FLIGHT, and the chosen decode target dies before the
    transfer resolves. The request must land on the third survivor
    (recompute ladder) with exactly one lifecycle finish and zero
    leaked in-flight entries."""
    r = _fleet(transport=True, n_decode=2)
    tokens = []
    h = r.submit(_prompts(1)[0], max_new_tokens=4,
                 on_token=tokens.append, tag=0)
    # drive until the prepare is in flight
    n = 0
    while not r._inflight:
        assert r.step_all() or not r._inflight, "handoff never launched"
        n += 1
        assert n < 200
    ctx = next(iter(r._inflight.values()))
    target = ctx["target"]
    assert ctx["channel"] == "kv" and ctx["src"] == 0
    # both failures BEFORE the transfer can resolve
    r.fail_replica(0, reason="death")
    r.fail_replica(target, reason="death")
    _drive(r)
    third = [i for i in (1, 2) if i != target][0]
    out = tuple(h.result(timeout=10))
    assert out == tuple(_baseline(n=1)[0])
    assert len(tokens) == len(out)       # exactly one finish, no dupes
    kh = r.telemetry()["router"]["kv_handoffs"]
    assert kh["recompute"] >= 1          # the ladder, not the pages
    assert r._inflight == {} and r._pending_handoffs == []
    assert r.replicas[third].kv_handoffs_in >= 1
    for eng in r.replicas:
        assert eng._pending_exports == {}


def test_fail_replica_mid_flight_transfer_still_completes():
    """Exporter dies while its prepare is in flight: the record is
    self-contained, so the import still lands and the give-up/commit
    path closes against the dead exporter idempotently."""
    r = _fleet(transport=True)
    h = r.submit(_prompts(1)[0], max_new_tokens=4, tag=0)
    n = 0
    while not r._inflight:
        r.step_all()
        n += 1
        assert n < 200
    r.fail_replica(0, reason="death")     # exporter gone
    _drive(r)
    assert tuple(h.result(timeout=10)) == tuple(_baseline(n=1)[0])
    assert r.replicas[0]._pending_exports == {}
    assert r.telemetry()["router"]["kv_handoffs"]["pages"] == 1


def test_autoscale_retire_reasons_the_lease_ledger():
    r = _fleet(transport=True, membership=True)
    _serve(r)
    r.decommission(2, cause="autoscale_retire")
    tick, rep, frm, to, why = r.membership.transitions[-1]
    assert (rep, to, why) == (2, "dead", "autoscale_retire")


def test_add_replica_rejoins_transport_and_membership():
    r = _fleet(transport=True, membership=True)
    _serve(r)
    r.fail_replica(2, reason="death")
    assert r.membership.state(2) == "dead"
    idx = r.add_replica(_mk_engine("decode"))
    assert idx == 2                      # tombstone reuse
    assert r.membership.state(2) == "live"
    assert 2 in r.transport.endpoints()
    out = _serve(r, n=2)
    assert out == _baseline(n=2)


def test_disarmed_step_all_microbench():
    """The disarmed fabric must stay invisible: an idle disarmed
    ``step_all`` pass is a handful of ``is None`` checks — pinned
    loosely (5ms) so only a real regression trips it."""
    r = _fleet()
    assert r.transport is None and r.membership is None
    r.step_all()                         # warm any lazy paths
    t0 = time.perf_counter()
    for _ in range(50):
        r.step_all()
    per_pass = (time.perf_counter() - t0) / 50
    assert per_pass < 5e-3, f"idle disarmed pass took {per_pass:.4f}s"


# -- registries ----------------------------------------------------------------
def test_chaos_sites_registered():
    for site in ("transport.send", "transport.recv", "transport.link"):
        assert site in chaos.SITES and chaos.SITES[site] == "site"


def test_metric_catalog_registered():
    from paddle_tpu.profiler.instrument import CATALOG
    for name in ("transport_messages_total", "transport_retries_total",
                 "fleet_lease_transitions_total",
                 "serve_handoff_aborts_total"):
        assert name in CATALOG, f"{name} fell out of CATALOG"


def test_wire_families_pinned():
    from paddle_tpu.serving.wire import WIRE_SCHEMAS, key_hash, seal
    for fam in ("kv_transfer_ack", "membership_lease"):
        spec = WIRE_SCHEMAS[fam]
        assert spec["version"] == 1
        assert spec["key_hashes"][1] == key_hash(spec), \
            f"{fam} key-hash pin drifted"
    ack = tp_mod.build_ack("m1", "kv", 3, "ok", None, 2)
    assert seal(ack, "kv_transfer_ack") is ack
    hb = mem_mod.build_heartbeat(0, 1, "decode", 8, 0, 0)
    assert seal(hb, "membership_lease") is hb


def test_lock_order_ranks_the_new_planes():
    from paddle_tpu.serving.locking import (LOCK_BEARERS, LOCK_ORDER,
                                            LOCK_OWNERS)
    order = list(LOCK_ORDER)
    assert order.index("router") < order.index("transport") \
        < order.index("membership") < order.index("engine")
    assert LOCK_OWNERS["ReplicaTransport"] == "transport"
    assert LOCK_OWNERS["MembershipTable"] == "membership"
    assert LOCK_BEARERS["transport"] == "transport"
    assert LOCK_BEARERS["membership"] == "membership"


def test_serve_top_renders_transport_panel():
    """serve_top's fleet dashboard surfaces the fabric: transport
    loss/recovery counters, per-site retry/give-up breakdown, and the
    lease-state line — on any armed router telemetry snapshot."""
    import importlib
    serve_top = importlib.import_module("serve_top")
    plan = chaos.FaultPlan(seed=11)
    plan.add("transport.send", "error", "drop", prob=0.3)
    r = _fleet(transport=True, membership=True)
    chaos.install_plan(plan)
    try:
        out = _serve(r)
    finally:
        chaos.clear_plan()
    assert out == _baseline()
    frame = serve_top.render(r.telemetry())
    assert "transport tick" in frame
    assert "retransmits" in frame and "deduped" in frame
    assert "leases    live 3" in frame
    # the per-site breakdown line appears once any retry fired
    tel = r.telemetry()["router"]["transport"]
    if tel["retries_by_site"]:
        site = sorted(tel["retries_by_site"])[0].split(".")[-1]
        assert f"{site} r" in frame
