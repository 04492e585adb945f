"""Where each engine step's host time went, as the program counts it
(``profiler.host_time``): ``serve.post``'s arguments, the Python collections
as spans on the trace's clock, the first tokens on ``serve.emit``,
``telemetry()["host"]`` and the flight recorder's step clock."""
import gc
import glob
import gzip
import json
import sys
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import host_time
from paddle_tpu.serving import EngineConfig, ObsConfig, ServingEngine
from paddle_tpu.serving import engine as E

HOST = ("host_wall_us", "host_sync_us", "host_cpu_us", "host_offcpu_us",
        "host_lock_us", "gc_us", "gc_collections", "compile_us", "compiles")


def _engine(token_budget=16, **kw):
    paddle.seed(3)
    cfg = LlamaConfig.tiny(vocab_size=61, hidden_size=32, layers=2, heads=4,
                           kv_heads=2, seq=64)
    cfg.use_flash_attention = False
    return ServingEngine(LlamaForCausalLM(cfg), EngineConfig(
        max_seqs=4, token_budget=token_budget, block_size=4, num_blocks=64,
        **kw))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 61, n).tolist()


def _warm(eng):
    eng.submit(_prompt(9), max_new_tokens=3)
    eng.run_until_idle()


def _spans(trace_dir):
    """(name, ts, dur, args) of the host's ``serve.*`` / ``host.*`` /
    ``train.*`` spans in the trace ``jax.profiler`` wrote."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.trace.json.gz")
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    host = {e["pid"] for e in events if e.get("ph") == "M"
            and e["name"] == "process_name"
            and e["args"]["name"].startswith("/host:")}
    return sorted(((e["name"], e["ts"], e["dur"], e.get("args", {}))
                   for e in events if e.get("ph") == "X" and e["pid"] in host
                   and e["name"].startswith(("serve.", "host.", "train."))),
                  key=lambda s: s[1])


def _slow_pack(monkeypatch, seconds, which=None):
    """Patch ``_pack_plan`` to sleep ``seconds`` (at the ``which``-th call
    only, counting from 1, where given)."""
    pack, calls = ServingEngine._pack_plan, []

    def slow(self, plan, armed):
        calls.append(1)
        if which is None or len(calls) == which:
            time.sleep(seconds)
        return pack(self, plan, armed)

    monkeypatch.setattr(ServingEngine, "_pack_plan", slow)


def test_a_step_that_sleeps_reads_off_the_cpu(monkeypatch):
    eng = _engine()
    _warm(eng)
    _slow_pack(monkeypatch, 0.02)
    eng.submit(_prompt(6), max_new_tokens=2)
    eng.step()
    (got,) = [s for s in eng.telemetry()["host"]["slowest"]
              if s["step"] == eng.steps]
    assert got["host_offcpu_us"] >= 15_000
    assert got["host_wall_us"] >= got["host_offcpu_us"] + got["host_cpu_us"]
    assert set(HOST) <= set(got)


def test_a_busy_step_reads_on_the_cpu():
    acct = host_time.Interval()
    t = time.thread_time()
    while time.thread_time() - t < 0.02:
        pass
    got = acct.read()
    assert got["host_cpu_us"] >= 20_000 and got["host_sync_us"] == 0
    assert got["host_wall_us"] >= got["host_cpu_us"] + got["host_offcpu_us"]


def test_a_collection_inside_a_step_is_counted_and_spanned(tmp_path,
                                                          monkeypatch):
    eng = _engine()
    _warm(eng)
    pack = ServingEngine._pack_plan

    def collecting(self, plan, armed):
        gc.collect()
        return pack(self, plan, armed)

    monkeypatch.setattr(ServingEngine, "_pack_plan", collecting)
    eng.submit(_prompt(6), max_new_tokens=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    (post,) = [s for s in spans if s[0] == "serve.post"]
    assert int(post[3]["gc_collections"]) >= 1 and int(post[3]["gc_us"]) > 0
    (step,) = [s for s in spans if s[0] == "serve.step"]
    (pack_span,) = [s for s in spans if s[0] == "serve.pack"]
    inside = [s for s in spans if s[0] == "serve.gc"
              and pack_span[1] <= s[1] <= pack_span[1] + pack_span[2]]
    assert inside, [s[0] for s in spans]
    assert step[1] <= inside[0][1] and \
        inside[0][1] + inside[0][2] <= step[1] + step[2] + 1e-3
    assert {"generation", "collected"} <= set(inside[0][3])


def test_a_collection_is_named_by_the_realm_it_runs_in(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
        with host_time.Realm("train"):
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    names = [s[0] for s in _spans(tmp_path)]
    assert "host.gc" in names and "train.gc" in names
    assert "serve.gc" not in names


def test_a_new_shape_counts_its_compile():
    eng = _engine(token_budget=24)             # a step program of its own
    eng.submit(_prompt(9), max_new_tokens=2)
    eng.step()
    first = eng.host_log.snapshot()["sums"]
    assert first["compiles"] >= 1 and first["compile_us"] > 0
    eng.run_until_idle()
    assert eng.host_log.snapshot()["sums"]["compiles"] == first["compiles"]


def test_first_tokens_on_emit_are_the_engines_ttfts(tmp_path, monkeypatch):
    """A request submitted to an idle engine waits on one launch for its
    first token; one submitted while a step is in flight, on two."""
    eng = _engine()
    _warm(eng)
    ttfts = []
    monkeypatch.setattr(E._instr, "record_serve_ttft", ttfts.append)
    jax.profiler.start_trace(str(tmp_path))
    try:
        a = eng.submit(_prompt(9, 1), max_new_tokens=3)
        eng.step()                             # launches a's prefill
        b = eng.submit(_prompt(5, 2), max_new_tokens=3)
        while eng.step():
            pass
    finally:
        jax.profiler.stop_trace()
    emits = [s[3] for s in _spans(tmp_path) if s[0] == "serve.emit"]
    firsts = [e for e in emits if int(e["first_tokens"])]
    assert [int(e["first_tokens"]) for e in firsts] == [1, 1]
    assert [int(e["first_token_steps"]) for e in firsts] == [1, 2]
    assert [float(e["first_token_s"]) for e in firsts] == pytest.approx(
        ttfts, rel=1e-4)
    assert ttfts == pytest.approx(
        [r.first_token_at - r.arrival for r in (a, b)], rel=1e-9)


def test_a_requests_launch_mark_is_taken_under_the_engine_lock():
    """A submit that waits for the engine lock while a step launches marks
    the launch as it stands once it holds the lock, not before."""
    eng = _engine()
    got = []
    with eng._lock:
        t = threading.Thread(
            target=lambda: got.append(eng.submit(_prompt(5), 2)))
        t.start()
        time.sleep(0.05)                  # submit now waits for the lock
        eng.steps = 7                     # a step launched meanwhile
    t.join(10)
    assert got[0].launch_mark == 8


class _Late:
    """A step's output that takes 5 ms to come back to the host: the CPU
    backend has finished a step by the time its launch returns."""

    def __init__(self, out):
        self.out = out

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.005)
        return np.asarray(self.out)


def test_host_sync_is_the_sync_spans_time(tmp_path):
    eng = _engine()
    _warm(eng)
    call = eng._step_call
    # the CPU backend dispatches inline in tests: the step program runs in
    # serve.launch on this thread, and what it spends off the CPU there
    # (descheduled, or waiting on XLA's own threads) is the launch's
    launched = [0]

    def late(w, tokens, prev, *rest):
        prev = prev.out if isinstance(prev, _Late) else prev
        t0, cpu0 = time.monotonic_ns(), time.thread_time_ns()
        out, kp, vp = call(w, tokens, prev, *rest)
        launched[0] += (time.monotonic_ns() - t0
                        - (time.thread_time_ns() - cpu0)) // 1000
        return _Late(out), kp, vp

    eng._step_call = late
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            eng.submit(_prompt(11, i), max_new_tokens=6)
        while eng.step():
            pass
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    counted = synced = offcpu = 0.0
    for step in (s for s in spans if s[0] == "serve.step"):
        inside = [s for s in spans
                  if step[1] <= s[1] <= step[1] + step[2] and s is not step]
        post = [s for s in inside if s[0] == "serve.post" and s[3]]
        if post:
            args = post[0][3]
            counted += int(args["host_sync_us"])
            synced += sum(s[2] for s in inside if s[0] == "serve.sync")
            offcpu += int(args["host_offcpu_us"])
    assert synced > 20_000
    assert counted == pytest.approx(synced, rel=0.05)
    # a wait on the device is not off the CPU
    assert offcpu - launched[0] < synced / 2


def test_telemetry_keeps_the_slowest_steps(monkeypatch):
    eng = _engine()
    _warm(eng)
    before = eng.host_log.snapshot()
    _slow_pack(monkeypatch, 0.03, which=3)
    for i in range(3):
        eng.submit(_prompt(7, i), max_new_tokens=8)
    while eng.step():
        pass
    host = eng.telemetry()["host"]
    assert host["steps"] == eng.steps
    slow = host["slowest"]
    assert 1 <= len(slow) <= 8
    assert slow[0]["step"] == before["steps"] + 3
    assert slow[0]["host_offcpu_us"] >= 20_000
    walls = [s["host_wall_us"] for s in slow]
    assert walls == sorted(walls, reverse=True)
    assert host["sums"]["host_wall_us"] >= sum(walls)


def test_the_log_forgets_what_falls_out_of_its_window():
    log = host_time.HostLog(keep=4, slowest=2)
    for step, wall in enumerate([90, 1, 2, 3, 4, 5], 1):
        log.add(step, {"host_wall_us": wall})
    snap = log.snapshot()
    assert [s["step"] for s in snap["slowest"]] == [6, 5]
    assert snap["sums"] == {"host_wall_us": 105} and snap["steps"] == 6


def test_the_log_keeps_the_slowest_of_its_window_as_readings_come():
    """What a search of the window at every snapshot would give."""
    rng = np.random.default_rng(5)
    log = host_time.HostLog(keep=50, slowest=8)
    walls = rng.integers(0, 400, 1000)        # ties and repeats too
    for step, wall in enumerate(walls, 1):
        log.add(step, {"host_wall_us": int(wall)})
        window = list(enumerate(walls, 1))[max(0, step - 50):step]
        want = sorted(w for _, w in window)[-8:][::-1]
        got = log.snapshot()["slowest"]
        assert [s["host_wall_us"] for s in got] == want
        assert all(walls[s["step"] - 1] == s["host_wall_us"]
                   and s["step"] > step - 50 for s in got)


def test_a_stall_record_says_where_the_step_went(monkeypatch):
    eng = _engine(obs=ObsConfig(flight_steps=8, stall_threshold_s=0.01))
    _warm(eng)
    _slow_pack(monkeypatch, 0.02)
    eng.submit(_prompt(6), max_new_tokens=2)
    eng.step()
    rec = list(eng.obs._steps)[-1]
    assert rec["dt_s"] == rec["host"]["host_wall_us"] / 1e6 >= 0.02
    assert rec["host"]["host_offcpu_us"] >= 15_000
    assert any(d["reason"] == "stall" for d in eng.obs.dumps)


def test_compiles_ending_on_many_threads_are_all_counted():
    before = host_time.Interval()
    threads = [threading.Thread(target=lambda: [
        host_time._on_event(host_time.COMPILE_EVENT, 1e-6)
        for _ in range(2000)]) for _ in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    got = before.read()
    assert got["compiles"] == 16 * 2000
    assert got["compile_us"] == 16 * 2000          # a microsecond each
