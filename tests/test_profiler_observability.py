"""Observability layer: metrics registry, instrumented paths, runlog,
multi-rank trace merge, and the profiler bug fixes that ride along."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, profiler as prof
from paddle_tpu.profiler import instrument, metrics

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_merge  # noqa: E402


@pytest.fixture
def metrics_on():
    """Enable the global metrics plane on a clean registry; restore off."""
    metrics.reset_registry()
    metrics.enable_metrics()
    try:
        yield metrics.get_registry()
    finally:
        metrics.disable_metrics()
        metrics.reset_registry()


# -- metrics registry ---------------------------------------------------------
class TestMetricsRegistry:
    def test_counter_basic_and_labels(self):
        reg = metrics.MetricsRegistry()
        c = reg.counter("requests_total", "reqs", labelnames=("op",))
        c.labels(op="read").inc()
        c.labels(op="read").inc(2)
        c.labels(op="write").inc()
        snap = c.snapshot()
        assert snap[("read",)] == 3.0
        assert snap[("write",)] == 1.0
        with pytest.raises(ValueError):
            c.labels(wrong="x")
        with pytest.raises(ValueError):
            c.labels(op="read").inc(-1)

    def test_gauge_set_inc_dec(self):
        reg = metrics.MetricsRegistry()
        g = reg.gauge("inflight")
        g.set(5)
        g.inc()
        g.dec(2)
        assert g.value == 4.0

    def test_histogram_buckets_cumulative(self):
        reg = metrics.MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 4
        assert snap["sum"] == pytest.approx(55.55)
        # cumulative: <=0.1 -> 1, <=1.0 -> 2, <=10.0 -> 3 (+Inf implicit 4)
        assert snap["buckets"] == {0.1: 1, 1.0: 2, 10.0: 3}

    def test_histogram_time_context(self):
        reg = metrics.MetricsRegistry()
        h = reg.histogram("t", buckets=(10.0,))
        with h.time():
            pass
        assert h.count == 1 and 0 <= h.sum < 10.0

    def test_get_or_create_idempotent_and_kind_conflict(self):
        reg = metrics.MetricsRegistry()
        a = reg.counter("x")
        assert reg.counter("x") is a
        with pytest.raises(ValueError):
            reg.gauge("x")

    def test_labeled_family_rejects_direct_record(self):
        """Recording on a labeled family (instead of .labels(...)) would
        accumulate into a value no exporter emits — it must raise, and
        re-registration with different labelnames must raise too."""
        reg = metrics.MetricsRegistry()
        c = reg.counter("y", labelnames=("op",))
        with pytest.raises(ValueError):
            c.inc()
        with pytest.raises(ValueError):
            reg.counter("y")  # labelnames omitted on re-registration
        g = reg.gauge("z", labelnames=("op",))
        with pytest.raises(ValueError):
            g.set(1)
        h = reg.histogram("w", labelnames=("op",))
        with pytest.raises(ValueError):
            h.observe(1.0)
        # children still record fine
        c.labels(op="a").inc()
        assert c.labels(op="a").value == 1.0

    def test_concurrent_increments_exact(self):
        reg = metrics.MetricsRegistry()
        c = reg.counter("n", labelnames=("op",))
        h = reg.histogram("v", buckets=(0.5, 1.5))
        n_threads, per_thread = 8, 500

        def work():
            for _ in range(per_thread):
                c.labels(op="w").inc()
                h.observe(1.0)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.labels(op="w").value == n_threads * per_thread
        assert h.count == n_threads * per_thread
        assert h.snapshot()["buckets"][1.5] == n_threads * per_thread

    def test_prometheus_text_format(self):
        reg = metrics.MetricsRegistry()
        reg.counter("hits_total", "hit count",
                    labelnames=("op",)).labels(op="get").inc(3)
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = reg.to_prometheus_text()
        assert "# HELP hits_total hit count" in text
        assert "# TYPE hits_total counter" in text
        assert 'hits_total{op="get"} 3.0' in text
        assert "# TYPE lat_seconds histogram" in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="1.0"} 2' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_count 2" in text
        assert text.endswith("\n")

    def test_json_snapshot_roundtrip(self):
        reg = metrics.MetricsRegistry()
        reg.counter("a", labelnames=("k",)).labels(k="v").inc()
        reg.gauge("b").set(2.5)
        decoded = json.loads(reg.to_json())
        assert decoded["a"] == {"k=v": 1.0}
        assert decoded["b"] == 2.5


# -- scheduler edge cases -----------------------------------------------------
class TestScheduler:
    def test_skip_first_plus_repeat(self):
        sched = prof.make_scheduler(closed=1, ready=1, record=2, repeat=2,
                                    skip_first=3)
        S = prof.ProfilerState
        states = [sched(i) for i in range(12)]
        # steps 0-2 skipped; then two cycles of [CLOSED, READY, RECORD,
        # RECORD_AND_RETURN]; beyond repeat*period: CLOSED forever
        assert states[:3] == [S.CLOSED] * 3
        assert states[3:7] == [S.CLOSED, S.READY, S.RECORD,
                               S.RECORD_AND_RETURN]
        assert states[7:11] == [S.CLOSED, S.READY, S.RECORD,
                                S.RECORD_AND_RETURN]
        assert states[11] == S.CLOSED

    def test_tuple_shorthand_records_window(self):
        exported = []
        p = prof.Profiler(scheduler=(1, 3),
                          on_trace_ready=lambda pr: exported.append(
                              len(pr._events)))
        p.start()
        for _ in range(5):
            with prof.RecordEvent("tick"):
                pass
            p.step()
        p.stop()
        # records exactly steps [1, 3) then closes (repeat=1)
        assert len(exported) == 1


# -- profiler core fixes ------------------------------------------------------
class TestProfilerCore:
    def test_worker_thread_spans_collected(self):
        """Spans begun/ended on worker threads must land in the profile
        (the old thread-local buffer silently dropped them)."""
        p = prof.Profiler()
        p.start()

        def worker():
            with prof.RecordEvent("worker_span"):
                time.sleep(0.001)

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        with prof.RecordEvent("main_span"):
            pass
        p.stop()
        names = [e["name"] for e in p._events]
        assert "worker_span" in names and "main_span" in names

    def test_summary_honors_sorted_by_and_returns_table(self, capsys):
        p = prof.Profiler()
        p._events = [
            {"name": "many_small", "cat": "Operator", "ph": "X", "ts": 0,
             "dur": 10.0, "pid": 1, "tid": 1} for _ in range(10)
        ] + [
            {"name": "one_big", "cat": "Operator", "ph": "X", "ts": 0,
             "dur": 60.0, "pid": 1, "tid": 1}
        ]
        by_total = p.summary(sorted_by=prof.SortedKeys.CPUTotal)
        by_max = p.summary(sorted_by=prof.SortedKeys.CPUMax)
        capsys.readouterr()
        assert isinstance(by_total, str) and isinstance(by_max, str)
        # total: many_small (100us) before one_big (60us); max: reversed
        lines_total = by_total.splitlines()
        lines_max = by_max.splitlines()
        assert lines_total[1].startswith("many_small")
        assert lines_max[1].startswith("one_big")

    def test_step_info_honors_unit(self):
        p = prof.Profiler()
        p._step_times = [2.0, 4.0]  # ms
        assert "avg: 3.000 ms" in p.step_info()
        assert "avg: 0.003 s" in p.step_info(unit="s")
        assert "avg: 3000.000 us" in p.step_info(unit="us")

    def test_chrome_export_metadata(self, tmp_path):
        p = prof.Profiler(on_trace_ready=prof.export_chrome_tracing(
            str(tmp_path), worker_name="w"))
        with p:
            with prof.RecordEvent("span"):
                pass
            p.step()
        trace = json.load(open(p.last_export_path))
        assert trace["displayTimeUnit"] == "ms"
        evs = trace["traceEvents"]
        meta = [e for e in evs if e.get("ph") == "M"]
        assert any(e["name"] == "process_name" for e in meta)
        assert any(e["name"] == "thread_name" for e in meta)
        anchors = [e for e in evs
                   if e.get("name") == trace_merge.CLOCK_ANCHOR_EVENT]
        assert anchors and "unix_time_us" in anchors[0]["args"]


# -- protobuf export ----------------------------------------------------------
def _pb_read_varint(blob, i):
    shift = v = 0
    while True:
        b = blob[i]
        i += 1
        v |= (b & 0x7F) << shift
        if not b & 0x80:
            return v, i
        shift += 7


def _pb_decode_events(blob):
    out, i = [], 0
    while i < len(blob):
        tag, i = _pb_read_varint(blob, i)
        assert tag == (1 << 3) | 2
        ln, i = _pb_read_varint(blob, i)
        ev, j, end = {}, i, i + ln
        while j < end:
            tag, j = _pb_read_varint(blob, j)
            num, wire = tag >> 3, tag & 7
            if wire == 2:
                sl, j = _pb_read_varint(blob, j)
                val = blob[j:j + sl].decode()
                j += sl
            else:
                val, j = _pb_read_varint(blob, j)
            ev[num] = val
        out.append(ev)
        i = end
    return out


class TestProtobufExport:
    def test_roundtrip_decode(self, tmp_path):
        p = prof.Profiler(on_trace_ready=prof.export_protobuf(
            str(tmp_path), worker_name="w"))
        p._events = [{"name": "opA", "cat": "Operator", "ph": "X",
                      "ts": 100, "dur": 25, "pid": 3, "tid": 7},
                     {"name": "opB", "cat": "Communication", "ph": "X",
                      "ts": 200, "dur": 50, "pid": 3, "tid": 8}]
        p.on_trace_ready(p)
        with open(p.last_export_path, "rb") as f:
            events = _pb_decode_events(f.read())
        assert [(e[1], e[2], e[3], e[4], e[5], e[6]) for e in events] == [
            ("opA", 100, 125, "Operator", 3, 7),
            ("opB", 200, 250, "Communication", 3, 8)]


# -- trace merge --------------------------------------------------------------
class TestTraceMerge:
    def _rank_file(self, path, anchor_ts, anchor_unix_us, events, pid):
        payload = {"traceEvents": [
            {"name": trace_merge.CLOCK_ANCHOR_EVENT, "ph": "i", "s": "g",
             "pid": pid, "tid": 0, "ts": anchor_ts,
             "args": {"unix_time_us": anchor_unix_us}},
            {"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": f"rank pid {pid}"}},
        ] + events, "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(payload, f)
        return path

    def test_merge_aligns_on_wall_clock_and_dedups_pids(self, tmp_path):
        r0 = self._rank_file(
            str(tmp_path / "r0.json"), 1000.0, 5_000_000.0,
            [{"name": "step", "ph": "X", "ts": 1500.0, "dur": 10.0,
              "pid": 7, "tid": 1}], pid=7)
        r1 = self._rank_file(
            str(tmp_path / "r1.json"), 100.0, 5_001_000.0,
            [{"name": "step", "ph": "X", "ts": 200.0, "dur": 10.0,
              "pid": 7, "tid": 1}], pid=7)
        merged = trace_merge.merge_traces([r0, r1])
        assert merged["displayTimeUnit"] == "ms"
        steps = sorted((e for e in merged["traceEvents"]
                        if e["name"] == "step"), key=lambda e: e["ts"])
        # rank0's step is at unix 5_000_500, rank1's at 5_001_100:
        # 600us apart on the merged timeline, earliest event at t=0 base
        assert steps[1]["ts"] - steps[0]["ts"] == pytest.approx(600.0)
        # second file's colliding pid got re-qualified
        assert steps[0]["pid"] == 7
        assert steps[1]["pid"] == "7.1"

    def test_merge_without_anchor_warns_but_merges(self, tmp_path, capsys):
        p0 = str(tmp_path / "n0.json")
        with open(p0, "w") as f:
            json.dump({"traceEvents": [{"name": "e", "ph": "X", "ts": 5.0,
                                        "dur": 1.0, "pid": 1, "tid": 1}]}, f)
        merged = trace_merge.merge_traces([p0])
        assert [e["name"] for e in merged["traceEvents"]] == ["e"]

    def test_cli_writes_output(self, tmp_path):
        r0 = self._rank_file(str(tmp_path / "a.json"), 0.0, 1_000_000.0,
                             [{"name": "x", "ph": "X", "ts": 1.0, "dur": 1.0,
                               "pid": 1, "tid": 1}], pid=1)
        out = str(tmp_path / "merged.json")
        assert trace_merge.main([r0, "-o", out]) == 0
        assert json.load(open(out))["metadata"]["merged_from"] == [r0]


# -- runlog -------------------------------------------------------------------
class TestRunLog:
    def test_jsonl_schema(self, tmp_path):
        path = str(tmp_path / "rl.jsonl")
        with prof.RunLog(path, rank=0, world=1, flops_per_step=1e9,
                         peak_flops=1e12, meta={"run": "t"}) as rl:
            rl.log_step(step=0, step_time_ms=10.0, loss=1.5, tokens=1000)
            rl.log_step(loss=1.2)  # derives step index + wall time
        recs = prof.read_runlog(path)
        assert recs[0]["kind"] == "meta"
        assert recs[0]["rank"] == 0 and recs[0]["run"] == "t"
        s0 = recs[1]
        assert s0["kind"] == "step" and s0["step"] == 0
        assert s0["step_time_ms"] == 10.0 and s0["loss"] == 1.5
        assert s0["tokens_per_s"] == pytest.approx(100_000.0)
        # mfu = 1e9 flops / 0.01 s / 1e12 peak = 0.1
        assert s0["mfu"] == pytest.approx(0.1)
        s1 = recs[2]
        assert s1["step"] == 1 and s1["step_time_ms"] > 0
        for key in ("step", "step_time_ms", "loss", "tokens", "tokens_per_s",
                    "mfu", "unix_time"):
            assert key in s0 and key in s1

    def test_mfu_null_without_peak(self, tmp_path):
        path = str(tmp_path / "rl.jsonl")
        with prof.RunLog(path, rank=0, world=1, flops_per_step=1e9) as rl:
            rec = rl.log_step(step=0, step_time_ms=5.0)
        assert rec["mfu"] is None

    def test_directory_path_gets_rank_name(self, tmp_path):
        rl = prof.RunLog(str(tmp_path), rank=3, world=4)
        rl.close()
        assert os.path.basename(rl.path) == "runlog_rank3.jsonl"

    def test_fit_closes_path_runlog_on_exception(self, tmp_path):
        """A runlog opened from a path must be closed even when training
        raises mid-epoch."""
        import paddle_tpu.optimizer as opt
        from paddle_tpu.hapi.model import Model
        net = nn.Linear(4, 2)
        m = Model(net)
        m.prepare(optimizer=opt.SGD(learning_rate=0.01,
                                    parameters=net.parameters()),
                  loss=nn.MSELoss())

        class _Boom:
            def __len__(self):
                return 2

            def __getitem__(self, i):
                if i == 0:
                    return (np.ones((2, 4), np.float32),
                            np.ones((2, 2), np.float32))
                raise RuntimeError("loader died")

        rlpath = str(tmp_path / "rl.jsonl")
        with pytest.raises(RuntimeError, match="loader died"):
            m.fit(_Boom(), epochs=1, verbose=0, shuffle=False,
                  runlog=rlpath)
        recs = prof.read_runlog(rlpath)  # file flushed + closed
        assert [r["kind"] for r in recs] == ["meta", "step"]

    def test_model_flops_per_step(self):
        net = nn.Linear(4, 2)
        fps = prof.model_flops_per_step(net, [2, 4])
        # forward: 2*B*4*2 matmul + B*2 bias add = 32+4 = 36; x3 for bwd
        assert fps == 3 * (2 * 2 * 4 * 2 + 2 * 2)


# -- instrumented paths -------------------------------------------------------
class TestInstrumentedPaths:
    def test_op_dispatch_counter(self, metrics_on):
        x = paddle.to_tensor([1.0, 2.0])
        (x + x) * x
        snap = metrics_on.snapshot()
        assert snap["ops_dispatch_total"].get("op=add") >= 1
        assert snap["ops_dispatch_total"].get("op=multiply") >= 1

    def test_collective_metrics_and_span(self, metrics_on):
        import paddle_tpu.distributed as dist
        t = paddle.to_tensor(np.ones(8, np.float32))
        p = prof.Profiler()
        with p:
            dist.all_reduce(t)
            p.step()
        snap = metrics_on.snapshot()
        assert snap["collective_calls_total"][
            "op=all_reduce,tier=identity"] == 1.0
        assert snap["collective_bytes_total"][
            "op=all_reduce,tier=identity"] == 32.0
        assert any(e["name"] == "Communication::all_reduce"
                   and e["cat"] == "Communication" for e in p._events)

    def test_jit_compile_cache_metrics(self, metrics_on):
        from paddle_tpu import jit

        @jit.to_static
        def f(x):
            return x * 2.0 + 1.0

        f(paddle.to_tensor([1.0]))  # fresh trace: miss
        f(paddle.to_tensor([2.0]))  # same signature: hit
        snap = metrics_on.snapshot()
        assert snap["jit_compile_total"]["fn=f"] == 1.0
        assert snap["jit_cache_hits_total"]["fn=f"] == 1.0
        assert snap["jit_compile_seconds"]["count"] == 1

    def test_checkpoint_duration_metrics(self, metrics_on, tmp_path):
        from paddle_tpu.distributed import checkpoint as ckpt
        sd = {"w": paddle.to_tensor(np.ones((4, 4), np.float32))}
        ckpt.save_state_dict(sd, str(tmp_path))
        target = {"w": paddle.to_tensor(np.zeros((4, 4), np.float32))}
        ckpt.load_state_dict(target, str(tmp_path))
        snap = metrics_on.snapshot()
        assert snap["checkpoint_save_seconds"]["count"] == 1
        assert snap["checkpoint_load_seconds"]["count"] == 1
        assert np.allclose(np.asarray(target["w"]._data), 1.0)

    def test_watchdog_tick_and_fire_metrics(self, metrics_on):
        from paddle_tpu.distributed.watchdog import StepWatchdog
        fired = threading.Event()
        wd = StepWatchdog(timeout=0.05, poll_interval=0.01,
                          on_hang=fired.set)
        wd.start()
        wd.tick()
        assert fired.wait(5.0)
        wd.stop()
        snap = metrics_on.snapshot()
        assert snap["watchdog_ticks_total"] >= 1.0
        assert snap["watchdog_fires_total"] >= 1.0

    def test_host_collective_round_metrics(self, metrics_on):
        from paddle_tpu.distributed.host_collectives import HostCollectives

        class _FakeStore:
            def __init__(self):
                self.kv = {}

            def set(self, k, v):
                self.kv[k] = v

            def get(self, k, timeout=None):
                return self.kv[k]

            def add(self, k, n):
                self.kv[k] = self.kv.get(k, 0) + n
                return self.kv[k]

            def delete_key(self, k):
                self.kv.pop(k, None)

        hc = HostCollectives(_FakeStore(), rank=0, world=1)
        out = hc.all_reduce(np.ones(4, np.float32))
        assert np.allclose(out, 1.0)
        snap = metrics_on.snapshot()
        assert snap["host_collective_rounds_total"]["op=ag"] == 1.0
        assert snap["host_collective_bytes_total"]["op=ag"] > 0


# -- end-to-end smoke + overhead ----------------------------------------------
def _toy_fit(steps=3, runlog_path=None):
    """3-step toy Model.fit; returns (model, history-of-side-effects)."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.hapi.model import Model
    net = nn.Linear(4, 2)
    m = Model(net)
    m.prepare(
        optimizer=opt.SGD(learning_rate=0.01, parameters=net.parameters()),
        loss=nn.MSELoss())
    rng = np.random.default_rng(0)
    xs = rng.random((2 * steps, 4), np.float32)
    ys = rng.random((2 * steps, 2), np.float32)
    data = [(xs[i:i + 2], ys[i:i + 2]) for i in range(0, 2 * steps, 2)]
    rl = None
    if runlog_path:
        rl = prof.RunLog(runlog_path, rank=0, world=1,
                         flops_per_step=prof.model_flops_per_step(net, [2, 4]),
                         peak_flops=1e12)
    m.fit(data, epochs=1, verbose=0, runlog=rl)
    if rl is not None:
        rl.close()
    return m


class TestSmoke:
    def test_three_step_fit_trace_metrics_runlog(self, metrics_on, tmp_path):
        """Acceptance: 3 profiled steps produce a merged-ready chrome trace
        (Forward/Backward/Optimization + Communication spans), a metrics
        snapshot with nonzero op-dispatch and collective counters, and a
        JSONL runlog with step-time and MFU fields."""
        import paddle_tpu.distributed as dist
        rlpath = str(tmp_path / "rl.jsonl")
        p = prof.Profiler(on_trace_ready=prof.export_chrome_tracing(
            str(tmp_path), worker_name="rank0"))
        with p:
            _toy_fit(steps=3, runlog_path=rlpath)
            dist.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
            p.step()

        # chrome trace: phase + communication spans, merge-ready metadata
        trace = json.load(open(p.last_export_path))
        names = set(e["name"] for e in trace["traceEvents"])
        for span in ("Forward", "Backward", "Optimization", "ProfileStep",
                     "Dataloader", "Communication::all_reduce"):
            assert span in names, f"missing span {span}"
        assert any(e["name"] == trace_merge.CLOCK_ANCHOR_EVENT
                   for e in trace["traceEvents"])
        merged = trace_merge.merge_traces([p.last_export_path])
        assert any(e["name"] == "Forward" for e in merged["traceEvents"])

        # metrics: nonzero op-dispatch + collective + step counters
        snap = metrics_on.snapshot()
        assert sum(snap["ops_dispatch_total"].values()) > 0
        assert sum(snap["collective_calls_total"].values()) >= 1
        assert snap["train_steps_total"] == 3.0
        assert snap["dataloader_batches_total"] == 3.0

        # runlog: 1 meta + 3 steps with step-time and MFU populated
        recs = prof.read_runlog(rlpath)
        steps = [r for r in recs if r["kind"] == "step"]
        assert len(steps) == 3
        for r in steps:
            assert r["step_time_ms"] > 0
            assert r["mfu"] is not None and r["mfu"] > 0
            assert r["loss"] is not None

    def test_disabled_paths_single_bool_overhead(self):
        """Micro-benchmark the disabled guards: the per-call cost of the
        instrumented no-op paths must be in the nanosecond range (generous
        20us/call bound absorbs CI noise) — i.e. a boolean check, not
        registry work."""
        assert not metrics.metrics_enabled()
        assert not prof.host_tracing_enabled()
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            instrument.record_op_dispatch("noop")
        per_metric = (time.perf_counter() - t0) / n
        t0 = time.perf_counter()
        for _ in range(n):
            with prof.RecordEvent("noop"):
                pass
        per_span = (time.perf_counter() - t0) / n
        assert per_metric < 20e-6, f"metrics off-path {per_metric:.2e}s/call"
        assert per_span < 20e-6, f"span off-path {per_span:.2e}s/call"

    @pytest.mark.slow
    def test_device_trace_lifecycle(self):
        """Device-side tracing (jax.profiler) rides the TPU/GPU targets;
        slow-marked: the default tier-1 run stays CPU/host-only."""
        p = prof.Profiler(targets=[prof.ProfilerTarget.CPU,
                                   prof.ProfilerTarget.TPU])
        with p:
            x = paddle.to_tensor([1.0])
            with prof.RecordEvent("host_span"):
                x + x
            p.step()
        assert any(e["name"] == "host_span" for e in p._events)

    def test_engine_fit_runlog_and_spans(self, metrics_on, tmp_path):
        from paddle_tpu.distributed.engine import Engine
        import paddle_tpu.optimizer as opt
        net = nn.Linear(4, 2)
        loss = nn.MSELoss()
        eng = Engine(net, loss=loss,
                     optimizer=opt.SGD(learning_rate=0.01,
                                       parameters=net.parameters()))
        rng = np.random.default_rng(1)
        data = [(rng.random((2, 4), np.float32),
                 rng.random((2, 2), np.float32)) for _ in range(2)]
        rlpath = str(tmp_path / "engine_rl.jsonl")
        p = prof.Profiler()
        with p:
            hist = eng.fit(data, epochs=1, runlog=rlpath)
            p.step()
        assert len(hist) == 2
        names = set(e["name"] for e in p._events)
        assert "ProfileStep" in names and "Dataloader" in names
        steps = [r for r in prof.read_runlog(rlpath) if r["kind"] == "step"]
        assert len(steps) == 2 and all(r["step_time_ms"] > 0 for r in steps)
