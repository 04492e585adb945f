"""Memory observability plane: device-memory ledger, pool attribution,
near-OOM pressure dumps, resettable device peaks, the AOT
memory_analysis rider, evidence-row round-trips, and the what-fits
capacity planner validated against measured CPU live-array bytes."""
import gc
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import device, nn
from paddle_tpu import optimizer as popt
from paddle_tpu.profiler import evidence, instrument, metrics
from paddle_tpu.profiler.memwatch import (MemoryWatcher, MemWatchConfig,
                                          resolve_watcher, tree_bytes)
from paddle_tpu.resilience import chaos

pytestmark = pytest.mark.mem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
EVIDENCE = os.path.join(REPO, "tests", "data", "evidence")
sys.path.insert(0, TOOLS)

import mem_report  # noqa: E402



@pytest.fixture(scope="module")
def repo_ledger(tmp_path_factory):
    """An evidence ledger built from the recorded artifacts under
    tests/data/evidence (BENCH_SERVE_*, MEM_WATCH_*, AOT_STATS_*) — the
    repo commits no ledger of its own."""
    from paddle_tpu.profiler import evidence
    path = str(tmp_path_factory.mktemp("ledger") / "ledger.jsonl")
    evidence.build_ledger(EVIDENCE, path)
    return path


def _toy_llama(vocab=61, hidden=32, layers=2, heads=4, kv=2, seq=64):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(vocab_size=vocab, hidden_size=hidden,
                           layers=layers, heads=heads, kv_heads=kv,
                           seq=seq)
    cfg.use_flash_attention = False
    return cfg, LlamaForCausalLM(cfg)


def _cfg_dict(cfg) -> dict:
    return {"vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_hidden_layers,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "max_position_embeddings": cfg.max_position_embeddings,
            "tie_word_embeddings": cfg.tie_word_embeddings}


# -- ledger: snapshots, pool attribution, ring, watermarks --------------------
class TestLedger:
    def test_pool_sums_hand_computed(self):
        """Registered pools attribute exactly their providers' byte
        sums; the untagged remainder lands in ``other`` (never
        negative)."""
        w = MemoryWatcher(MemWatchConfig(ring_steps=4))
        a = np.zeros((8, 8), np.float32)        # 256 B
        b = np.zeros((16,), np.float64)         # 128 B
        w.register_pool("params", lambda: [a])
        w.register_pool("kv_pages", lambda: {"k": b, "v": b})
        rec = w.snapshot(step=0)
        assert rec["pools"]["params"] == 256
        assert rec["pools"]["kv_pages"] == 256
        assert rec["pools"]["other"] >= 0
        assert rec["bytes_in_use"] >= 512
        assert rec["source"] in ("pjrt", "live_arrays")

    def test_tree_bytes_covers_array_kinds(self):
        import jax
        import jax.numpy as jnp
        sds = jax.ShapeDtypeStruct((4, 4), jnp.float32)
        assert tree_bytes(sds) == 64
        assert tree_bytes(jnp.ones((2, 3), jnp.bfloat16)) == 12
        assert tree_bytes({"a": np.zeros(5, np.int8), "b": None}) == 5

    def test_ring_bounded_and_watermarks_monotone(self):
        w = MemoryWatcher(MemWatchConfig(ring_steps=3))
        grow = []
        w.register_pool("kv_pages", lambda: grow)
        for i in range(8):
            grow.append(np.zeros(128, np.float32))
            w.snapshot(step=i)
        assert w.snapshots == 8
        assert len(w._ring) == 3                      # deque(maxlen)
        steps = [r["step"] for r in w._ring]
        assert steps == [5, 6, 7]                     # exact last-N window
        assert w.watermarks["pools"]["kv_pages"] == 8 * 512
        # watermark stays at the peak even if the pool shrinks
        grow[:] = grow[:1]
        w.snapshot(step=8)
        assert w.watermarks["pools"]["kv_pages"] == 8 * 512
        assert w._ring[-1]["pools"]["kv_pages"] == 512

    def test_reset_watermarks_clears_pool_peaks(self):
        w = MemoryWatcher(MemWatchConfig(ring_steps=4))
        payload = [np.zeros(256, np.float32)]
        w.register_pool("params", lambda: payload)
        w.snapshot(step=0)
        assert w.watermarks["pools"]["params"] == 1024
        w.reset_watermarks()
        assert w.watermarks["pools"] == {}
        assert w.watermarks["peak_bytes_in_use"] == 0
        payload[:] = [np.zeros(64, np.float32)]
        w.snapshot(step=1)
        assert w.watermarks["pools"]["params"] == 256  # fresh floor

    def test_provider_failure_attributes_zero_never_raises(self):
        w = MemoryWatcher(MemWatchConfig(ring_steps=2))

        def boom():
            raise RuntimeError("provider died")

        w.register_pool("params", boom)
        rec = w.snapshot(step=0)
        assert rec is not None and rec["pools"]["params"] == 0

    def test_metrics_emitted_when_armed(self):
        metrics.reset_registry()
        metrics.enable_metrics()
        try:
            w = MemoryWatcher(MemWatchConfig(
                ring_steps=2, limit_bytes=1 << 30,
                stats_fn=lambda: {"bytes_in_use": 0}))
            w.register_pool("params", lambda: np.zeros(64, np.float32))
            w.snapshot(step=0)
            snap = metrics.get_registry().snapshot()
            assert snap["mem_bytes_in_use"]["pool=params"] == 256.0
            assert "pool=total" in snap["mem_bytes_in_use"]
            assert "pool=params" in snap["mem_peak_bytes"]
            assert 0.0 < snap["mem_watermark_fraction"] < 1.0
        finally:
            metrics.disable_metrics()
            metrics.reset_registry()


# -- near-OOM pressure trigger ------------------------------------------------
#: deterministic-pressure stats source: bytes_in_use comes only from the
#: tagged pools (max(0, tagged)), immune to the test process's ambient
#: live arrays — the same hook tools/chaos_drill.py --mem drives
_POOLS_ONLY = {"stats_fn": (lambda: {"bytes_in_use": 0})}


class TestPressure:
    def _grow_to_trigger(self, w, pages, n):
        for i in range(n):
            pages.append(np.zeros(256, np.float32))  # 1 KiB per page
            w.snapshot(step=i)

    def test_trigger_fires_exactly_once_and_latches(self, tmp_path):
        dump_path = str(tmp_path / "memwatch.json")
        pages = []
        w = MemoryWatcher(MemWatchConfig(
            ring_steps=16, watermark=0.5, limit_bytes=32 * 1024,
            dump_path=dump_path, **_POOLS_ONLY))
        w.register_pool("kv_pages", lambda: pages)
        self._grow_to_trigger(w, pages, 30)
        assert len(w.dumps) == 1
        assert w.dumps[0]["reason"] == "near_oom"
        with open(dump_path) as f:
            dump = json.load(f)
        assert dump["kind"] == "memwatch"
        assert dump["detail"]["pool"] == "kv_pages"
        assert dump["detail"]["fraction"] >= 0.5
        # the triggering snapshot is IN the dumped ring (flush-after-
        # record discipline: the dump explains itself)
        assert dump["steps"][-1]["pools"]["kv_pages"] == \
            dump["detail"]["pools"]["kv_pages"]
        # latched: more pressure, no second dump
        self._grow_to_trigger(w, pages, 5)
        assert len(w.dumps) == 1
        # reset_triggers re-arms
        w.reset_triggers()
        self._grow_to_trigger(w, pages, 1)
        assert len(w.dumps) == 2

    def test_culprit_is_growth_not_size(self, tmp_path):
        """A big-but-static pool must not be blamed for pressure a
        growing pool caused."""
        big = [np.zeros(64 * 1024, np.uint8)]     # 64 KiB, static
        grow = []
        w = MemoryWatcher(MemWatchConfig(
            ring_steps=8, watermark=0.9, limit_bytes=100 * 1024,
            dump_path=str(tmp_path / "d.json"), **_POOLS_ONLY))
        w.register_pool("params", lambda: big)
        w.register_pool("kv_pages", lambda: grow)
        w.snapshot(step=0)                        # baseline: params big
        for i in range(40):
            grow.append(np.zeros(256, np.float32))
            w.snapshot(step=1 + i)
        assert len(w.dumps) == 1
        with open(str(tmp_path / "d.json")) as f:
            dump = json.load(f)
        assert dump["detail"]["pool"] == "kv_pages"

    def test_dump_never_raises_on_unwritable_path(self):
        w = MemoryWatcher(MemWatchConfig(
            ring_steps=2, dump_path="/nonexistent-dir/nope/d.json"))
        w.register_pool("params", lambda: np.zeros(4, np.float32))
        w.snapshot(step=0)
        assert w.dump(reason="manual") is None
        assert w.dump_failures == 1

    def test_chaos_snapshot_fault_swallowed(self):
        w = MemoryWatcher(MemWatchConfig(ring_steps=2))
        chaos.install_plan(chaos.FaultPlan(seed=7).add(
            "mem.snapshot", "error", at=(1,)))
        try:
            assert w.snapshot(step=0) is None
        finally:
            chaos.clear_plan()
        assert w.snapshot_failures == 1
        assert w.snapshot(step=1) is not None     # next snapshot fine

    def test_mem_drill_stable_per_seed(self):
        from chaos_drill import run_mem_drill
        a = run_mem_drill(seed=77, verbose=False)
        b = run_mem_drill(seed=77, verbose=False)
        assert a["ok"] and a["stable"] == b["stable"]
        assert a["stable"]["pool"] == "kv_pages"


# -- disarm discipline --------------------------------------------------------
class TestDisarm:
    def test_resolve_watcher_contract(self, monkeypatch):
        monkeypatch.delenv("PADDLE_MEMWATCH", raising=False)
        monkeypatch.delenv("PADDLE_MEMWATCH_DUMP", raising=False)
        assert resolve_watcher(None) is None
        assert resolve_watcher(False) is None
        assert isinstance(resolve_watcher(True), MemoryWatcher)
        w = MemoryWatcher()
        assert resolve_watcher(w) is w
        cfg = MemWatchConfig(ring_steps=2)
        assert resolve_watcher(cfg).config is cfg
        with pytest.raises(TypeError):
            resolve_watcher("yes")
        monkeypatch.setenv("PADDLE_MEMWATCH", "1")
        assert isinstance(resolve_watcher(None), MemoryWatcher)
        monkeypatch.delenv("PADDLE_MEMWATCH")
        monkeypatch.setenv("PADDLE_MEMWATCH_DUMP", "/tmp/d.json")
        got = resolve_watcher(None)
        assert got is not None and got.dump_path == "/tmp/d.json"

    def test_record_mem_disabled_paths_under_budget(self):
        """PR 1 budget: the disabled record_mem_* helpers stay under
        20us/call (single-boolean guard)."""
        assert not metrics.metrics_enabled()
        n = 20_000
        calls = (
            lambda: instrument.record_mem_bytes_in_use("params", 1024),
            lambda: instrument.record_mem_peak_bytes("params", 1024),
            lambda: instrument.record_mem_watermark_fraction(0.5),
            lambda: instrument.record_mem_pressure_dump("near_oom"),
            lambda: instrument.record_serve_kv_pool_bytes(1024),
        )
        for call in calls:
            t0 = time.perf_counter()
            for _ in range(n):
                call()
            per_call = (time.perf_counter() - t0) / n
            assert per_call < 20e-6, f"off-path {per_call:.2e}s/call"

    def test_catalog_covers_new_families(self):
        for name in ("mem_bytes_in_use", "mem_peak_bytes",
                     "mem_watermark_fraction", "mem_pressure_dumps_total",
                     "serve_kv_pool_bytes"):
            assert name in instrument.CATALOG


# -- device peak counters -----------------------------------------------------
class TestDevicePeaks:
    def test_live_array_bytes_tracks_allocation(self):
        import jax.numpy as jnp
        gc.collect()
        before = device.live_array_bytes()
        x = jnp.ones((256, 256), jnp.float32)     # 256 KiB
        after = device.live_array_bytes()
        assert after - before >= x.nbytes
        del x
        gc.collect()
        assert device.live_array_bytes() <= after - 256 * 1024 + 4096

    def test_reset_peak_memory_stats(self):
        import jax.numpy as jnp
        device.reset_peak_memory_stats()
        floor = device.max_memory_allocated()
        w = MemoryWatcher(MemWatchConfig(ring_steps=2))
        x = jnp.ones((128, 128), jnp.float32)     # 64 KiB
        w.snapshot(step=0)                        # polls -> notes peak
        assert device.max_memory_allocated() >= floor
        assert device.max_memory_allocated() >= x.nbytes
        # reset again: peak falls back to the current floor
        peak_before = device.max_memory_allocated()
        del x
        gc.collect()
        device.reset_peak_memory_stats()
        assert device.max_memory_allocated() <= peak_before
        assert device.cuda.reset_peak_memory_stats() is None
        device._PEAK_RESET.clear()                # restore process state

    def test_peak_grows_after_reset_without_watcher_polls(self):
        """Regression (review-caught): on a backend with no allocator
        counters, the post-reset peak must track allocations observed at
        plain max_memory_allocated() polls — not freeze at the
        reset-time value until a MemoryWatcher happens to poll."""
        import jax.numpy as jnp
        gc.collect()
        try:
            device.reset_peak_memory_stats()
            floor = device.max_memory_allocated()
            x = jnp.ones((512, 512), jnp.float32)     # 1 MiB
            grown = device.max_memory_allocated()     # poll, no watcher
            assert grown >= floor + x.nbytes
            del x
        finally:
            device._PEAK_RESET.clear()

    def test_watcher_reset_wires_device_peak(self):
        w = MemoryWatcher(MemWatchConfig(ring_steps=2))
        w.snapshot(step=0)
        w.reset_watermarks()
        try:
            assert device._PEAK_RESET  # the wire-through happened
        finally:
            device._PEAK_RESET.clear()


# -- integration seams --------------------------------------------------------
class TestSeams:
    def test_trainer_pools_hand_computed(self):
        from paddle_tpu.parallel.trainer import SpmdTrainer
        paddle.seed(3)
        net = nn.Linear(8, 4)
        opt = popt.AdamW(learning_rate=0.01, parameters=net.parameters())

        def loss_fn(m, x, y):
            d = m(x) - y
            return (d * d).mean()

        tr = SpmdTrainer(net, opt, loss_fn,
                         memwatch=MemWatchConfig(ring_steps=4))
        x = np.zeros((4, 8), np.float32)
        y = np.zeros((4, 4), np.float32)
        tr.train_step(paddle.to_tensor(x), paddle.to_tensor(y))
        tel = tr.memwatch.telemetry()
        pbytes = sum(tree_bytes(p._data)
                     for _, p in net.named_parameters())
        assert tel["last"]["pools"]["params"] == pbytes
        assert tel["last"]["pools"]["optimizer"] == 2 * pbytes  # f32 moments
        assert tel["snapshots"] == 1

    def test_trainer_disarmed_by_default(self, monkeypatch):
        monkeypatch.delenv("PADDLE_MEMWATCH", raising=False)
        monkeypatch.delenv("PADDLE_MEMWATCH_DUMP", raising=False)
        from paddle_tpu.parallel.trainer import SpmdTrainer
        net = nn.Linear(4, 2)
        opt = popt.SGD(learning_rate=0.1, parameters=net.parameters())
        tr = SpmdTrainer(net, opt, lambda m, x: m(x).mean())
        assert tr.memwatch is None

    def test_engine_pools_and_telemetry_bytes(self):
        from paddle_tpu.serving import EngineConfig, ServingEngine
        paddle.seed(5)
        _, model = _toy_llama()
        eng = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8, memwatch=True))
        rng = np.random.default_rng(5)
        reqs = [eng.submit(rng.integers(1, 61, (6,)).tolist(),
                           max_new_tokens=4) for _ in range(3)]
        eng.run_until_idle(max_steps=200)
        assert all(r.done for r in reqs)
        tel = eng.telemetry()
        kv_total = eng._kp.nbytes + eng._vp.nbytes
        assert tel["pool"]["bytes"] == kv_total
        assert tel["pool"]["page_bytes"] * tel["pool"]["size"] == kv_total
        assert tel["pool"]["used_bytes"] == \
            tel["pool"]["used"] * tel["pool"]["page_bytes"]
        assert tel["mem"]["last"]["pools"]["kv_pages"] == kv_total
        assert tel["mem"]["snapshots"] == eng.steps

    def test_engine_kv_pool_bytes_metric(self):
        from paddle_tpu.serving import EngineConfig, ServingEngine
        paddle.seed(5)
        _, model = _toy_llama()
        metrics.reset_registry()
        metrics.enable_metrics()
        try:
            eng = ServingEngine(model, EngineConfig(
                max_seqs=2, token_budget=16, block_size=8))
            eng.submit([1, 2, 3, 4], max_new_tokens=3)
            eng.run_until_idle(max_steps=50)
            snap = metrics.get_registry().snapshot()
            assert "serve_kv_pool_bytes" in snap
            assert snap["serve_kv_pool_bytes"] % eng.page_bytes == 0
        finally:
            metrics.disable_metrics()
            metrics.reset_registry()


# -- what-fits planner --------------------------------------------------------
class TestWhatFits:
    #: acceptance tolerance: predicted vs measured CPU live-array bytes
    TOL = 0.01

    def test_param_count_exact_vs_model(self):
        cfg, model = _toy_llama()
        measured = sum(int(np.prod(p.shape)) if p.shape else 1
                       for _, p in model.named_parameters())
        assert mem_report.param_counts(_cfg_dict(cfg))["total"] == measured

    def test_train_prediction_vs_measured_live_bytes(self):
        """Toy trainer: predicted params/optimizer bytes match the
        memory watcher's measured CPU live-array pool attribution
        within the pinned tolerance (acceptance criterion)."""
        from paddle_tpu.parallel.trainer import SpmdTrainer
        cfg, model = _toy_llama()
        opt = popt.AdamW(learning_rate=0.01,
                         parameters=model.parameters())

        def loss_fn(m, ids):
            return m(ids).mean()

        tr = SpmdTrainer(model, opt, loss_fn,
                         memwatch=MemWatchConfig(ring_steps=4))
        ids = np.ones((2, 16), np.int64)
        tr.train_step(paddle.to_tensor(ids))
        measured = tr.memwatch.telemetry()["last"]["pools"]
        p = mem_report.plan(_cfg_dict(cfg), mode="train",
                            dtype="float32", optimizer="adamw")
        for comp, pool in (("params", "params"),
                           ("optimizer", "optimizer")):
            pred, got = p["components"][comp], measured[pool]
            assert abs(pred - got) <= self.TOL * got, \
                f"{comp}: predicted {pred} vs measured {got}"

    def test_serve_prediction_vs_engine_pool_bytes(self):
        """Second model config (serving): the kv_cache prediction equals
        the engine's actual preallocated K+V pool bytes, and params
        match the decoder weight snapshot within tolerance."""
        from paddle_tpu.serving import EngineConfig, ServingEngine
        cfg, model = _toy_llama(vocab=128, hidden=32, layers=2,
                                heads=4, kv=2, seq=128)
        eng = ServingEngine(model, EngineConfig(
            max_seqs=4, token_budget=24, block_size=8, memwatch=True))
        p = mem_report.plan(_cfg_dict(cfg), mode="serve",
                            dtype="float32", block_size=8, max_seqs=4,
                            context=128)
        assert p["components"]["kv_cache"] == \
            eng._kp.nbytes + eng._vp.nbytes
        measured_params = sum(
            int(np.prod(p_.shape)) * 4 if p_.shape else 4
            for _, p_ in model.named_parameters())
        pred = p["components"]["params"]
        assert abs(pred - measured_params) <= self.TOL * measured_params

    def test_fits_verdict(self):
        cfg = mem_report.PRESETS["llama2-7b"]
        p = mem_report.plan(cfg, mode="train", dtype="bf16",
                            optimizer="adamw", zero_stage=2, batch=32,
                            mesh={"mp": 4, "sharding": 8}, hbm_gib=16)
        assert p["fits"] is True and p["headroom_bytes"] > 0
        tight = mem_report.plan(cfg, mode="train", dtype="bf16",
                                optimizer="adamw", zero_stage=0,
                                batch=32, hbm_gib=16)
        assert tight["fits"] is False and tight["headroom_bytes"] < 0

    def test_long_context_capacity_precheck(self):
        """ROADMAP item 5 pre-check: 128k-context KV for a 7B model does
        not fit one 16 GiB chip at bf16 but fits at int8 KV across mp=4
        — the planner answers without hardware."""
        cfg = mem_report.PRESETS["llama2-7b"]
        bf16 = mem_report.plan(cfg, mode="serve", dtype="bf16",
                               context=131072, max_seqs=1,
                               hbm_gib=16)
        int8 = mem_report.plan(cfg, mode="serve", dtype="bf16",
                               kv_dtype="int8", context=131072,
                               max_seqs=1, mesh={"mp": 4}, hbm_gib=16)
        assert bf16["fits"] is False
        assert int8["fits"] is True

    def test_self_check_green_and_detects_drift(self, tmp_path):
        assert mem_report.self_check() == []
        with open(mem_report.FIXTURE) as f:
            fixture = json.load(f)
        fixture["cases"][0]["expect"]["per_chip_bytes"] += 1
        bad = tmp_path / "fixture.json"
        bad.write_text(json.dumps(fixture))
        problems = mem_report.self_check(str(bad))
        assert problems and "per_chip_bytes" in problems[0]

    def test_self_check_subprocess(self):
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "mem_report.py"),
             "--self-check"], capture_output=True, text=True, cwd=REPO)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "match the planner exactly" in r.stdout

    def test_plan_cli_and_report_cli(self, repo_ledger):
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "mem_report.py"),
             "--plan", "--preset", "llama2-7b", "--dtype", "bf16",
             "--mesh", "mp=4,sharding=8", "--zero", "2", "--batch", "32",
             "--fits", "16"], capture_output=True, text=True, cwd=REPO)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "FITS" in r.stdout
        r2 = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "mem_report.py"),
             "--ledger", repo_ledger],
            capture_output=True, text=True, cwd=REPO)
        assert r2.returncode == 0, r2.stdout + r2.stderr
        assert "mem_report" in r2.stdout

    def test_planner_input_validation(self):
        cfg = mem_report.PRESETS["toy"]
        with pytest.raises(ValueError):
            mem_report.plan(cfg, mode="inference")
        with pytest.raises(ValueError):
            mem_report.plan(cfg, dtype="float63")
        with pytest.raises(ValueError):
            mem_report.plan(cfg, remat="most")
        with pytest.raises(ValueError):
            mem_report.plan(cfg, zero_stage=4)


# -- evidence round-trip + resolver byte-identity -----------------------------
class TestEvidence:
    def _dump(self, tmp_path, name="memwatch_0.json"):
        pages = [np.zeros(256, np.float32)]
        w = MemoryWatcher(MemWatchConfig(
            ring_steps=4, limit_bytes=1 << 20,
            stats_fn=lambda: {"bytes_in_use": 0}))
        w.register_pool("kv_pages", lambda: pages)
        w.snapshot(step=0)
        path = str(tmp_path / name)
        rec = w.dump(reason="manual", path=path)
        assert rec is not None
        return path

    def test_ingest_mem_roundtrip(self, tmp_path):
        path = self._dump(tmp_path)
        rows = evidence.ingest_mem(path)
        assert len(rows) == 1
        row = rows[0]
        assert row["source"] == "mem"
        assert row["kind"] == "mem_snapshot"
        assert row["ok"] is True                      # manual dump
        assert row["data"]["last"]["pools"]["kv_pages"] == 1024
        assert row["data"]["watermarks"]["pools"]["kv_pages"] == 1024
        # filename-dispatched through ingest_path too
        assert [r["id"] for r in evidence.ingest_path(path)] == \
            [row["id"]]
        # deterministic content-addressed id
        assert evidence.ingest_mem(path)[0]["id"] == row["id"]

    def test_pressure_dump_ingests_ok_false(self, tmp_path):
        pages = []
        w = MemoryWatcher(MemWatchConfig(
            ring_steps=16, watermark=0.9, limit_bytes=8 * 1024,
            dump_path=str(tmp_path / "MEM_WATCH_r99.json"),
            stats_fn=lambda: {"bytes_in_use": 0}))
        w.register_pool("kv_pages", lambda: pages)
        for i in range(12):
            pages.append(np.zeros(256, np.float32))
            w.snapshot(step=i)
        rows = evidence.ingest_mem(str(tmp_path / "MEM_WATCH_r99.json"))
        assert rows[0]["ok"] is False                 # pressure = failure
        assert rows[0]["round"] == "r99"
        assert rows[0]["data"]["reason"] == "near_oom"
        assert rows[0]["data"]["detail"]["pool"] == "kv_pages"

    def test_mem_rows_leave_resolver_decisions_byte_identical(
            self, tmp_path, repo_ledger):
        """Acceptance criterion: appending memory evidence rows to a
        ledger leaves perf_resolve's decisions for the pre-existing
        devices byte-identical."""
        import perf_resolve
        rows, quarantined = evidence.read_rows(repo_ledger)
        assert rows and not quarantined
        assert perf_resolve.resolve(rows)["devices"]
        before = perf_resolve.resolve(rows)
        path = self._dump(tmp_path)
        mem_rows = evidence.ingest_mem(path)
        after = perf_resolve.resolve(rows + mem_rows)
        assert json.dumps(before["devices"], sort_keys=True) == \
            json.dumps(after["devices"], sort_keys=True)
        assert after["ledger_rows"] == before["ledger_rows"] + 1

    def test_committed_mem_artifact_in_ledger(self, repo_ledger):
        """The committed MEM_WATCH artifact ingests and its rows land in
        a ledger built from the repo (the --build round-trip)."""
        paths = [p for p in evidence.scan_repo(EVIDENCE)
                 if os.path.basename(p).startswith("MEM_WATCH_")]
        assert paths, "no committed MEM_WATCH artifact"
        rows, _ = evidence.read_rows(repo_ledger)
        ids = {r["id"] for r in rows}
        for p in paths:
            got = evidence.ingest_mem(p)
            assert got and got[0]["id"] in ids

    def test_mem_report_joins_ledger(self, repo_ledger):
        rep = mem_report.report(repo_ledger)
        assert rep["mem_rows"] >= 1
        assert rep["latest"]["last"]["pools"]
        text = mem_report.render_report(rep)
        assert "latest snapshot" in text


# -- AOT memory_analysis rider ------------------------------------------------
class TestAotMem:
    def _toy_program(self, store_dir, stats_path, monkeypatch):
        import jax.numpy as jnp
        from paddle_tpu.aot import cache as aot_cache
        monkeypatch.setenv("PADDLE_AOT_STATS", stats_path)
        aot_cache.reset_stats()

        def f(x):
            return (x * 2.0 + 1.0).sum()

        prog = aot_cache.cached_jit(f, name="mem_toy", cache=store_dir)
        out = prog(jnp.arange(8, dtype=jnp.float32))
        assert float(out) == pytest.approx(64.0)
        return prog

    def test_memory_analysis_recorded_and_restored(self, tmp_path,
                                                   monkeypatch):
        from paddle_tpu.aot import cache as aot_cache
        store = str(tmp_path / "store")
        stats_path = str(tmp_path / "stats.json")
        self._toy_program(store, stats_path, monkeypatch)
        with open(stats_path) as f:
            stats = json.load(f)
        mem = stats["programs"]["mem_toy"].get("mem")
        assert mem and mem["argument_bytes"] == 32.0   # 8 x f32
        assert "temp_bytes" in mem or "output_bytes" in mem

        # a second process-instance hits the cache and restores the mem
        # block from artifact meta WITHOUT recomputing memory_analysis
        calls = {"n": 0}
        real = aot_cache._program_stats

        def counting(jitted, avals):
            calls["n"] += 1
            return real(jitted, avals)

        monkeypatch.setattr(aot_cache, "_program_stats", counting)
        aot_cache.reset_stats()
        self._toy_program(store, stats_path, monkeypatch)
        assert calls["n"] == 0, "hit recomputed program stats"
        with open(stats_path) as f:
            stats2 = json.load(f)
        prog2 = stats2["programs"]["mem_toy"]
        assert prog2["hits"] == 1 and prog2["misses"] == 0
        assert prog2.get("mem") == mem

    def test_no_stats_consumer_skips_analysis(self, tmp_path,
                                              monkeypatch):
        import jax.numpy as jnp
        from paddle_tpu.aot import cache as aot_cache
        monkeypatch.delenv("PADDLE_AOT_STATS", raising=False)
        calls = {"n": 0}
        real = aot_cache._program_stats

        def counting(jitted, avals):
            calls["n"] += 1
            return real(jitted, avals)

        monkeypatch.setattr(aot_cache, "_program_stats", counting)
        prog = aot_cache.cached_jit(lambda x: x + 1, name="mem_toy2",
                                    cache=str(tmp_path / "s2"))
        prog(jnp.zeros(4))
        assert calls["n"] == 0, "paid program stats with no consumer"

    def test_ingest_aot_stats_carries_mem(self, tmp_path, repo_ledger):
        stats = {"programs": {"train_step": {
            "hits": 0, "misses": 1, "fallbacks": 0,
            "cost": {"flops": 1e9, "bytes_accessed": 1e6},
            "mem": {"temp_bytes": 4096.0, "argument_bytes": 1024.0,
                    "output_bytes": 512.0}}},
            "device_kind": "cpu"}
        p = tmp_path / "aot_stats_1.json"
        p.write_text(json.dumps(stats))
        rows = evidence.ingest_aot_stats(str(p))
        assert rows[0]["data"]["mem"]["temp_bytes"] == 4096.0
        # artifacts WITHOUT a mem block keep their pre-mem row digest
        # (content-addressed ledger stability)
        fixture_rows = evidence.ingest_aot_stats(
            os.path.join(EVIDENCE, "AOT_STATS_cpu_fixture.json"))
        assert all("mem" not in r["data"] for r in fixture_rows)
        ids = {r["id"] for r in evidence.read_rows(repo_ledger)[0]}
        assert all(r["id"] in ids for r in fixture_rows)


# -- dashboards / supervisor --------------------------------------------------
class TestSurfaces:
    def test_serve_top_renders_memory_panel(self):
        import serve_top
        tel = {
            "steps": 3, "tokens_generated": 10, "queue_depth": 0,
            "running": 1, "requests": {"finished": 1, "submitted": 2,
                                       "preempted": 0},
            "pool": {"size": 16, "block_size": 8, "used": 4, "cached": 0,
                     "free": 12, "utilization": 0.25, "page_bytes": 2048,
                     "bytes": 32768, "used_bytes": 8192,
                     "prefix": {"hits": 0, "queries": 1}},
            "mem": {"last": {"bytes_in_use": 130000, "fraction": 0.62,
                             "source": "live_arrays",
                             "pools": {"params": 94080,
                                       "kv_pages": 32768, "other": 3152}},
                    "watermarks": {"peak_bytes_in_use": 131072},
                    "dumps": [{"reason": "near_oom"}]},
        }
        frame = serve_top.render(tel)
        assert "kv bytes" in frame
        assert "memory" in frame
        assert "kv_pages" in frame
        assert "near_oom" in frame
        # a telemetry without mem still renders (disarmed engines)
        del tel["mem"]
        assert "memory" not in serve_top.render(tel)

    def test_supervise_mem_report_with_stale_guard(self, tmp_path):
        import supervise
        pages = [np.zeros(256, np.float32)]
        w = MemoryWatcher(MemWatchConfig(ring_steps=2,
                                         limit_bytes=1 << 20))
        w.register_pool("kv_pages", lambda: pages)
        w.snapshot(step=0)
        dump_path = str(tmp_path / "memwatch_0.json")
        w.dump(reason="manual", path=dump_path)
        env = {"PADDLE_MEMWATCH_DUMP": dump_path}
        rep = supervise._mem_report(env, since=0.0)
        assert rep["reason"] == "manual"
        assert rep["last"]["pools"]["kv_pages"] == 1024
        assert rep["watermarks"]["pools"]["kv_pages"] == 1024
        # stale-mtime guard: a dump older than the attempt is skipped
        assert supervise._mem_report(env,
                                     since=time.time() + 60) is None
        assert supervise._mem_report({}, since=0.0) is None

    def test_supervisor_threads_memwatch_dump_path(self, tmp_path):
        import supervise
        sup = supervise.Supervisor(["true"], report_dir=str(tmp_path))
        env = sup._attempt_env()
        assert env["PADDLE_MEMWATCH_DUMP"].endswith("memwatch_0.json")
