"""Perf-evidence plane: ledger ingestion, attribution math, the resolver's
determinism/provenance contract, and apply_perf_config's never-load-bearing
fallback ladder."""
import json
import os
import subprocess
import sys
import time

import pytest

import paddle_tpu as paddle  # noqa: F401  (full framework: flags defined)
from paddle_tpu.framework import flags
from paddle_tpu.profiler import evidence, instrument, metrics

pytestmark = pytest.mark.perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
EVIDENCE = os.path.join(REPO, "tests", "data", "evidence")
sys.path.insert(0, TOOLS)

import perf_report  # noqa: E402
import perf_resolve  # noqa: E402

# A recorded hardware session in the formats the ingestors read: a probe
# whose fused tiers failed, a later probe that died before its first tier,
# a training session, and the round after it carrying that session's value.
_PROBE_R04 = {
    "ok": True, "platform": "tpu", "device_kind": "TPU v5 lite",
    "steps": {
        "matmul": {"ok": True, "sec": 36.3, "matmul4096_us": 7175.0},
        "flash_fwd": {"ok": True, "sec": 6.5, "us": 20641.7},
        "flash_bwd": {"ok": True, "sec": 1.3, "us": 25778.7},
        "flashmask": {"ok": True, "sec": 6.7, "us": 10361.6},
        "fused": {"ok": False, "sec": 30.6,
                  "error": "NotImplementedError: Only 2D gather is "
                           "supported"},
        "fused_adamw": {"ok": False, "sec": 9.1,
                        "error": "JaxRuntimeError: INTERNAL: compile "
                                 "helper exit code 1"}}}
_PROBE_LATEST = {"ok": False,
                 "error": "probe watchdog expired (backend init hung)"}
_SESSION_R04 = {
    "metric": "llama_train_tokens_per_sec_per_chip", "value": 17114.5,
    "unit": "tokens/s", "vs_baseline": 0.5617,
    "extra": {"mfu": 0.2808, "config": "llama-0.5b-b8",
              "device": "TPU v5 lite",
              "attempts": {"llama-0.5b-b8": {"tps": 17114.5, "mfu": 0.2808},
                           "llama-1.1b-b8": {"error": "RESOURCE_EXHAUSTED"}}}}
_BENCH_R05 = {
    "n": 5, "cmd": "python bench.py", "rc": 1, "tail": "",
    "parsed": {
        "metric": "llama_train_tokens_per_sec_per_chip", "value": 17114.5,
        "unit": "tokens/s", "vs_baseline": 0.5617,
        "extra": {"error": "probe tier failed: probe watchdog expired",
                  "value_source": {"file": "BENCH_SESSION_r04.json",
                                   "mfu": 0.2808, "config": "llama-0.5b-b8",
                                   "device": "TPU v5 lite"}}}}


def _write_session(root):
    """The recorded session above plus the repo's CPU AOT-stats fixture,
    as artifact files under ``root``."""
    import shutil
    for name, doc in (("PROBE_r04.json", _PROBE_R04),
                      ("PROBE_LATEST.json", _PROBE_LATEST),
                      ("BENCH_SESSION_r04.json", _SESSION_R04),
                      ("BENCH_r05.json", _BENCH_R05)):
        (root / name).write_text(json.dumps(doc))
    shutil.copy(os.path.join(EVIDENCE, "AOT_STATS_cpu_fixture.json"), root)


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """(ledger path, config path) built by the tool itself from the
    recorded session: nothing here is committed to the repo."""
    root = tmp_path_factory.mktemp("evidence")
    _write_session(root)
    ledger, config = str(root / "ledger.jsonl"), str(root / "config.json")
    assert perf_resolve.main(["--ledger", ledger, "--out", config,
                              "--build", "--repo", str(root)]) == 0
    return ledger, config


# -- ingestion ----------------------------------------------------------------
class TestIngestion:
    def test_every_artifact_ingests(self, tmp_path):
        """Every perf artifact — the repo's committed ones and a recorded
        hardware session — yields at least one normalized row, and
        ingestion is deterministic (content-addressed ids do not depend
        on mtime or ingest order)."""
        _write_session(tmp_path)
        paths = evidence.scan_repo(EVIDENCE) + \
            evidence.scan_repo(str(tmp_path))
        names = {os.path.basename(p) for p in paths}
        for expected in ("PROBE_r04.json", "PROBE_LATEST.json",
                         "BENCH_SESSION_r04.json", "BENCH_r05.json",
                         "BENCH_SERVE_r09.json", "MEM_WATCH_r11.json",
                         "AOT_STATS_cpu_fixture.json"):
            assert expected in names
        for path in paths:
            first = evidence.ingest_path(path)
            again = evidence.ingest_path(path)
            assert first, f"{os.path.basename(path)} ingested no rows"
            assert [r["id"] for r in first] == [r["id"] for r in again]
            for row in first:
                assert row["schema"] == evidence.SCHEMA_VERSION
                assert row["source"] in evidence.SOURCES
                assert row["id"].startswith(f"{row['source']}:")

    def test_probe_ok_false_is_first_class(self, tmp_path):
        """A probe that died (ok:false) ingests as a probe_failed row —
        the resolver's signal that the last window died (instead of
        silently trusting r04 forever)."""
        _write_session(tmp_path)
        rows = evidence.ingest_probe(str(tmp_path / "PROBE_LATEST.json"))
        assert len(rows) == 1
        row = rows[0]
        assert row["kind"] == "probe_failed"
        assert row["ok"] is False
        assert row["round"] == "latest"
        assert "watchdog" in row["data"]["error"]

    def test_probe_failed_tiers_stay_rows(self, tmp_path):
        """Inside an ok probe, failed tiers (fused, fused_adamw on r04)
        remain ok:false rows — failure is evidence."""
        _write_session(tmp_path)
        rows = evidence.ingest_probe(str(tmp_path / "PROBE_r04.json"))
        by_tier = {r["data"]["tier"]: r for r in rows}
        assert by_tier["fused"]["ok"] is False
        assert by_tier["fused_adamw"]["ok"] is False
        assert by_tier["matmul"]["ok"] is True
        assert by_tier["matmul"]["device_kind"] == "TPU v5 lite"

    def test_autotune_cache_format_ingests(self, tmp_path):
        """kernels/autotune.py's REAL disk format: the key is
        json[(kernel, sq, sk, head_dim, dtype, causal)] (see
        flash_attention._tune_signature) — no device element, so the
        caller's device hint is what keys the winner per device."""
        cache = {json.dumps(["flash_fwd", 2048, 2048, 64,
                             "bfloat16", True]): [256, 128]}
        p = tmp_path / "AUTOTUNE_CACHE.json"
        p.write_text(json.dumps(cache))
        rows = evidence.ingest_autotune(str(p), device_kind="TPU v5 lite")
        assert len(rows) == 1
        assert rows[0]["kind"] == "autotune_winner"
        assert rows[0]["device_kind"] == "TPU v5 lite"
        assert rows[0]["data"]["block"] == [256, 128]
        # without a hint the row is device-less (and the resolver will
        # not key decisions from it)
        assert evidence.ingest_autotune(str(p))[0]["device_kind"] is None

    def test_build_ledger_threads_probe_device_to_autotune(self, tmp_path):
        """build_ledger gives device-less artifacts (the autotune cache)
        the device of the newest successful probe in the same root —
        the probe is what wrote the cache (regression: real tuned
        winners were dropped for want of a device key)."""
        probe = {"ok": True, "device_kind": "TPU v5 lite",
                 "platform": "tpu",
                 "steps": {"matmul": {"ok": True, "sec": 1.0}}}
        (tmp_path / "PROBE_r11.json").write_text(json.dumps(probe))
        cache = {json.dumps(["flash_fwd", 2048, 2048, 128,
                             "bfloat16", True]): [512, 256]}
        (tmp_path / "AUTOTUNE_CACHE.json").write_text(json.dumps(cache))
        led, _ = evidence.build_ledger(str(tmp_path),
                                       str(tmp_path / "l.jsonl"))
        winners = [r for r in led.rows()
                   if r["kind"] == "autotune_winner"]
        assert winners[0]["device_kind"] == "TPU v5 lite"
        cfg = perf_resolve.resolve(led.rows())
        entry = cfg["devices"]["TPU v5 lite"]
        assert entry["flags"]["use_autotune"]["value"] is True
        (key, spec), = entry["kernel_blocks"].items()
        assert json.loads(key) == ["flash_fwd", 2048, 2048, 128,
                                   "bfloat16", True]

    def test_runlog_and_flight_ingest(self, tmp_path):
        runlog = tmp_path / "runlog_rank0.jsonl"
        runlog.write_text(
            json.dumps({"kind": "meta", "rank": 0, "world": 1,
                        "flops_per_step": 1e9, "peak_flops": 1e12}) + "\n"
            + json.dumps({"kind": "step", "step": 0,
                          "step_time_ms": 10.0, "mfu": 0.1}) + "\n"
            + '{"kind": "step", "truncated...')  # torn tail tolerated
        rows = evidence.ingest_runlog(str(runlog))
        kinds = sorted(r["kind"] for r in rows)
        assert kinds == ["runlog_meta", "runlog_summary"]
        flight = tmp_path / "flight_0.json"
        flight.write_text(json.dumps(
            {"reason": "stall", "steps": [{"step": 3, "dt_s": 99.0}],
             "telemetry": {"slo": {"met": 0}}}))
        frows = evidence.ingest_flight(str(flight))
        assert frows[0]["kind"] == "step_plan"
        assert frows[0]["ok"] is False  # anomaly-triggered dump
        assert frows[0]["data"]["last_step"]["dt_s"] == 99.0

    def test_malformed_artifact_never_raises(self, tmp_path):
        bad = tmp_path / "PROBE_r99.json"
        bad.write_text("{truncated")
        assert evidence.ingest_path(str(bad)) == []
        empty = tmp_path / "BENCH_r99.json"
        empty.write_text("[]")
        assert evidence.ingest_path(str(empty)) == []


class TestLedger:
    def test_malformed_rows_quarantined_never_raising(self, tmp_path):
        good = evidence.make_row("probe", "probe_step", {"tier": "t"},
                                 file="PROBE_r01.json", rnd="r01")
        p = tmp_path / "ledger.jsonl"
        p.write_text(json.dumps(good) + "\n"
                     + "{not json at all\n"
                     + json.dumps({"schema": 99, "id": "x:1:2"}) + "\n"
                     + json.dumps(["a", "list"]) + "\n"
                     + json.dumps({"schema": 1}) + "\n"  # no id
                     + json.dumps(good)[:40] + "\n")     # truncated
        rows, quarantined = evidence.read_rows(str(p))
        assert [r["id"] for r in rows] == [good["id"]]
        assert len(quarantined) == 5
        assert all("error" in q and "line" in q for q in quarantined)

    def test_merge_is_atomic_and_deduplicating(self, tmp_path):
        led = evidence.Ledger(str(tmp_path / "l.jsonl"))
        row = evidence.make_row("bench", "train_throughput", {"value": 1},
                                file="BENCH_r01.json", rnd="r01")
        assert led.merge([row]) == 1
        assert led.merge([row]) == 0  # id-deduped
        assert len(led.rows()) == 1
        assert not [f for f in os.listdir(tmp_path)
                    if ".tmp" in f], "tmp file leaked"

    def test_missing_ledger_reads_empty(self, tmp_path):
        rows, q = evidence.read_rows(str(tmp_path / "nope.jsonl"))
        assert rows == [] and q == []


# -- attribution math ---------------------------------------------------------
class TestAttribution:
    def test_roofline_hand_computed(self):
        """Toy cost pinned by hand: flops=100, bytes=4, peak 100 flop/s,
        bw 8 B/s -> intensity 25, balance 12.5, ratio 2 (compute-bound);
        compute_s 1.0 > memory_s 0.5 -> modeled 1.0."""
        r = evidence.roofline({"flops": 100.0, "bytes_accessed": 4.0},
                              peak_flops=100.0, peak_bytes_per_s=8.0)
        assert r["compute_s"] == pytest.approx(1.0)
        assert r["memory_s"] == pytest.approx(0.5)
        assert r["intensity"] == pytest.approx(25.0)
        assert r["machine_balance"] == pytest.approx(12.5)
        assert r["ratio"] == pytest.approx(2.0)
        assert r["bound"] == "compute"
        assert r["modeled_s"] == pytest.approx(1.0)

    def test_memory_bound_program(self):
        r = evidence.roofline({"flops": 10.0, "bytes_accessed": 100.0},
                              peak_flops=100.0, peak_bytes_per_s=8.0)
        assert r["bound"] == "memory"
        assert r["modeled_s"] == pytest.approx(12.5)  # bytes/bw wins

    def test_attribute_step_hand_computed(self):
        """wall 2.0s; program: compute 1.0s vs memory 1.0s -> device 1.0;
        collective 0.5, data 0.1 -> host 0.4; fractions 0.5/0.25/0.05/0.2
        and mfu = 100e12/(2*100e12) = 0.5."""
        out = evidence.attribute_step(
            2.0, {"step": {"flops": 100e12, "bytes_accessed": 8e11}},
            peak_flops=100e12, peak_bytes_per_s=8e11,
            collective_s=0.5, data_s=0.1)
        f = out["fractions"]
        assert f["compute"] == pytest.approx(0.5)
        assert f["collective"] == pytest.approx(0.25)
        assert f["data"] == pytest.approx(0.05)
        assert f["host"] == pytest.approx(0.2)
        assert sum(f.values()) == pytest.approx(1.0)
        assert out["mfu"] == pytest.approx(0.5)
        assert out["host_s"] == pytest.approx(0.4)

    def test_overcommitted_model_still_sums_to_one(self):
        """Modeled device time exceeding wall (noisy tiny steps) must not
        produce negative host or fractions > 1."""
        out = evidence.attribute_step(
            0.5, {"p": {"flops": 100e12, "bytes_accessed": 0.0}},
            peak_flops=100e12)
        f = out["fractions"]
        assert f["host"] == 0.0
        assert f["compute"] == pytest.approx(1.0)
        assert sum(f.values()) == pytest.approx(1.0)

    def test_attribution_emits_metrics_when_armed(self):
        metrics.reset_registry()
        metrics.enable_metrics()
        try:
            evidence.attribute_step(
                2.0, {"step": {"flops": 1e12, "bytes_accessed": 1e9}},
                peak_flops=100e12, peak_bytes_per_s=8e11,
                emit_metrics=True)
            snap = metrics.get_registry().snapshot()
            assert "perf_step_fraction" in snap
            assert "perf_program_roofline_ratio" in snap
        finally:
            metrics.disable_metrics()
            metrics.reset_registry()


# -- resolver -----------------------------------------------------------------
class TestResolver:
    def test_written_config_matches_its_ledger(self, built):
        """The acceptance contract: resolving a ledger reproduces the
        config the tool wrote from it byte-for-byte."""
        ledger, config = built
        rows, quarantined = evidence.read_rows(ledger)
        assert rows and not quarantined
        with open(config) as f:
            written = f.read()
        assert perf_resolve.render(perf_resolve.resolve(
            rows, os.path.basename(ledger))) == written

    def test_resolver_deterministic_across_runs_and_order(self, built):
        rows, _ = evidence.read_rows(built[0])
        a = perf_resolve.render(perf_resolve.resolve(rows))
        b = perf_resolve.render(perf_resolve.resolve(list(reversed(rows))))
        assert a == b

    def test_check_mode_subprocess(self, built, tmp_path):
        ledger, config = built
        cmd = [sys.executable, os.path.join(TOOLS, "perf_resolve.py"),
               "--ledger", ledger, "--check", "--out"]
        r = subprocess.run(cmd + [config], capture_output=True, text=True,
                           cwd=str(tmp_path))
        assert r.returncode == 0, r.stdout + r.stderr
        drifted = tmp_path / "drifted.json"
        drifted.write_text("{}\n")
        r = subprocess.run(cmd + [str(drifted)], capture_output=True,
                           text=True, cwd=str(tmp_path))
        assert r.returncode == 1 and "out of date" in r.stderr
        assert not os.path.exists(os.path.join(REPO, "PERF_CONFIG.json"))

    def test_every_decision_carries_provenance(self, built):
        with open(built[1]) as f:
            config = json.load(f)
        ids = {r["id"] for r in evidence.read_rows(built[0])[0]}
        n_decisions = 0
        for entry in config["devices"].values():
            for section in ("flags", "policies"):
                for decision in (entry.get(section) or {}).values():
                    n_decisions += 1
                    assert decision["evidence"], "decision cites nothing"
                    assert set(decision["evidence"]) <= ids
        assert n_decisions >= 2  # use_pallas_fused + use_autotune

    def test_fused_veto_and_carried_window(self, built):
        """r04's fused/fused_adamw failures resolve use_pallas_fused to
        False, and the newer failed probe marks the window carried."""
        with open(built[1]) as f:
            entry = json.load(f)["devices"]["TPU v5 lite"]
        assert entry["flags"]["use_pallas_fused"]["value"] is False
        assert entry["flags"]["use_pallas_fused"]["stale"] is False
        assert entry["window"]["status"] == "carried"
        assert entry["window"]["evidence"]  # cites the probe_failed row

    def test_fused_flip_when_tiers_pass(self, tmp_path):
        """Synthetic newer probe round with passing fused tiers flips the
        decision to True — evidence-driven, not hardcoded."""
        probe = {"ok": True, "device_kind": "TPU v5 lite",
                 "platform": "tpu",
                 "steps": {"fused": {"ok": True, "us": 10.0},
                           "fused_adamw": {"ok": True, "us": 12.0}}}
        p = tmp_path / "PROBE_r11.json"
        p.write_text(json.dumps(probe))
        rows = evidence.ingest_probe(str(p))
        cfg = perf_resolve.resolve(rows)
        d = cfg["devices"]["TPU v5 lite"]["flags"]["use_pallas_fused"]
        assert d["value"] is True
        assert d["stale"] is False
        assert cfg["devices"]["TPU v5 lite"]["window"]["status"] == "fresh"

    def test_fused_veto_untested_stays_off(self, tmp_path):
        """A round whose ladder never reached fused_adamw (probe time
        budget) must NOT flip the flag on: the regression veto was not
        tested (regression: one passing tier read as 'both passed')."""
        probe = {"ok": True, "device_kind": "TPU v5 lite",
                 "platform": "tpu",
                 "steps": {"fused": {"ok": True, "us": 10.0}}}
        p = tmp_path / "PROBE_r11.json"
        p.write_text(json.dumps(probe))
        d = perf_resolve.resolve(evidence.ingest_probe(str(p)))[
            "devices"]["TPU v5 lite"]["flags"]["use_pallas_fused"]
        assert d["value"] is False
        assert "not run" in d["reason"]

    def test_autotune_winners_flip_use_autotune_and_blocks(self, tmp_path):
        cache = {json.dumps(["flash_fwd", 2048, 2048, 64, "bfloat16",
                             False]): [256, 128]}
        p = tmp_path / "AUTOTUNE_CACHE.json"
        p.write_text(json.dumps(cache))
        rows = evidence.ingest_autotune(str(p), device_kind="TPU v5 lite")
        cfg = perf_resolve.resolve(rows)
        entry = cfg["devices"]["TPU v5 lite"]
        assert entry["flags"]["use_autotune"]["value"] is True
        (key, spec), = entry["kernel_blocks"].items()
        assert json.loads(key) == ["flash_fwd", 2048, 2048, 64,
                                   "bfloat16", False]
        assert spec["block"] == [256, 128]
        assert spec["evidence"] == [rows[0]["id"]]

    def test_roundless_evidence_never_marked_stale(self, tmp_path):
        """AUTOTUNE_CACHE.json carries no round in its name: its winner
        rows cannot be ordered against probe rounds and must not be
        marked stale by a newer probe (regression: a fresh session's
        tuned blocks were refused at apply time)."""
        probe = {"ok": True, "device_kind": "TPU v5 lite",
                 "platform": "tpu",
                 "steps": {"fused": {"ok": True, "us": 1.0},
                           "fused_adamw": {"ok": True, "us": 1.0}}}
        (tmp_path / "PROBE_r11.json").write_text(json.dumps(probe))
        cache = {json.dumps(["flash_fwd", 2048, 2048, 64, "bfloat16",
                             True]): [512, 256]}
        (tmp_path / "AUTOTUNE_CACHE.json").write_text(json.dumps(cache))
        rows = (evidence.ingest_probe(str(tmp_path / "PROBE_r11.json"))
                + evidence.ingest_autotune(
                    str(tmp_path / "AUTOTUNE_CACHE.json"),
                    device_kind="TPU v5 lite"))
        entry = perf_resolve.resolve(rows)["devices"]["TPU v5 lite"]
        assert entry["flags"]["use_autotune"]["value"] is True
        assert entry["flags"]["use_autotune"]["stale"] is False

    def test_window_carried_is_per_device(self, tmp_path):
        """A probe_failed row naming ANOTHER device must not mark this
        device's window carried; a device-less failure (dead backend)
        counts against every device."""
        ok = {"ok": True, "device_kind": "TPU v5p", "platform": "tpu",
              "steps": {"matmul": {"ok": True, "sec": 1.0}}}
        (tmp_path / "PROBE_r05.json").write_text(json.dumps(ok))
        other = {"ok": False, "device_kind": "TPU v4",
                 "error": "v4 pod reclaimed"}
        (tmp_path / "PROBE_r06.json").write_text(json.dumps(other))
        rows = (evidence.ingest_probe(str(tmp_path / "PROBE_r05.json"))
                + evidence.ingest_probe(str(tmp_path / "PROBE_r06.json")))
        win = perf_resolve.resolve(rows)["devices"]["TPU v5p"]["window"]
        assert win["status"] == "fresh"
        anon = {"ok": False, "error": "watchdog expired"}
        (tmp_path / "PROBE_r07.json").write_text(json.dumps(anon))
        rows += evidence.ingest_probe(str(tmp_path / "PROBE_r07.json"))
        win = perf_resolve.resolve(rows)["devices"]["TPU v5p"]["window"]
        assert win["status"] == "carried"

    def test_remat_policy_from_lab_ab(self):
        results = {
            "llama-0.5b-b8": {"value": 17114.5,
                              "extra": {"mfu": 0.28,
                                        "device": "TPU v5 lite"}},
            "llama-0.5b-b8-noremat": {"value": 18500.0,
                                      "extra": {"mfu": 0.30,
                                                "device": "TPU v5 lite"}},
        }
        rows = evidence.rows_from_mfu_lab(results, "r10",
                                          "MFU_LAB_r10.json")
        cfg = perf_resolve.resolve(rows)
        remat = cfg["devices"]["TPU v5 lite"]["flags"]["remat_policy"]
        assert remat["value"] == "off"
        assert len(remat["evidence"]) == 2


# -- apply_perf_config: never load-bearing ------------------------------------
class TestApplyPerfConfig:
    @pytest.fixture(autouse=True)
    def _restore_flags(self):
        before = flags.known_flags()
        pending = dict(flags._PERF_PENDING)
        yield
        flags._FLAGS.clear()
        flags._FLAGS.update(before)
        flags._PERF_PENDING.clear()
        flags._PERF_PENDING.update(pending)

    def test_missing_config_is_noop(self):
        before = flags.known_flags()
        rep = flags.apply_perf_config("/nonexistent/PERF_CONFIG.json",
                                      device_kind="TPU v5 lite")
        assert rep["status"] == "corrupt"
        assert flags.known_flags() == before

    def test_corrupt_config_is_noop(self, tmp_path):
        p = tmp_path / "cfg.json"
        p.write_text("{torn json")
        before = flags.known_flags()
        rep = flags.apply_perf_config(str(p), device_kind="TPU v5 lite")
        assert rep["status"] == "corrupt"
        assert flags.known_flags() == before

    def test_wrong_schema_is_noop(self, tmp_path):
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps({"schema": 99, "devices": {}}))
        rep = flags.apply_perf_config(str(p), device_kind="TPU v5 lite")
        assert rep["status"] == "corrupt"

    def test_device_mismatch_refused(self, built):
        """A device kind the config has no decisions for changes
        nothing (topology-mismatch refusal)."""
        CONFIG = built[1]
        before = flags.known_flags()
        rep = flags.apply_perf_config(CONFIG, device_kind="TPU v6e")
        assert rep["status"] == "device_mismatch"
        assert flags.known_flags() == before
        # and the fixture-only cpu entry has zero flag decisions: a cpu
        # process "applies" the empty set, leaving defaults untouched
        rep_cpu = flags.apply_perf_config(CONFIG, device_kind="cpu")
        assert rep_cpu["status"] == "applied"
        assert rep_cpu["flags"] == {}
        assert flags.known_flags() == before

    def test_matching_device_applies_with_provenance(self, built):
        rep = flags.apply_perf_config(built[1], device_kind="TPU v5 lite")
        assert rep["status"] == "applied"
        assert rep["flags"]["use_autotune"] == "applied"
        assert flags.flag("use_autotune") is False

    def test_stale_decision_refused(self, tmp_path):
        cfg = {"schema": 1, "devices": {"TPU v5 lite": {"flags": {
            "use_autotune": {"value": True, "stale": True,
                             "evidence": ["probe:r01:x"]}}}}}
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        before = flags.flag("use_autotune")
        rep = flags.apply_perf_config(str(p), device_kind="TPU v5 lite")
        assert rep["flags"]["use_autotune"] == "stale"
        assert flags.flag("use_autotune") == before

    def test_env_override_outranks_resolver(self, tmp_path, monkeypatch):
        cfg = {"schema": 1, "devices": {"TPU v5 lite": {"flags": {
            "use_autotune": {"value": True, "stale": False,
                             "evidence": ["probe:r01:x"]}}}}}
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        monkeypatch.setenv("FLAGS_use_autotune", "0")
        rep = flags.apply_perf_config(str(p), device_kind="TPU v5 lite")
        assert rep["flags"]["use_autotune"] == "env_override"
        assert flags.flag("use_autotune") is False

    def test_unknown_flag_deferred_until_defined(self, tmp_path):
        """A decision for a flag defined later (kernel modules register
        on first import) parks in _PERF_PENDING and lands at
        define_flag time."""
        cfg = {"schema": 1, "devices": {"TPU v5 lite": {"flags": {
            "perf_test_flag_xyz": {"value": True, "stale": False,
                                   "evidence": ["probe:r01:x"]}}}}}
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        rep = flags.apply_perf_config(str(p), device_kind="TPU v5 lite")
        assert rep["flags"]["perf_test_flag_xyz"] == "deferred"
        val = flags.define_flag("perf_test_flag_xyz", False, "test")
        assert val is True  # the parked decision won over the default
        assert flags.flag("perf_test_flag_xyz") is True

    def test_kernel_blocks_reach_autotune_cache(self, tmp_path):
        from paddle_tpu.kernels import autotune
        key = ["flash_fwd", "TPU v5 lite", "test_sig_perf"]
        cfg = {"schema": 1, "devices": {"TPU v5 lite": {
            "flags": {},
            "kernel_blocks": {json.dumps(key): {
                "block": [256, 128], "evidence": ["autotune:x:y"]}}}}}
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        autotune.clear()
        try:
            rep = flags.apply_perf_config(str(p),
                                          device_kind="TPU v5 lite")
            assert rep["kernel_blocks"] == 1
            assert autotune.cached(key[0], key[1:]) == (256, 128)
        finally:
            autotune.clear()

    def test_type_mismatched_value_refused(self, tmp_path):
        """A config value whose type disagrees with the registered flag
        (the string \"false\" is truthy!) must not become load-bearing."""
        cfg = {"schema": 1, "devices": {"TPU v5 lite": {"flags": {
            "use_autotune": {"value": "false", "stale": False,
                             "evidence": ["probe:r01:x"]}}}}}
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        before = flags.flag("use_autotune")
        rep = flags.apply_perf_config(str(p), device_kind="TPU v5 lite")
        assert rep["flags"]["use_autotune"] == "invalid_value"
        assert flags.flag("use_autotune") == before

    def test_remat_flag_reaches_trainer(self):
        """The resolver's remat_policy decision is consumed: SpmdTrainer
        with no explicit policy reads FLAGS_remat_policy — 'off' skips
        checkpoint wrapping, default '' keeps the compiled-in 'full'."""
        import paddle_tpu.nn as nn
        from paddle_tpu import optimizer as popt
        from paddle_tpu.parallel.trainer import SpmdTrainer

        def loss_fn(model, x):
            return model(x).mean()

        def build():
            model = nn.Linear(4, 4)
            o = popt.SGD(learning_rate=0.1,
                         parameters=model.parameters())
            return model, o

        flags._FLAGS["remat_policy"] = "off"
        model, o = build()
        tr = SpmdTrainer(model, o, loss_fn, remat_layers=[model])
        assert tr.remat_policy == "off"
        assert not getattr(model, "_remat_wrapped", False)
        flags._FLAGS["remat_policy"] = ""
        model, o = build()
        tr = SpmdTrainer(model, o, loss_fn, remat_layers=[model])
        assert tr.remat_policy == "full"
        assert getattr(model, "_remat_wrapped", False)
        # explicit caller choice always outranks the flag
        flags._FLAGS["remat_policy"] = "off"
        model, o = build()
        tr = SpmdTrainer(model, o, loss_fn, remat_layers=[model],
                         remat_policy="dots")
        assert tr.remat_policy == "dots"
        assert getattr(model, "_remat_wrapped", False)
        # a bad FLAG value degrades to 'full' (never load-bearing);
        # the same bad value passed EXPLICITLY still raises (user error)
        flags._FLAGS["remat_policy"] = "ful"
        model, o = build()
        tr = SpmdTrainer(model, o, loss_fn, remat_layers=[model])
        assert tr.remat_policy == "full"
        with pytest.raises(ValueError):
            model, o = build()
            SpmdTrainer(model, o, loss_fn, remat_layers=[model],
                        remat_policy="ful")

    def test_apply_never_raises(self, tmp_path):
        """Even a config whose decisions are garbage objects degrades to
        a report, not an exception."""
        cfg = {"schema": 1, "devices": {"TPU v5 lite": {
            "flags": {"use_autotune": "not-a-dict"},
            "kernel_blocks": {"not json": {"block": None}}}}}
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        rep = flags.apply_perf_config(str(p), device_kind="TPU v5 lite")
        assert rep["flags"]["use_autotune"] == "malformed"


# -- runlog live evidence / supervise summary ---------------------------------
class TestLiveEvidence:
    def test_runlog_appends_evidence_rows(self, tmp_path, monkeypatch):
        from paddle_tpu.profiler.runlog import RunLog
        ev = tmp_path / "evidence.jsonl"
        monkeypatch.setenv("PADDLE_PERF_EVIDENCE", str(ev))
        log = RunLog(str(tmp_path / "runlog.jsonl"), rank=0, world=1,
                     flops_per_step=1e9, peak_flops=1e12)
        log.mark()
        log.log_step(step_time_ms=10.0, loss=1.0, tokens=100)
        log.log_step(step_time_ms=12.0, loss=0.9, tokens=100)
        log.close()
        rows, quarantined = evidence.read_rows(str(ev))
        assert not quarantined
        kinds = [r["kind"] for r in rows]
        assert kinds == ["runlog_meta", "train_step", "train_step"]
        assert rows[1]["data"]["step_time_ms"] == 10.0
        assert rows[1]["data"]["mfu"] == pytest.approx(1e9 / 0.01 / 1e12)

    def test_supervise_perf_summary(self, tmp_path, built):
        """supervise._perf_report joins the generation's evidence stream
        with its AOT cost stats into the crash report's perf block —
        and the stale-mtime guard drops files from older generations."""
        import supervise
        ev = tmp_path / "evidence_0.jsonl"
        led = evidence.Ledger(str(ev))
        led.append_line(evidence.make_row(
            "runlog", "runlog_meta",
            {"rank": 0, "world": 1, "flops_per_step": 2e9,
             "peak_flops": 1e12}, file="runlog.jsonl"))
        led.append_line(evidence.make_row(
            "runlog", "train_step",
            {"step": 4, "step_time_ms": 4.0, "mfu": 0.5},
            file="runlog.jsonl"))
        stats = tmp_path / "aot_stats_0.json"
        stats.write_text(json.dumps({
            "programs": {"train_step": {"hits": 1, "misses": 0,
                                        "fallbacks": 0,
                                        "cost": {"flops": 2e9,
                                                 "bytes_accessed": 1e6}}},
            "device_kind": "cpu", "platform": "cpu"}))
        env = {"PADDLE_PERF_EVIDENCE": str(ev),
               "PADDLE_AOT_STATS": str(stats),
               "PADDLE_PERF_CONFIG": built[1]}
        rep = supervise._perf_report(env, since=0.0)
        assert rep["evidence"]["rows"] == 2
        assert rep["evidence"]["by_source"] == {"runlog": 2}
        last = rep["last_step"]
        assert last["step"] == 4
        att = last["attribution"]
        assert att["fractions"]["compute"] > 0
        assert "train_step" in att["programs"]
        assert "TPU v5 lite" in rep["perf_config"]["devices"]
        # stale guard: a since after the files' mtimes drops them
        stale = supervise._perf_report(env, since=time.time() + 60)
        assert stale is None or "evidence" not in stale

    def test_perf_report_tool_renders_a_ledger(self, built):
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "perf_report.py"),
             "--ledger", built[0], "--config", built[1]],
            capture_output=True, text=True, cwd=REPO)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "mfu anchor" in r.stdout
        assert "resolver decisions in effect" in r.stdout
        assert "probe window failed" in r.stdout

    def test_perf_report_attribution_join(self, tmp_path):
        """--runlog/--aot-stats join produces the step anatomy section."""
        runlog = tmp_path / "runlog_rank0.jsonl"
        runlog.write_text(
            json.dumps({"kind": "meta", "rank": 0, "world": 1,
                        "flops_per_step": 2e9, "peak_flops": 197e12,
                        "device_kind": "TPU v5 lite"}) + "\n"
            + json.dumps({"kind": "step", "step": 0,
                          "step_time_ms": 5.0, "mfu": 0.4}) + "\n")
        stats = tmp_path / "aot_stats_0.json"
        stats.write_text(json.dumps({
            "programs": {"train_step": {
                "hits": 0, "misses": 1, "fallbacks": 0,
                "cost": {"flops": 2e9, "bytes_accessed": 1e6}}},
            "device_kind": "TPU v5 lite"}))
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "perf_report.py"),
             "--runlog", str(runlog), "--aot-stats", str(stats),
             "--json"],
            capture_output=True, text=True, cwd=REPO)
        assert r.returncode == 0, r.stdout + r.stderr
        rep = json.loads(r.stdout)
        assert rep["anatomy"] is not None
        assert rep["anatomy"]["programs"]["train_step"]["bound"] in (
            "compute", "memory")
        assert rep["current_mfu"] == 0.4


# -- lint provenance gate -----------------------------------------------------
@pytest.mark.lint
class TestLintPerfConfig:
    def test_resolved_pair_zero_findings(self, built):
        """A config/ledger pair the resolver wrote passes the provenance
        check (full lint runs in test_analysis; this pins the perf check
        in isolation, fast)."""
        sys.path.insert(0, TOOLS)
        import lint
        ledger, config = built
        assert lint._perf_config_check(config, ledger) == []

    def test_bad_citation_and_unknown_flag_fire(self, tmp_path, built):
        import lint
        LEDGER, CONFIG = built
        with open(CONFIG) as f:
            cfg = json.load(f)
        entry = cfg["devices"]["TPU v5 lite"]
        entry["flags"]["use_pallas_fused"]["evidence"] = ["probe:r0:nope"]
        entry["flags"]["definitely_not_a_flag"] = {
            "value": 1, "stale": False, "evidence": ["probe:r0:nope"]}
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        rules = sorted(f.rule for f in
                       lint._perf_config_check(str(p), LEDGER))
        assert "PRF501" in rules and "PRF502" in rules

    def test_flag_registry_scan_sees_lazy_kernel_flags(self):
        from paddle_tpu.analysis import load_flag_registry
        reg = load_flag_registry()
        for name in ("use_autotune", "use_pallas_fused",
                     "sp_overlap_linear", "check_nan_inf"):
            assert name in reg


# -- mfu_lab rider ------------------------------------------------------------
class TestMfuLabEvidence:
    def test_failed_rung_is_ok_false(self):
        rows = evidence.rows_from_mfu_lab(
            {"llama-1.1b-b8": {"error": "RESOURCE_EXHAUSTED: OOM"}},
            "r10", "MFU_LAB_r10.json")
        assert rows[0]["ok"] is False
        assert "OOM" in rows[0]["data"]["error"]


# -- disabled-path overhead (PR 1 budget) -------------------------------------
class TestOverhead:
    def test_record_perf_disabled_paths_under_budget(self):
        """The new record_perf_* helpers keep the single-boolean
        disabled guard: generous 20us/call bound absorbs CI noise."""
        assert not metrics.metrics_enabled()
        n = 20_000
        calls = (
            lambda: instrument.record_perf_evidence_rows("probe", 1),
            lambda: instrument.record_perf_resolver_decision(
                "use_autotune", "applied"),
            lambda: instrument.record_perf_step_fraction("compute", 0.5),
            lambda: instrument.record_perf_roofline("train_step", 1.2),
        )
        for call in calls:
            t0 = time.perf_counter()
            for _ in range(n):
                call()
            per_call = (time.perf_counter() - t0) / n
            assert per_call < 20e-6, f"off-path {per_call:.2e}s/call"

    def test_catalog_covers_new_families(self):
        for name in ("perf_evidence_rows_total",
                     "perf_resolver_decisions_total",
                     "perf_step_fraction",
                     "perf_program_roofline_ratio"):
            assert name in instrument.CATALOG
