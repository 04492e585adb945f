"""The harness end to end on the CPU at tiny sizes: the result line, the
refusal to run without a TPU, and a configuration, a traffic mix and a metric
added as files only."""
import json
import os
import subprocess
import sys

import pytest

import bench_tiny as tiny
from bench import run as R
from bench.lib import spec, trace as T


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench_root"))


def test_no_tpu_no_result(tmp_path):
    """Without an accelerator the command exits non-zero and prints no
    result: it never falls back to the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(tiny.REPO, "bench", "run.py"),
         "--workload", "cgpt67-serve-decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tiny.REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr


def test_no_program_no_result(tmp_path):
    """In a directory that holds only ``BENCHMARK.json`` and the files under
    its ``paths`` there is no system to measure: non-zero, no result."""
    import shutil
    bm = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    for path in bm["paths"]:
        shutil.copytree(os.path.join(tiny.REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable] + bm["command"][1:] + [
            "--workload", bm["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_fewer_chips_than_the_cell_asks_for(root, monkeypatch):
    from paddle_tpu.utils import chip
    cell = spec.Cell("tiny-gpt-train", root)
    cell.chips = 4
    monkeypatch.setattr(chip, "require_tpu", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    with pytest.raises(SystemExit) as e:
        R.check_device(cell)
    assert e.value.code == 2


@pytest.mark.parametrize("cell_name,metrics", [
    ("tiny-gpt-train", {"train_tok_s_chip", "setup_s"}),
    ("tiny-llama-chat", {"ttft_mean_s", "itl_p95_s", "setup_s"}),
    ("tiny-gpt-closed", {"serve_tok_s", "itl_p95_s", "setup_s"}),
])
def test_result_line_of_an_untraced_run(root, cell_name, metrics):
    res = R.execute(spec.Cell(cell_name, root), tiny.args(), tiny.DEVICE)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "compared"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == metrics
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in res["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(res)


def test_result_line_of_a_traced_run_reads_the_recorded_trace(root, monkeypatch):
    """``--trace 1``: per-layer metrics, busy and window seconds and the
    breakdown. The CPU has no device plane, so the reduction is handed the
    trace recorded on a v5e; a reader with nothing to read leaves its metric
    out."""
    recorded = os.path.join(tiny.DATA, "train_step.trace.json.gz")
    monkeypatch.setattr(T, "find", lambda _dir: recorded)
    res = R.execute(spec.Cell("tiny-gpt-train", root), tiny.args(trace=1),
                    {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert list(res)[-1] == "compared" and "breakdown" in res
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert res["device"]["busy_s"] == pytest.approx(0.29587, rel=1e-3)
    got = set(res["metrics"])
    assert {"train_step_s", "step_mfu.train", "optimizer_time_share",
            "flash_attn_roofline", "device_idle_share.train"} == got
    assert len(res["breakdown"]["device_ops"]) == 10
    assert res["breakdown"]["idle_gaps"][0][0].startswith("bench.")
    for name, secs in res["breakdown"]["device_ops"]:
        assert isinstance(name, str) and secs > 0


def test_a_configuration_a_mix_and_a_metric_are_files_and_one_entry(root):
    """What a later PR does: new files and one ``workloads`` entry, no edit
    to any file that is there."""
    bm_path = os.path.join(root, "BENCHMARK.json")
    before = {p: open(os.path.join(dp, p)).read()
              for dp, _, fs in os.walk(os.path.join(root, "bench")) for p in fs}
    cfg = dict(tiny.GPT2, n_layer=3, n_embd=32, n_inner=64)
    tiny._dump(os.path.join(root, "bench", "configs", "new-gpt.json"), cfg)
    mix = dict(tiny.CLOSED, clients=2, requests=6)
    tiny._dump(os.path.join(root, "bench", "traffic", "new-closed.json"), mix)
    tiny._dump(os.path.join(root, "bench", "limits", "new-cell.json"),
               {"limits": tiny.SERVE_LIMITS})
    tiny._dump(os.path.join(root, "bench", "metrics", "requests_done.json"),
               {"reader": "measured", "field": "requests", "unit": "1",
                "layer": "scheduler / KV pool", "moves": "serve_tok_s"})
    bm = json.load(open(bm_path))
    bm["configs"].append({"name": "new-gpt", "source": "test",
                          "file": "bench/configs/new-gpt.json", "reduced": []})
    bm["workloads"].append({"name": "new-cell", "config": "new-gpt",
                            "traffic": "new-closed", "chips": 1, "why": "test"})
    for m in bm["end_to_end"]:
        if m["name"] in ("serve_tok_s", "itl_p95_s"):
            m["workloads"].append("new-cell")
    bm["per_layer"].append({"name": "requests_done", "unit": "1",
                            "better": "higher", "source": "program_counter",
                            "layer": "scheduler / KV pool",
                            "moves": "serve_tok_s", "workloads": ["new-cell"]})
    tiny._dump(bm_path, bm)
    cell = spec.Cell("new-cell", root)
    res = R.execute(cell, tiny.args(), tiny.DEVICE)
    assert res["correct"] and set(res["metrics"]) == {
        "serve_tok_s", "itl_p95_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer()] == ["requests_done"]
    read = cell.reader("requests_done")
    assert read({"cell": cell, "measured": {"requests": 7}}, "requests_done") == 7
    assert read({"cell": cell, "measured": {}}, "requests_done") is None
    after = {p: open(os.path.join(dp, p)).read()
             for dp, _, fs in os.walk(os.path.join(root, "bench")) for p in fs}
    assert all(after[p] == text for p, text in before.items())
