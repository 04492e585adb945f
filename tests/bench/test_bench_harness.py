"""The harness end to end on the CPU at tiny sizes: the result line, the
refusal to run without a TPU, and a configuration, a traffic mix and a metric
added as files only: to a made-up benchmark, and to a copy of the
repository's own, which then still keeps to every rule of its contract."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_contract as contract
import bench_tiny as tiny
import longcat_tiny
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
    ("tiny-llama-chat", {"itl_p95_s", "setup_s"}),
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
    assert {"busy_s", "window_s", "trace_cut", "steps_kept"} <= set(res["device"])
    # the recording holds two steps where the run's slice made more: that is
    # what a cut export looks like, so the line says so and keeps to the whole
    # step it has, the first (``test_bench_yardstick`` reads it uncut)
    assert res["device"]["trace_cut"] is True and res["device"]["steps_kept"] == 1
    assert res["device"]["busy_s"] == pytest.approx(0.29587 / 2, rel=1e-2)
    assert res["device"]["busy_s"] < res["device"]["window_s"] < 0.16
    got = set(res["metrics"])
    assert {"train_step_s", "step_mfu.train", "optimizer_time_share",
            "flash_attn_roofline", "device_idle_share.train"} == got
    assert len(res["breakdown"]["device_ops"]) == 10
    assert res["breakdown"]["idle_gaps"][0][0].startswith("bench.")
    for name, secs in res["breakdown"]["device_ops"]:
        assert isinstance(name, str) and secs > 0


def test_the_slice_keeps_every_span_with_its_arguments_and_no_python_frame(
        root, monkeypatch):
    """The profiler runs with its Python tracer off. What the readers take
    from the host are ``TraceAnnotation``s of the host tracer: the run's own
    export (the CPU's: no device plane, so the reduction is handed a
    recording) still holds the program's ``serve.*`` spans with their counts
    and the benchmark's ``bench.*`` spans, and not one Python frame."""
    import gzip
    own = T.find
    recorded = os.path.join(tiny.DATA, "chat_two_steps.trace.json.gz")
    monkeypatch.setattr(T, "find", lambda d: recorded)
    cell = spec.Cell("tiny-gpt-closed", root)
    res = R.execute(cell, tiny.args(trace=1),
                    {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert res["correct"] is True
    with gzip.open(own(os.path.join(root, ".bench_trace", cell.name))) as f:
        events = [e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in events}
    assert {"serve.step", "serve.run", "serve.schedule", "serve.pack",
            "serve.launch", "serve.sync", "serve.emit", "serve.post",
            "bench.engine_step"} <= names
    assert not [n for n in names if n.startswith("$")]       # Python frames
    runs = [e for e in events if e["name"] == "serve.run"]
    steps = [e for e in events if e["name"] == "bench.engine_step"]
    assert len(runs) == len(steps) > 3
    for e in runs:
        assert {"prefill_tokens", "decode_tokens", "pages_walked",
                "attn_tiles", "attn_tiles_ahead"} <= set(e["args"])
    assert sum(int(e["args"]["decode_tokens"]) for e in runs) > 0


def test_a_configuration_a_mix_and_a_metric_are_files_and_one_entry(root):
    """What a later PR does: new files and one ``workloads`` entry, no edit
    to any file that is there."""
    bm_path = os.path.join(root, "BENCHMARK.json")
    before = {p: open(os.path.join(dp, p)).read()
              for dp, _, fs in os.walk(os.path.join(root, "bench")) for p in fs}
    cfg = dict(tiny.GPT2, n_layer=3, n_embd=32, n_inner=64)
    tiny._dump(os.path.join(root, "bench", "configs", "new-gpt.json"), cfg)
    mix = dict(tiny.CLOSED, clients=6, requests=12)
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


# -- the same on a copy of the repository's own benchmark -------------------------
NEW_CELL, NEW_CONFIG = "new-moe-batch", "new-moe-ep4-l2"
# the tiny LongCat share with its cuts as a later PR would state them: depth,
# 8 experts held of 32 (the floor), a quarter of the vocabulary
NEW_MOE = dict(
    longcat_tiny.LONGCAT, n_routed_experts=8, first_expert=8, vocab_size=320,
    published={"num_layers": 28, "n_routed_experts": 32, "vocab_size": 1280},
    reduced={"num_layers": "test", "n_routed_experts": "test",
             "vocab_size": "rows 0..319 of 1,280: the traffic draws its ids "
                           "from the slice, logits and comparison are over it"})


def real_copy(tmp) -> str:
    """The repository's ``BENCHMARK.json`` and the data files it names."""
    root = str(tmp)
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), root)
    for d in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(tiny.REPO, "bench", d),
                        os.path.join(root, "bench", d))
    return root


def files_of(root):
    return {os.path.join(dp, p): open(os.path.join(dp, p)).read()
            for dp, _, fs in os.walk(os.path.join(root, "bench")) for p in fs}


def a_later_pr(root, config=NEW_MOE, reduced=None, lists=()):
    """What a later PR brings: a configuration, a traffic mix, limits and two
    metric files, and in ``BENCHMARK.json`` one ``configs`` entry, one
    ``workloads`` entry, the cell on ``serve_tok_s``'s list, two ``per_layer``
    entries of its own (and the cell on the per-layer ``lists`` named)."""
    bench = os.path.join(root, "bench")
    tiny._dump(os.path.join(bench, "configs", NEW_CONFIG + ".json"), config)
    tiny._dump(os.path.join(bench, "traffic", "new-closed.json"), tiny.CLOSED)
    tiny._dump(os.path.join(bench, "limits", NEW_CELL + ".json"),
               {"limits": longcat_tiny.LIMITS})
    own = {"new_requests_done": ("requests", "1", "scheduler / KV pool", "higher"),
           "new_step_s": ("engine_step_s", "s", "engine step", "lower")}
    for name, (field, unit, layer, _) in own.items():
        tiny._dump(os.path.join(bench, "metrics", name + ".json"),
                   {"reader": "measured", "field": field, "unit": unit,
                    "layer": layer, "moves": "serve_tok_s"})
    path = os.path.join(root, "BENCHMARK.json")
    bm = json.load(open(path))
    bm["configs"].append({
        "name": NEW_CONFIG, "source": "test",
        "file": f"bench/configs/{NEW_CONFIG}.json",
        "reduced": sorted(config["reduced"]) if reduced is None else reduced,
        "why": "test"})
    bm["workloads"].append({"name": NEW_CELL, "config": NEW_CONFIG,
                            "traffic": "new-closed", "chips": 1, "why": "test"})
    for m in bm["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append(NEW_CELL)
    for m in bm["per_layer"]:
        if m["name"] in lists:
            m["workloads"].append(NEW_CELL)
    for name, (_, unit, layer, better) in own.items():
        bm["per_layer"].append({
            "name": name, "unit": unit, "better": better,
            "source": "host_clock", "layer": layer, "moves": "serve_tok_s",
            "workloads": [NEW_CELL]})
    tiny._dump(path, bm)


def test_a_second_throughput_cell_with_a_sliced_vocabulary_is_files_and_entries(
        tmp_path, monkeypatch):
    """What a later PR does, on the repository's own benchmark: a second cell
    judged on ``serve_tok_s`` and ``setup_s`` alone, with two per-layer
    metrics that list it alone, on a configuration that cuts depth, experts
    held and the vocabulary. No file that was there is edited, every rule of
    the contract still holds, the batch cell keeps its metrics, and the cell
    runs: ids, logits and comparison over the slice."""
    from paddle_tpu.serving import ServingEngine
    root = real_copy(tmp_path)
    for check in contract.EVERY:
        check(root)
    before = files_of(root)
    a_later_pr(root)
    for check in contract.EVERY:
        check(root)
    cell = spec.Cell(NEW_CELL, root)
    assert {m["name"] for m in cell.end_to_end()} == {"serve_tok_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer()] == ["new_requests_done",
                                                     "new_step_s"]
    submitted, submit = [], ServingEngine.submit

    def spy(self, prompt, **kw):
        submitted.append(prompt)
        return submit(self, prompt, **kw)

    monkeypatch.setattr(ServingEngine, "submit", spy)
    res = R.execute(cell, tiny.args(seed=3600000123), tiny.DEVICE)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    ids = [i for prompt in submitted for i in prompt]
    assert len(submitted) > 8 and 0 <= min(ids) and 256 < max(ids) < 320
    ctx = {"cell": cell, "measured": {"requests": 7, "engine_step_s": 0.5}}
    assert cell.reader("new_requests_done")(ctx, "new_requests_done") == 7
    assert cell.reader("new_step_s")(ctx, "new_step_s") == 0.5
    after = files_of(root)
    assert all(after[p] == text for p, text in before.items())


@pytest.mark.parametrize("fault,refused", [
    ("a sixteenth of the vocabulary", "under an eighth"),
    ("a width in reduced", "hidden_size: a width may not be cut"),
    ("on a list that moves what it does not report",
     "paged_attn_time_share.serve moves itl_p95_s, which "
     r"\['new-moe-batch'\] does not report"),
])
def test_what_a_later_pr_may_not_bring(tmp_path, fault, refused):
    root = real_copy(tmp_path)
    if fault == "a sixteenth of the vocabulary":
        a_later_pr(root, dict(NEW_MOE, vocab_size=80))
    elif fault == "a width in reduced":
        a_later_pr(root, dict(
            NEW_MOE, published=dict(NEW_MOE["published"], hidden_size=192),
            reduced=dict(NEW_MOE["reduced"], hidden_size="test")))
    else:
        a_later_pr(root, lists=("paged_attn_time_share.serve",))
    with pytest.raises(AssertionError, match=refused):
        contract.benchmark_json(root)
    contract.batch_cell(root)              # what was there is as it was
