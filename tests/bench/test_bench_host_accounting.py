"""PR 39's metric files over two steps of a traced chat run on the chip: where
each launched step's host time went (``serve.post``), the first tokens
(``serve.emit``) and the decode rows fed on the device (``serve.run``), read
by the ``span_counts`` reader; PR 26's recording of an older program carries
none of those arguments and reads nothing."""
import os

import pytest

import bench_tiny as tiny
from bench.lib import spans as S, spec

CELL = "mistral7b-serve-chat"
# (my chip run, PR 39, seed 3900000102), cut by ``bench/tools/cut_trace.py``
# at two steps whose second hands out a first token
TWO_STEPS = os.path.join(tiny.DATA, "chat_host_two_steps.trace.json.gz")
CHAT = os.path.join(tiny.DATA, "chat_two_steps.trace.json.gz")      # PR 26
NEW = {   # name: (span, num, den, unit, layer)
    "host_offcpu_share.serve": ("serve.post", "host_offcpu_us",
                                "host_wall_us", "%", "engine step"),
    "host_gc_share.serve": ("serve.post", "gc_us", "host_wall_us", "%",
                            "engine step"),
    "host_compile_share.serve": ("serve.post", "compile_us", "host_wall_us",
                                 "%", "engine step"),
    "engine_ttft_mean_s": ("serve.emit", "first_token_s", "first_tokens", "s",
                           "scheduler / KV pool"),
    "first_token_steps.serve": ("serve.emit", "first_token_steps",
                                "first_tokens", "steps",
                                "scheduler / KV pool"),
    "device_fed_share.serve": ("serve.run", "device_fed_rows",
                               "decode_tokens", "%", "engine step"),
}
HOST = ("host_wall_us", "host_sync_us", "host_cpu_us", "host_offcpu_us",
        "host_lock_us", "gc_us", "gc_collections", "compile_us", "compiles")


def read(monkeypatch, path, name):
    cell = spec.Cell(CELL, tiny.REPO)
    ctx = tiny.recorded_context(monkeypatch, path, cell)
    return cell.reader(name)(ctx, name)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_metric_file_is_span_counts_over_its_arguments(name):
    span, num, den, unit, layer = NEW[name]
    f = spec.Cell(CELL, tiny.REPO).metric_file(name)
    assert f == {"reader": "span_counts", "span": span, "num": [num],
                 "den": [den], "unit": unit, "layer": layer,
                 "moves": "itl_p95_s"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_metric_reads_the_recorded_steps_and_not_the_older_program(
        monkeypatch, name):
    value = read(monkeypatch, TWO_STEPS, name)
    assert value is not None and value <= (100.0 if NEW[name][3] == "%"
                                           else float("inf"))
    # off the CPU: wall - sync - CPU outside the sync, which two steps of a
    # host that counts CPU time in 10 ms ticks can put below zero
    assert value >= 0 or name == "host_offcpu_share.serve"
    assert read(monkeypatch, CHAT, name) is None


def test_the_recorded_steps_say_where_the_host_was(monkeypatch):
    t = S.load(TWO_STEPS)
    posts = [s[3] for s in S.named(t, "serve.post")]
    assert len(posts) == 2 and all(set(HOST) <= set(p) for p in posts)
    for p in posts:
        a = {k: float(v) for k, v in p.items()}
        assert a["host_sync_us"] + a["host_cpu_us"] + a["host_offcpu_us"] \
            >= a["host_wall_us"] - 2       # each truncated to a microsecond
        assert a["compiles"] == 0 and a["compile_us"] == 0
    # the host waited in serve.sync for the device through most of a step
    sync = sum(float(p["host_sync_us"]) for p in posts)
    assert sync == pytest.approx(
        sum(s[1] for s in S.named(t, "serve.sync")), rel=0.05)
    assert read(monkeypatch, TWO_STEPS, "host_compile_share.serve") == 0
    assert read(monkeypatch, TWO_STEPS, "device_fed_share.serve") == 100.0
    steps = read(monkeypatch, TWO_STEPS, "first_token_steps.serve")
    assert steps >= 1 and steps == int(steps)
    assert 0.01 < read(monkeypatch, TWO_STEPS, "engine_ttft_mean_s") < 1.0


def test_the_chat_cells_declared_metrics_still_read_the_new_program(
        monkeypatch):
    """The arguments and the collections' spans change no reading of an
    accepted metric's kind: each that reads PR 26's older recording reads
    the new one too."""
    cell = spec.Cell(CELL, tiny.REPO)
    quiet = {"itl_mean_s": 0.016, "itl_p95_s": 0.0162, "engine_step_s": 0.0155}
    old = tiny.recorded_context(monkeypatch, CHAT, cell, **quiet)
    was = {m["name"] for m in cell.per_layer()
           if cell.reader(m["name"])(old, m["name"]) is not None}
    new = tiny.recorded_context(monkeypatch, TWO_STEPS, cell, **quiet)
    now = {m["name"] for m in cell.per_layer()
           if cell.reader(m["name"])(new, m["name"]) is not None}
    if not S.argument(S.load(TWO_STEPS), "serve.run", ["first_scheduled"]):
        was.discard("sched_queue_wait_mean_s")   # nobody first planned here
    assert was and was <= now
