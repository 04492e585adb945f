"""The whole-step readers over the steps a trace KEPT, on eight decode steps
recorded on a v5e (``cgpt67-serve-decode``, PR 36; ``bench/tools/cut_trace.py``):
the three readings by hand, the same readings from an export that kept the
earliest half or quarter of the events (whole steps, or cut inside a step as
the profiler's cap cuts), nothing read where nothing was kept, and what the
result line says of a cut and of an uncut trace."""
import gzip
import json
import os

import pytest

import bench_tiny as tiny
from bench.archs import gpt2
from bench.lib import flops, spec, trace as T
from bench.tools import cut_trace

DECODE = os.path.join(tiny.DATA, "decode_eight_steps.trace.json.gz")
OURO = os.path.join(tiny.DATA, "ouro_one_step.trace.json.gz")      # PR 29
WHOLE_STEP = ("step_hbm_roofline.serve", "step_mfu.serve",
              "device_idle_share.serve")
# the run the steps were cut from: its gap between tokens before the profiler
# started (``itl_mean_s`` on its line; my chip run, PR 36)
QUIET = {"itl_mean_s": 0.0154}


def events_of(path):
    with gzip.open(path) as f:
        return json.load(f)["traceEvents"]


def written(tmp_path, events, name="cut.trace.json.gz"):
    out = str(tmp_path / name)
    with gzip.open(out, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return out


def cell_of(name="cgpt67-serve-decode"):
    return spec.Cell(name, tiny.REPO)


def readings(ctx, names=WHOLE_STEP):
    cell = ctx["cell"]
    return {n: cell.reader(n)(ctx, n) for n in names}


def test_the_three_whole_step_metrics_list_the_three_cells_that_report_what_they_move():
    for name in WHOLE_STEP:
        (m,) = [m for m in cell_of().benchmark["per_layer"] if m["name"] == name]
        assert m["workloads"] == ["mistral7b-serve-chat", "cgpt67-serve-decode",
                                  "ouro26-serve-decode"]
        f = cell_of().metric_file(name)
        assert f["reader"] == "serve_step" and f["module"] == "jit__engine_step_impl"
    assert cell_of().metric_file("device_idle_share.serve")["period"] == "itl_mean_s"
    assert cell_of().metric_file("device_idle_share.train")["reader"] == "idle_share"


def test_the_readings_of_eight_recorded_decode_steps_by_hand(monkeypatch):
    cell = cell_of()
    ctx = tiny.recorded_context(monkeypatch, DECODE, cell, **QUIET)
    steps = ctx["measured"]["steps"]
    runs = sorted((ts, d) for ts, d, n in ctx["trace"]["modules"]
                  if n.startswith("jit__engine_step_impl"))
    assert len(steps) == len(runs) == 8
    got = T.kept(ctx, "jit__engine_step_impl")
    assert not got["cut"] and got["steps"] == steps
    seconds = sum(d for _, d in runs) / 1e6
    assert got["seconds"] == pytest.approx(seconds)
    assert seconds < got["busy_s"] < 1.02 * seconds     # and the argmax programs
    read = readings(ctx)
    need = sum(flops.serve_step_bytes(gpt2, cell.config, s[2], s[5]) for s in steps)
    assert read["step_hbm_roofline.serve"] == pytest.approx(
        100 * need / 819e9 / seconds)
    ops = sum(flops.serve_step_flops(gpt2, cell.config, s[2], s[3], [s[4]])
              for s in steps)
    assert read["step_mfu.serve"] == pytest.approx(100 * ops / 197e12 / seconds)
    assert read["device_idle_share.serve"] == pytest.approx(
        100 * (1 - got["busy_s"] / 8 / QUIET["itl_mean_s"]))
    # 6.87 GB of weights a step at the chip's bandwidth are 8.4 ms of 11.9
    assert 65 < read["step_hbm_roofline.serve"] < 85
    assert 5 < read["step_mfu.serve"] < 9
    assert 15 < read["device_idle_share.serve"] < 30
    # no quiet part to take the period from: nothing read, nothing raised
    without = dict(ctx, measured={k: v for k, v in ctx["measured"].items()
                                  if k not in QUIET})
    assert readings(without)["device_idle_share.serve"] is None


@pytest.mark.parametrize("how", ["whole steps", "the earliest events"])
@pytest.mark.parametrize("share", [2, 4])
def test_a_cut_trace_reads_what_the_uncut_one_does(monkeypatch, tmp_path, share, how):
    """The slice ran eight steps and the export kept a half or a quarter of
    its events: the readers divide what the KEPT steps need by the kept
    steps' own time. So each reading is what the uncut trace reads over those
    same steps, to rounding; the bandwidth share and the idle share, which
    every decode step has alike, are the uncut readings to 1 %; the compute
    share follows the rows a step held (one of the eight held 30, not 16)."""
    cell = cell_of()
    steps = tiny.recorded_steps(DECODE)
    uncut = tiny.recorded_context(monkeypatch, DECODE, cell, **QUIET)
    whole = readings(uncut)
    events = events_of(DECODE)
    if how == "whole steps":
        kept = cut_trace.cut(events, skip=0, steps=8 // share)
    else:                 # the cap falls inside a step, as the profiler's does
        timed = sum(e.get("ph") != "M" for e in events)
        kept = cut_trace.earliest(events, timed // share + 300)
    ctx = tiny.recorded_context(monkeypatch, written(tmp_path, kept), cell,
                                steps=steps, **QUIET)
    got = T.kept(ctx, "jit__engine_step_impl")
    n = len(got["steps"])
    assert got["cut"] and n in (8 // share - 1, 8 // share)
    assert got["steps"] == steps[:n]
    assert ctx["trace"]["trace_cut"] is True
    same_steps = readings(dict(uncut, measured=dict(uncut["measured"], slice=(0, n))))
    read = readings(ctx)
    for name in WHOLE_STEP:
        assert read[name] == pytest.approx(same_steps[name], rel=1e-6), name
    for name in ("step_hbm_roofline.serve", "device_idle_share.serve"):
        assert read[name] == pytest.approx(whole[name], rel=0.01), name
    rows = lambda some: sum(s[2] for s in some) / len(some)
    assert read["step_mfu.serve"] / rows(steps[:n]) == pytest.approx(
        whole["step_mfu.serve"] / rows(steps), rel=0.02)
    # what the parent's reader did: all eight steps' bytes over the kept time
    runs = [d for _, d, n in ctx["trace"]["modules"]
            if n.startswith("jit__engine_step_impl")]
    need = sum(flops.serve_step_bytes(gpt2, cell.config, s[2], s[5]) for s in steps)
    parents = 100 * need / 819e9 / (sum(runs) / 1e6)
    assert parents == pytest.approx(
        whole["step_hbm_roofline.serve"] * 8 / len(runs), rel=0.02)
    assert parents > 105              # where the driver refuses a line


@pytest.mark.parametrize("case", ["one execution of eight", "no execution",
                                  "no slice", "an empty slice"])
def test_nothing_kept_reads_nothing(monkeypatch, tmp_path, case):
    cell = cell_of()
    steps = tiny.recorded_steps(DECODE)
    events = events_of(DECODE)
    path = DECODE
    if case == "one execution of eight":    # it may have lost operations
        path = written(tmp_path, cut_trace.cut(events, skip=0, steps=1))
    elif case == "no execution":
        path = written(tmp_path, [e for e in events if not e.get(
            "name", "").startswith("jit__engine_step_impl")])
    ctx = tiny.recorded_context(monkeypatch, path, cell, steps=steps, **QUIET)
    if case == "no slice":
        ctx["measured"]["slice"] = (None, None)
    elif case == "an empty slice":
        ctx["measured"]["slice"] = (5, 5)
    assert T.kept(ctx, "jit__engine_step_impl") is None
    assert readings(ctx) == dict.fromkeys(WHOLE_STEP)
    batch = cell_of("longcat560-serve-batch")
    other = dict(ctx, cell=batch, arch=batch.arch())
    assert readings(other, ("step_mfu.batch.serve", "step_hbm_roofline.batch.serve",
                            "device_idle_share.batch.serve",
                            "latent_attn_roofline.serve")) \
        == dict.fromkeys(("step_mfu.batch.serve", "step_hbm_roofline.batch.serve",
                          "device_idle_share.batch.serve",
                          "latent_attn_roofline.serve"))


def test_steps_that_ran_no_plan_are_not_paired_with_an_execution(monkeypatch):
    """The open loop steps the engine only while it has work, but a step that
    scheduled nothing ran no program: it is not one of the slice's steps."""
    cell = cell_of()
    steps = tiny.recorded_steps(DECODE)
    idle_step = (0.0, 0.0, 0, 0, 0, 0, None, 0)
    ctx = tiny.recorded_context(monkeypatch, DECODE, cell,
                                steps=steps[:3] + [idle_step] + steps[3:], **QUIET)
    got = T.kept(ctx, "jit__engine_step_impl")
    assert not got["cut"] and got["steps"] == steps


def test_the_looped_cell_reads_its_one_recorded_step(monkeypatch):
    """``ouro26-serve-decode`` is on the three lists since PR 36: its step of
    192 layer visits reads under 100 % of the bandwidth bound."""
    cell = cell_of("ouro26-serve-decode")
    ctx = tiny.recorded_context(monkeypatch, OURO, cell, itl_mean_s=0.0541)
    assert len(ctx["measured"]["steps"]) == 1
    read = readings(ctx)
    assert 40 < read["step_hbm_roofline.serve"] < 100
    assert 3 < read["step_mfu.serve"] < 10
    assert 0 < read["device_idle_share.serve"] < 20


# -- what the result line says of the traced window ---------------------------------
def test_the_lines_window_is_the_slice_uncut_and_the_kept_steps_cut(monkeypatch,
                                                                   tmp_path):
    monkeypatch.setattr(T, "find", lambda _dir: DECODE)
    whole = T.reduce_dir("/nowhere", 0.1234, steps=8)
    assert (whole["window_s"], whole["trace_cut"], whole["steps_kept"]) \
        == (0.1234, False, 8)
    assert whole == {**T.reduce_dir("/nowhere", 0.1234), "trace_cut": False}
    starts = sorted(s[0] for s in whole["spans"] if s[2] == "bench.engine_step")
    assert len(starts) == 8
    # the run's slice held 500 steps and the export eight of them
    cut = T.reduce_dir("/nowhere", 8.0, steps=500)
    assert cut["trace_cut"] is True and cut["steps_kept"] == 7
    assert cut["window_s"] == pytest.approx((starts[-1] - starts[0]) / 1e6)
    assert cut["busy_s"] < whole["busy_s"] and len(cut["ops"]) == len(whole["ops"])
    # busy over window on the line is then near the reader's idle share, whose
    # period is the untraced gap: the traced steps are a little longer
    line_idle = 100 * (1 - cut["busy_s"] / cut["window_s"])
    ctx = tiny.recorded_context(monkeypatch, DECODE, cell_of(), **QUIET)
    assert abs(line_idle - readings(ctx)["device_idle_share.serve"]) < 5
    # the parent's line for the same export: kept busy time over the slice
    assert 100 * (1 - whole["busy_s"] / 8.0) > 95
