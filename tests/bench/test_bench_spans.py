"""The program's spans and scopes as the benchmark reads them, on the CPU:
``bench/lib/spans.py`` and its three readers on two steps of a traced chat run
and of a traced training run cut from chip runs of PR 26
(``bench/tools/cut_trace.py``; the span arguments the program no longer
writes taken off), the idle attribution on hand-made gaps, and
that a reader with nothing to read leaves its metric out."""
import json
import os

import pytest

import bench_tiny as tiny
from bench import run as R
from bench.lib import spans as S, spec, trace as T
from bench.readers import scope_share, span_mean, span_ratio
from bench.tools import cut_trace, idle_by_span

CHAT = os.path.join(tiny.DATA, "chat_two_steps.trace.json.gz")
TRAIN = os.path.join(tiny.DATA, "train_two_steps.trace.json.gz")
OLD = os.path.join(tiny.DATA, "train_step.trace.json.gz")   # PR 24: no spans
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


class Cell:
    """What a reader asks of a cell, with metric files made on the spot."""

    def __init__(self, files, config=None, traffic=None, chips=1):
        self.root, self.name, self.chips = "/nowhere", "cell", chips
        self.files, self.config, self.traffic = files, config, traffic

    def metric_file(self, name):
        return self.files[name]


def ctx_for(monkeypatch, path, files, **cell):
    monkeypatch.setattr(T, "find", lambda _dir: path)
    return {"cell": Cell(files, **cell), "peaks": V5E}


# -- names and gaps, by hand ------------------------------------------------------
def op(start, dur, name="fusion.1", tf_op=""):
    return (start, dur, name, tf_op)


def test_a_scope_is_a_whole_name_on_the_path_or_the_operations_own():
    bwd = op(0, 1, "fusion.7", "jit(step_fn)/transpose(jvp(head_loss))/dot_general:")
    assert S.has("head_loss", bwd) and not S.has("head", bwd)
    paged = op(0, 1, "fusion.9", "jit(_engine_step_impl)/paged_attention/gather:")
    assert S.has("paged_attention", paged) and not S.has("attention", paged)
    fused = op(0, 1, "fusion.2", "jit(f)/squeeze;jit(f)/attn_proj/dot_general:")
    assert S.has("attn_proj", fused)
    kernel = op(0, 1, "flash_fwd.3", "")
    assert S.has("flash_fwd", kernel) and not S.has("flash", kernel)
    assert S.scope_of(bwd) == "transpose(jvp(head_loss))"
    assert S.scope_of(op(0, 1, "copy.1", "jit(step_fn)/copy:")) == ""


def test_idle_is_cut_at_span_edges_and_goes_to_the_innermost_span():
    """One gap inside ``serve.pack``, one from the end of a ``serve.step``
    to the next one's start and beyond (cut at both edges), two under the
    floor, one inside a step but in none of its phases."""
    spans = [(100, 900, "serve.step", {}), (150, 800, "serve.run", {}),
             (200, 300, "serve.pack", {}), (500, 100, "serve.launch", {}),
             (1100, 500, "serve.step", {}), (1150, 50, "serve.schedule", {})]
    ops = [op(0, 210), op(450, 40), op(500, 460), op(970, 10), op(1120, 90),
           op(1250, 100)]
    idle = S.idle_by_span({"spans": spans, "ops": ops})
    assert idle == {
        "serve.pack": pytest.approx(240e-6),       # 210..450
        # 980..1120: 20 in the first step, 100 between the two, 20 in the
        # second before its schedule; 1210..1250 after it
        S.OUTSIDE: pytest.approx(100e-6),
        "serve.step": pytest.approx((20 + 20 + 40) * 1e-6),
    }                      # 490..500 and 960..970 are under the 20 us floor


def test_an_operation_of_the_compilers_is_read_under_a_neighbours_scope():
    """A convert or a copy the compiler made carries no ``tf_op``: it takes
    the scope of the nearest operation it reads from, else of the nearest
    one that reads it, inside its own program; with neither it stays
    unscoped. Another program's ``copy.1`` is another operation."""
    gather, scatter = "jit(f)/paged_attention/gather:", "jit(f)/kv_write/scatter:"
    head = "jit(g)/head/dot_general:"
    ops = [
        (0, 1, "fusion.5", gather, "%fusion.5 = bf16[8] fusion(bf16[4] %p.1)", "f"),
        (1, 5, "convert.2", "", "%convert.2 = f32[8] convert(bf16[8] %fusion.5)", "f"),
        (6, 1, "copy.9", "", "%copy.9 = f32[8] copy(f32[8] %convert.2)", "f"),
        (7, 1, "copy.1", "", "%copy.1 = bf16[4] copy(bf16[4] %p.2)", "f"),
        (8, 1, "copy-start.3", "", "%copy-start.3 = s32[] copy-start(s32[] %p.3)", "f"),
        (9, 1, "fusion.7", scatter, "%fusion.7 = bf16[4] fusion(bf16[4] %copy.1, "
         "s32[] %copy-start.3), kind=kLoop, calls=%fused_computation.7", "f"),
        (10, 1, "copy.8", "", "%copy.8 = bf16[4] copy(bf16[4] %p.4)", "f"),
        (11, 5, "convert.2", "", "%convert.2 = f32[8] convert(bf16[8] %fusion.5)", "f"),
        (20, 1, "fusion.5", head, "%fusion.5 = bf16[8] fusion(bf16[4] %p.1)", "g"),
        (21, 1, "copy.1", "", "%copy.1 = bf16[8] copy(bf16[8] %fusion.5)", "g"),
    ]
    assert S.inherited_scopes(ops) == {
        ("f", "convert.2"): gather, ("f", "copy.9"): gather,
        ("f", "copy.1"): scatter, ("f", "copy-start.3"): scatter,
        ("f", "copy.8"): "", ("g", "copy.1"): head}


def test_between_sums_end_to_next_start():
    spans = [(0, 100, "serve.step", {}), (130, 100, "serve.step", {}),
             (250, 10, "serve.step", {}), (140, 20, "serve.run", {"n": "3"})]
    t = {"spans": sorted(spans), "ops": [op(0, 1)]}
    assert S.between(t, "serve.step") == 30 + 20
    assert S.total(t, ["serve.step", "serve.run"]) == 230
    assert S.argument(t, "serve.run", ["n", "n"]) == 6


# -- nothing to read ----------------------------------------------------------------
@pytest.mark.parametrize("reader,spec_", [
    (span_mean, {"spans": ["serve.sync"]}),
    (span_mean, {"between": "serve.step"}),
    (span_ratio, {"span": "serve.run", "num": ["first_wait_s"],
                  "den": ["first_scheduled"], "unit": "s"}),
    (scope_share, {"scope": "rematted_computation"}),
    (scope_share, {"scope": "rematted_computation", "inferred": True}),
    (scope_share, {"kernel": "flash_fwd", "flops": "fwd"}),
])
def test_a_trace_without_program_spans_reads_nothing(monkeypatch, reader, spec_):
    """PR 24's recorded trace is of a program with no span of its own (though
    its scope paths hold ``rematted_computation``), and a run may leave no
    trace at all: each reader answers None and the line leaves the metric
    out."""
    assert S.load(OLD) is None
    ctx = ctx_for(monkeypatch, OLD, {"m": spec_})
    assert reader.read(ctx, "m") is None
    ctx = ctx_for(monkeypatch, None, {"m": spec_})
    assert reader.read(ctx, "m") is None


def test_a_reader_finds_no_such_span_or_scope(monkeypatch):
    files = {"a": {"spans": ["train.block"]},          # no serve.run to count
             "b": {"span": "serve.run", "num": ["x"], "den": ["y"], "unit": "s"},
             "c": {"scope": "paged_attention"},
             "d": {"scope": "paged_attention", "inferred": True}}
    ctx = ctx_for(monkeypatch, TRAIN, files)
    assert span_mean.read(ctx, "a") is None
    assert span_ratio.read(ctx, "b") is None
    assert scope_share.read(ctx, "c") is None
    assert scope_share.read(ctx, "d") is None


# -- two steps of a chat run on a v5e ---------------------------------------------
SERVE_FILES = {
    "sync": {"spans": ["serve.sync"]},
    "prepare": {"spans": ["serve.pack", "serve.launch"]},
    "outside": {"between": "serve.step"},
    "wait": {"span": "serve.run", "num": ["first_wait_s"],
             "den": ["first_scheduled"], "unit": "s"},
    "prefill": {"span": "serve.run", "num": ["prefill_tokens"],
                "den": ["prefill_tokens", "decode_tokens"], "unit": "%"},
    "paged": {"scope": "paged_attention"},
    "kv_write": {"scope": "kv_write"},
    "dense": {"scope": ["attn_proj", "mlp", "head"]},
    "embed": {"scope": "embed"},
    "paged_inferred": {"scope": "paged_attention", "inferred": True},
    "kv_write_inferred": {"scope": "kv_write", "inferred": True},
}
SERVE_SCOPES = ["paged_attention", "kv_write", "attn_proj", "mlp", "head",
                "embed"]


def test_chat_fixture_spans_and_their_arguments():
    t = S.load(CHAT)
    steps = S.named(t, "serve.step")
    assert len(steps) == 2 and len(S.named(t, "serve.run")) == 2
    for name in ("serve.schedule", "serve.pack", "serve.launch", "serve.sync",
                 "serve.emit", "serve.post"):
        assert len(S.named(t, name)) == 2, name
    # nested by containment: every phase inside one of the two steps
    for start, dur, name, _ in t["spans"]:
        if name not in ("serve.step", "serve.submit"):
            assert any(s <= start and start + dur <= s + d + 1e-3
                       for s, d, _, _ in steps), name
    run = S.named(t, "serve.run")[0][3]
    assert set(run) == {"prefill_tokens", "decode_tokens", "first_scheduled",
                        "first_wait_s"}
    # the chat cell's budget is 128 tokens a step
    tokens = S.argument(t, "serve.run", ["prefill_tokens", "decode_tokens"])
    assert 128 < tokens <= 256 and tokens == int(tokens)


def test_chat_fixture_a_spans_mean_and_a_ratio_of_arguments(monkeypatch):
    ctx = ctx_for(monkeypatch, CHAT, SERVE_FILES)
    t = S.load(CHAT)
    sync = [s[1] for s in S.named(t, "serve.sync")]
    assert span_mean.read(ctx, "sync") == pytest.approx(sum(sync) / 2e6)
    assert 0.105 < span_mean.read(ctx, "sync") < 0.120     # the step program
    assert 0.001 < span_mean.read(ctx, "prepare") < 0.005
    a, b = S.named(t, "serve.step")
    assert span_mean.read(ctx, "outside") \
        == pytest.approx((b[0] - a[0] - a[1]) / 2e6)
    prefill = S.argument(t, "serve.run", ["prefill_tokens"])
    decode = S.argument(t, "serve.run", ["decode_tokens"])
    assert span_ratio.read(ctx, "prefill") \
        == pytest.approx(100 * prefill / (prefill + decode))
    first = S.argument(t, "serve.run", ["first_scheduled"])
    assert first >= 1
    assert span_ratio.read(ctx, "wait") == pytest.approx(
        S.argument(t, "serve.run", ["first_wait_s"]) / first)


def test_chat_fixture_scope_shares_add_up_to_the_busy_time(monkeypatch):
    ctx = ctx_for(monkeypatch, CHAT, SERVE_FILES)
    t = S.load(CHAT)
    busy = T.busy_us(t["ops"])
    assert busy == pytest.approx(sum(o[1] for o in t["ops"]))   # one at a time
    shares = {k: scope_share.read(ctx, k)
              for k in ("paged", "kv_write", "dense", "embed")}
    unscoped = sum(o[1] for o in t["ops"]
                   if not any(S.has(f, o) for f in SERVE_SCOPES))
    assert sum(shares.values()) + 100 * unscoped / busy == pytest.approx(100)
    assert shares["paged"] > 60 and shares["dense"] > 10
    assert 100 * unscoped / busy < 10
    # the compiler's own copies and converts are read under a neighbour's scope
    lent = [o for o in t["ops"] if o[3] and not o[4]]
    assert lent and all(o[3].startswith("jit(") for o in lent)
    # and each share says how much of it is there by that inference: the
    # pool's change of layout and the stacking of the new pools under
    # ``kv_write``; what runs under ``paged_attention`` names it itself
    assert scope_share.read(ctx, "kv_write_inferred") == pytest.approx(
        100 * sum(o[1] for o in lent if S.has("kv_write", o)) / busy)
    assert 5 < scope_share.read(ctx, "kv_write_inferred") < shares["kv_write"]
    assert 0 <= scope_share.read(ctx, "paged_inferred") < 0.01
    # an operation's name is its own only inside its program: the step
    # program's ``copy-start`` (a weight) and the argmax program's
    steps = {o[3] for o in t["ops"] if o[2] == "copy-start"}
    assert len(steps) > 1


def test_chat_fixture_idle_by_span():
    t = S.load(CHAT)
    idle = S.idle_by_span(t)
    busy = T.busy_intervals(t["ops"])
    gaps = sum(b - a for (_, a), (b, _) in zip(busy, busy[1:])
               if b - a >= T.GAP_FLOOR_US)
    assert sum(idle.values()) == pytest.approx(gaps / 1e6)
    # the one long gap a step: the tail of the wait for the tokens, then the
    # host's phases, then the launch until the program starts
    assert {"serve.sync", "serve.emit", "serve.schedule", "serve.pack",
            "serve.launch", S.OUTSIDE} <= set(idle)
    assert max(idle, key=idle.get) in ("serve.sync", "serve.launch")


# -- two steps of a training run on a v5e -----------------------------------------
CGPT = json.load(open(os.path.join(tiny.REPO, "bench", "configs",
                                   "cerebras-gpt-1.3b-l14.json")))
TRAIN_2K = json.load(open(os.path.join(tiny.REPO, "bench", "traffic",
                                       "train-2k.json")))
TRAIN_FILES = {
    "fwd": {"kernel": "flash_fwd", "flops": "fwd"},
    "bwd": {"kernel": ["flash_dq", "flash_dkv"], "flops": "bwd"},
    "recompute": {"scope": "rematted_computation"},
    "head_loss": {"scope": "head_loss"},
}


def test_train_fixture_kernels_by_name_and_their_rooflines(monkeypatch):
    from bench.archs import gpt2
    ctx = ctx_for(monkeypatch, TRAIN, TRAIN_FILES, config=CGPT, traffic=TRAIN_2K)
    ctx["arch"] = gpt2
    t = S.load(TRAIN)
    assert [s[2] for s in t["spans"]] == ["train.step", "train.block"] * 2
    # 2 steps x 14 layers; the forward runs twice under full remat
    counts = [sum(S.has(k, o) for o in t["ops"])
              for k in ("flash_fwd", "flash_dq", "flash_dkv")]
    assert counts == [56, 28, 28]
    remat = [o for o in t["ops"] if S.has("flash_fwd", o)
             and S.has("rematted_computation", o)]
    assert len(remat) == 28
    fwd_s = S.time_in(t, ["flash_fwd"]) / 1e6
    need = 2 * 14 * (2 / 7) * (7 * 2 * 2048 * 2048 * 128 * 8 * 16 / 2)
    assert scope_share.read(ctx, "fwd") == pytest.approx(
        100 * need / 197e12 / fwd_s)
    assert 2.5 < scope_share.read(ctx, "fwd") < 3.2
    assert 6.5 < scope_share.read(ctx, "bwd") < 8.0
    busy = T.busy_us(t["ops"])
    assert scope_share.read(ctx, "recompute") == pytest.approx(
        100 * S.time_in(t, ["rematted_computation"]) / busy)
    assert 15 < scope_share.read(ctx, "recompute") < 25
    assert 5 < scope_share.read(ctx, "head_loss") < 12


def test_train_fixture_unscoped_share_and_idle():
    t = S.load(TRAIN)
    busy = T.busy_us(t["ops"])
    named_ = ("attention", "mlp", "head_loss", "embed", "clip",
              "optimizer_step")
    unscoped = sum(o[1] for o in t["ops"]
                   if not any(S.has(f, o) for f in named_))
    assert 100 * unscoped / busy < 5
    idle = S.idle_by_span(t)
    assert set(idle) <= {"train.step", "train.block", S.OUTSIDE}
    assert sum(idle.values()) < 0.01 * busy / 1e6


# -- the tools, and the whole line --------------------------------------------------
def test_idle_by_span_prints_its_tables(monkeypatch, capsys):
    monkeypatch.setattr(T, "find", lambda _dir: CHAT)
    assert idle_by_span.main(["anywhere"]) == 0
    out = capsys.readouterr().out
    first = out.split("\n\n")[0].splitlines()
    assert first[0].startswith("idle seconds by program span")
    assert any(line.startswith("serve.sync") for line in first)
    assert "device seconds by scope path" in out and "paged_attention" in out
    monkeypatch.setattr(T, "find", lambda _dir: OLD)
    assert idle_by_span.main(["anywhere"]) == 1      # no program span there
    assert idle_by_span.main([]) == 2


def test_cut_trace_keeps_whole_steps(tmp_path):
    import gzip
    with gzip.open(CHAT) as f:
        events = json.load(f)["traceEvents"]
    one = cut_trace.cut(events, skip=0, steps=1)
    assert len(one) < 0.6 * len(events)
    out = tmp_path / "one.trace.json.gz"
    with gzip.open(out, "wt") as f:
        json.dump({"traceEvents": one}, f)
    t = S.load(str(out))
    assert len(S.named(t, "serve.step")) == 1
    assert len(S.named(t, "serve.sync")) == 1
    with pytest.raises(SystemExit, match="holds 2 steps"):
        cut_trace.cut(events, skip=1, steps=2)


NEW_SERVE = {"step_schedule_s", "step_prepare_s", "step_emit_s",
             "step_device_wait_s", "step_outside_s",
             "paged_attn_time_share.serve", "kv_write_time_share.serve",
             "dense_time_share.serve", "paged_attn_inferred_share.serve",
             "kv_write_inferred_share.serve", "dense_inferred_share.serve"}
# chat_ttft_mean_s: the mean the cell was judged on until PR 36, per layer since
NEW_CHAT = NEW_SERVE | {"sched_queue_wait_mean_s", "sched_prefill_token_share",
                        "chat_ttft_mean_s"}
NEW_TRAIN = {"flash_fwd_roofline", "flash_bwd_roofline",
             "recompute_time_share.train", "head_loss_time_share.train",
             "recompute_inferred_share.train", "head_loss_inferred_share.train"}


# the chat kind times the requests whose 1 s tail ends before the profiler
# starts, six tenths into the window: 4 s leave it a second of arrivals
@pytest.mark.parametrize("cell_name,seconds,recorded,new", [
    ("tiny-llama-chat", 4.0, CHAT, NEW_CHAT),
    ("tiny-gpt-closed", 1.5, CHAT, NEW_SERVE),
    ("tiny-gpt-train", 1.5, TRAIN, NEW_TRAIN),
])
def test_a_traced_runs_line_holds_every_new_metric_of_its_cell(
        tmp_path_factory, monkeypatch, cell_name, seconds, recorded, new):
    """``--trace 1`` end to end on the CPU with a recorded trace in the run's
    place: each metric this PR adds to the cell's kind is on the line with a
    value, and none of another kind's."""
    root = tiny.make_root(tmp_path_factory.mktemp("bench_root"))
    monkeypatch.setattr(T, "find", lambda _dir: recorded)
    res = R.execute(spec.Cell(cell_name, root),
                    tiny.args(seconds=seconds, trace=1),
                    {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    got = res["metrics"]
    assert new <= set(got)
    assert not (NEW_CHAT | NEW_TRAIN) - new & set(got)
    for name in new:                 # a scope may be all its own: 0 inferred
        assert got[name]["value"] > 0 or "_inferred_" in name, name
    json.dumps(res)
