"""The program's spans and scopes in the profiler's own trace: a
``RecordEvent`` is a ``jax.profiler.TraceAnnotation`` whoever started the
trace, the engine and the trainer carry their fixed spans, the models, the
decoders and the flash kernels their fixed scope names."""
import glob
import gzip
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer as opt, profiler as prof
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.parallel import SpmdTrainer
from paddle_tpu.serving import EngineConfig, ServingEngine
from paddle_tpu.serving import engine as engine_mod


def _host_spans(trace_dir, prefix, collections=False):
    """The host plane's complete events whose name starts with ``prefix``,
    from the trace ``jax.profiler`` wrote under ``trace_dir``; a Python
    collection's span (``<prefix>gc``, whenever one runs) only if asked."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.trace.json.gz")
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    host = {e["pid"] for e in events if e.get("ph") == "M"
            and e["name"] == "process_name"
            and e["args"]["name"].startswith("/host:")}
    got = [e for e in events if e.get("ph") == "X" and e["pid"] in host
           and e["name"].startswith(prefix)
           and (collections or not e["name"].endswith(".gc"))]
    return sorted(got, key=lambda e: e["ts"])


def _llama():
    paddle.seed(3)
    cfg = LlamaConfig.tiny(vocab_size=61, hidden_size=32, layers=2, heads=4,
                           kv_heads=2, seq=64)
    cfg.use_flash_attention = False
    return LlamaForCausalLM(cfg)


def test_record_event_lands_in_a_bare_jax_trace_with_its_arguments(tmp_path):
    """No ``Profiler`` object, no flag: ``jax.profiler.start_trace`` alone."""
    assert not prof.host_tracing_enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with prof.RecordEvent("unit.outer", prof.TracerEventType.UserDefined,
                              tokens=7, wait_s=0.25):
            with prof.RecordEvent("unit.inner"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    outer, inner = _host_spans(tmp_path, "unit.")
    assert (outer["name"], inner["name"]) == ("unit.outer", "unit.inner")
    assert outer["args"] == {"tokens": "7", "wait_s": "0.25"}
    # nested by containment, on one clock
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert inner["dur"] >= 2000


def test_profiler_buffer_keeps_the_counts_too():
    with prof.Profiler() as p:
        with prof.RecordEvent("unit.counted", tokens=3):
            pass
    (ev,) = [e for e in p._events if e["name"] == "unit.counted"]
    assert ev["args"] == {"tokens": 3} and ev["cat"] == "UserDefined"


def test_inactive_record_event_costs_under_5_us():
    """With no trace running and no ``Profiler`` recording a span is an
    inactive ``TraceMe`` and a few attribute writes."""
    assert not prof.host_tracing_enabled()
    n = 50_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with prof.RecordEvent("noop", tokens=1, wait_s=0.5):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 5e-6, f"inactive span {best:.2e} s"


SERVE_PHASES = ["serve.schedule", "serve.run", "serve.pack", "serve.launch",
                "serve.sync", "serve.emit", "serve.post"]
HOST = {"host_wall_us", "host_sync_us", "host_cpu_us", "host_offcpu_us",
        "host_lock_us", "gc_us", "gc_collections", "compile_us", "compiles"}
FIRST = {"first_tokens", "first_token_s", "first_token_steps"}


def test_engine_emits_each_phase_once_a_step_with_the_plans_counts(
        tmp_path, monkeypatch):
    """A step launches its plan and then reads back the step before it:
    ``serve.pack``/``serve.launch`` are this step's, ``serve.sync``/
    ``serve.emit`` the one before's, all four inside ``serve.run``. The
    first step has nothing in flight to read; after the last plan one call
    only reads (no ``serve.run``), and one more finds no work."""
    eng = ServingEngine(_llama(), EngineConfig(
        max_seqs=4, token_budget=16, block_size=4, num_blocks=64))
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(1, 61, 9).tolist(), max_new_tokens=3)
    eng.run_until_idle()                       # compiled before the trace
    plans = []
    run_plan = ServingEngine._run_plan

    def spy(self, plan, got, armed=False):
        plans.append(plan.total_tokens)
        return run_plan(self, plan, got, armed)

    monkeypatch.setattr(ServingEngine, "_run_plan", spy)
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = [eng.submit(rng.integers(1, 61, n).tolist(), max_new_tokens=4)
                for n in (21, 6, 11)]
        while eng.step():
            pass
        eng.step()                             # finds no work: no plan runs
    finally:
        jax.profiler.stop_trace()
    assert all(r.done and r.error is None for r in reqs)
    spans = _host_spans(tmp_path, "serve.")
    steps = [s for s in spans if s["name"] == "serve.step"]
    assert len(steps) == len(plans) + 2

    def inside(step):
        lo, hi = step["ts"], step["ts"] + step["dur"]
        return [s for s in spans if s is not step and lo <= s["ts"] <= hi]

    assert [s["name"] for s in inside(steps[-2])] == [
        "serve.schedule", "serve.sync", "serve.emit", "serve.post"]
    assert [s["name"] for s in inside(steps[-1])] == [
        "serve.schedule", "serve.post"]
    assert sum(s["name"] == "serve.submit" for s in spans) == 3
    for i, (step, tokens) in enumerate(zip(steps, plans)):
        got = inside(step)
        names = [n for n in SERVE_PHASES
                 if i or n not in ("serve.sync", "serve.emit")]
        assert sorted(s["name"] for s in got) == sorted(names)
        by = {s["name"]: s for s in got}
        run = by["serve.run"]
        for child in names[2:-1]:
            assert run["ts"] <= by[child]["ts"]
            assert by[child]["ts"] + by[child]["dur"] \
                <= run["ts"] + run["dur"] + 1e-3
        order = [by[n]["ts"] for n in names]
        assert order == sorted(order)
        a = run["args"]
        assert int(a["prefill_tokens"]) + int(a["decode_tokens"]) == tokens
        # only the counts a metric reads ride on the spans
        assert set(a) == {"prefill_tokens", "decode_tokens",
                          "first_scheduled", "first_wait_s",
                          "pages_walked", "pages_tabled", "attn_tiles",
                          "attn_tiles_ahead", "layer_visits",
                          "device_fed_rows"}
        assert int(a["layer_visits"]) == 2     # one visit a layer
        # and those of the step's first tokens on serve.emit, where the host's
        # time went on serve.post
        assert set(by["serve.post"]["args"]) >= HOST
        if "serve.emit" in by:             # where the step read sampled
            assert set(by["serve.emit"].get("args", {})) in (set(), FIRST)
        assert not any(s.get("args") for s in got
                       if s["name"] not in ("serve.run", "serve.emit",
                                            "serve.post"))
    runs = [s["args"] for s in spans if s["name"] == "serve.run"]
    # each request is planned for the first time exactly once
    assert sum(int(a["first_scheduled"]) for a in runs) == 3
    assert all(r.first_planned_at is not None for r in reqs)
    waited = sum(r.first_planned_at - r.arrival for r in reqs)
    assert sum(float(a["first_wait_s"]) for a in runs) \
        == pytest.approx(waited, rel=1e-4, abs=1e-6)
    assert sum(int(a["prefill_tokens"]) for a in runs) == 21 + 6 + 11
    assert sum(int(a["decode_tokens"]) for a in runs) == 3 * 3
    # every decode row took its token from the step before, on the device
    assert sum(int(a["device_fed_rows"]) for a in runs) == 3 * 3


def test_trainer_emits_step_and_block_spans(tmp_path):
    paddle.seed(5)
    model = GPTForCausalLM(GPTConfig.tiny(vocab_size=31, hidden_size=16,
                                          layers=1, heads=2, seq=16))
    tr = SpmdTrainer(model, opt.AdamW(learning_rate=1e-3,
                                      parameters=model.parameters()),
                     lambda m, ids, y: m.compute_loss(m(ids), y))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 31, (2, 16)),
                      jnp.int32)
    tr.train_step(ids, ids)
    tr.block()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            tr.train_step(ids, ids)
            tr.block()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path, "train.")
    assert [s["name"] for s in spans] == ["train.step", "train.block"] * 2
    assert not any(s.get("args") for s in spans)


def _scope_names(text):
    """Every whole name on the scope paths of a lowered program."""
    return set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*",
                          " ".join(re.findall(r'loc\("([^"]*)"', text))))


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_serving_step_program_names_its_scopes(family):
    if family == "llama":
        model = _llama()
    else:
        paddle.seed(4)
        model = GPTForCausalLM(GPTConfig.tiny(vocab_size=61, hidden_size=32,
                                              layers=2, heads=4, seq=64))
    eng = ServingEngine(model, EngineConfig(
        max_seqs=2, token_budget=8, block_size=4, num_blocks=16))
    t = eng.config.token_budget
    i32 = jnp.zeros(t, jnp.int32)
    text = engine_mod._engine_step.lower(
        eng.dec, None, eng._sample, None, eng._w, i32, eng._prev, i32, i32,
        i32, jnp.zeros(t, bool), jnp.asarray(eng._tables), eng._kp,
        eng._vp).as_text(debug_info=True)
    assert {"embed", "attn_proj", "kv_write", "paged_attention", "mlp",
            "head"} <= _scope_names(text)


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_training_step_program_names_its_scopes(family):
    if family == "llama":
        model = _llama()
    else:
        paddle.seed(4)
        model = GPTForCausalLM(GPTConfig.tiny(vocab_size=61, hidden_size=32,
                                              layers=2, heads=4, seq=64))
    tr = SpmdTrainer(
        model, opt.AdamW(learning_rate=1e-3, parameters=model.parameters(),
                         grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0)),
        lambda m, ids, y: m.compute_loss(m(ids), y),
        remat_layers=list(model.model.layers if family == "llama"
                          else model.transformer.h), remat_policy="full")
    ids = jnp.zeros((2, 16), jnp.int32)
    tr.train_step(ids, ids)
    text = tr._step_fn.lower(
        {n: tr._params[n]._data for n in tr._param_list}, tr._opt_state,
        jnp.float32(1e-3), jnp.float32(1), jax.random.PRNGKey(0), ids,
        ids).as_text(debug_info=True)
    assert {"embed", "attention", "mlp", "head_loss", "clip",
            "optimizer_step", "rematted_computation"} <= _scope_names(text)


def test_flash_kernels_are_named_per_direction():
    from paddle_tpu.kernels import flash_pallas as fp
    x = jnp.ones((1, 2, 256, 128), jnp.bfloat16)

    def loss(q, k, v):
        return fp.flash_attention(q, k, v, causal=True) \
            .astype(jnp.float32).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x))
    assert re.findall(r"name=(flash_\w+)", jaxpr) == [
        "flash_fwd", "flash_dq", "flash_dkv"]
