"""Ouro, the looped decoder (``models/ouro.py``, ``generation._OuroDecoder``):
the training forward, ``generate()``'s dense cache and the serving engine's
paged pools against the plain reference written straight through
(``bench/reference/ouro_block.forward``), logits and exit passes, at the
published threshold and at one under it; the pools' depth; pages that move
whole; the spans' counts; and the size of the traced step program.

Tiny sizes, float32, CPU: hidden 64, 4 heads, 2 layers x 3 passes, vocabulary
256.
"""
import functools
import glob
import gzip
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation as G, optimizer as opt
from paddle_tpu.models import OuroConfig, OuroForCausalLM
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import EngineConfig, ServingEngine
from paddle_tpu.serving import engine as engine_mod

import engine_record

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench.reference import ouro_block as ref  # noqa: E402

pytestmark = pytest.mark.serve

VOCAB, PASSES, LAYERS = 256, 3, 2
THRESHOLDS = [1.0, 0.6]


@functools.lru_cache(maxsize=None)
def _model(threshold):
    """Seeded weights: matrices and embeddings normal(0, 0.05), norm scales
    1 + 0.05 normal, a gate wide enough that passes differ."""
    cfg = OuroConfig.tiny(vocab_size=VOCAB, hidden_size=64, layers=LAYERS,
                          heads=4, passes=PASSES, seq=128,
                          threshold=threshold)
    cfg.use_flash_attention = False
    model = OuroForCausalLM(cfg)
    rng = np.random.default_rng(0)
    for name, p in model.named_parameters():
        z = rng.standard_normal(p.shape)
        if "norm" in name:
            v = 1 + 0.05 * z
        elif name.endswith("early_exit_gate.weight"):
            v = 0.3 * z
        elif name.endswith("early_exit_gate.bias"):
            v = 0.5 * z
        else:
            v = 0.05 * z
        p._data = jnp.asarray(v, jnp.float32)
    return model


def _reference(model, ids):
    """(logits [S, V], exit pass [S]) of the plain reference on one
    sequence, from the model's own leaves."""
    c = model.config
    sd = {n: p._data for n, p in model.named_parameters()}
    top = {k: v for k, v in sd.items() if ".layers." not in k}
    layers = [{k.split(f"model.layers.{i}.")[1]: v for k, v in sd.items()
               if f".layers.{i}." in k} for i in range(c.num_hidden_layers)]
    cfg = {"num_attention_heads": c.num_attention_heads,
           "num_key_value_heads": c.num_key_value_heads,
           "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta,
           "total_ut_steps": c.total_ut_steps,
           "early_exit_threshold": c.early_exit_threshold}
    logits, passes = ref.forward(top, layers, jnp.asarray(ids, jnp.int32), cfg)
    return np.asarray(logits), np.asarray(passes)


def _ids(seed, shape):
    return np.random.default_rng(seed).integers(0, VOCAB, shape)


# -- (a) the training forward --------------------------------------------------
@pytest.mark.parametrize("threshold", THRESHOLDS)
def test_forward_and_loss_match_the_reference(threshold):
    model = _model(threshold)
    ids = _ids(1, (2, 24))
    logits = np.asarray(model(paddle.to_tensor(ids))._data)
    want, passes = zip(*(_reference(model, row) for row in ids))
    np.testing.assert_allclose(logits, np.stack(want), atol=2e-5)
    passes = np.stack(passes)
    if threshold < 1:
        assert (passes < PASSES).any() and (passes == PASSES).any()
    else:
        assert (passes == PASSES).all()
    # shifted cross entropy of the reference's logits
    lp = jax.nn.log_softmax(jnp.asarray(np.stack(want))[:, :-1], -1)
    nll = -np.take_along_axis(np.asarray(lp), ids[:, 1:, None], -1).mean()
    for chunk in (None, 8):
        loss = model.forward_loss(paddle.to_tensor(ids), paddle.to_tensor(ids),
                                  loss_chunk_size=chunk)
        assert float(loss) == pytest.approx(float(nll), rel=1e-5)


def test_parameters_carry_the_published_names():
    names = {n for n, _ in _model(1.0).named_parameters()}
    layer = {f"model.layers.1.{n}.weight" for n in (
        "self_attn.q_proj", "self_attn.k_proj", "self_attn.v_proj",
        "self_attn.o_proj", "mlp.gate_proj", "mlp.up_proj", "mlp.down_proj",
        "input_layernorm", "input_layernorm_2", "post_attention_layernorm",
        "post_attention_layernorm_2")}
    top = {"model.embed_tokens.weight", "model.norm.weight",
           "model.early_exit_gate.weight", "model.early_exit_gate.bias",
           "lm_head.weight"}
    assert layer | top <= names and len(names) == 11 * LAYERS + 5


def test_trainer_takes_a_step_through_the_pass_loop():
    from paddle_tpu.parallel import SpmdTrainer
    paddle.seed(2)
    cfg = OuroConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=2,
                          passes=2, seq=32)
    cfg.use_flash_attention = False
    model = OuroForCausalLM(cfg)
    tr = SpmdTrainer(model, opt.AdamW(learning_rate=1e-2,
                                      parameters=model.parameters()),
                     lambda m, ids, y: m.forward_loss(ids, y))
    ids = jnp.asarray(_ids(3, (2, 16)) % 64, jnp.int32)
    losses = [float(tr.train_step(ids, ids)) for _ in range(4)]
    tr.block()
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# -- (b) generate(): prefill, then decode through the dense cache -------------
@pytest.mark.parametrize("threshold", THRESHOLDS)
def test_dense_cache_logits_match_the_reference(threshold):
    model = _model(threshold)
    dec = G._decoder_for(model)
    assert type(dec) is G._OuroDecoder
    w = dec.weights(model)
    ids = jnp.asarray(_ids(4, (2, 20)), jnp.int32)
    new = 6
    kcs, vcs, key_mask, logits = G._prefill(dec, w, ids, jnp.ones_like(ids),
                                            new)
    assert kcs.shape[0] == vcs.shape[0] == PASSES * LAYERS
    fed = [np.asarray(ids)]
    got = [np.asarray(logits)]
    for t in range(new - 1):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        fed.append(np.asarray(tok)[:, None])
        at = ids.shape[1] + t
        key_mask = key_mask.at[:, at].set(True)
        step, kcs, vcs = dec.step(w, tok[:, None],
                                  jnp.full((2, 1), at, jnp.int32), kcs, vcs,
                                  at, key_mask[:, None, None, :])
        logits = step[:, 0]
        got.append(np.asarray(logits))
    got = np.stack(got, 1)                               # [B, new, V]
    fed = np.concatenate(fed, 1)
    for b in range(2):
        want, _ = _reference(model, fed[b])
        np.testing.assert_allclose(got[b], want[ids.shape[1] - 1:], atol=2e-5)
    # the public entry point on the same path gives the same tokens
    toks, _ = model.generate(paddle.to_tensor(np.asarray(ids)),
                             max_new_tokens=new)
    assert (np.asarray(toks._data)[:, :-1] == fed[:, ids.shape[1]:]).all()


# -- (c) the serving engine: chunks and decode through the paged pools --------
_record = engine_record.record


def _check(model, steps, reqs):
    """Each sampled row's logits and exit pass against the reference's full
    forward over its request's prompt and served tokens. Returns the rows
    checked and the exit passes seen."""
    want = {id(r): _reference(model, list(r.prompt) + list(r.output))
            for r in reqs}
    rows, seen = 0, set()
    for logits, exits, points in steps:
        for req, pos, row in points:
            ref_logits, ref_pass = want[id(req)]
            np.testing.assert_allclose(logits[row], ref_logits[pos],
                                       atol=3e-5)
            assert exits[row] == ref_pass[pos]
            seen.add(int(exits[row]))
            rows += 1
    return rows, seen


def _engine(model, **kw):
    cfg = dict(max_seqs=4, token_budget=16, block_size=8, num_blocks=48,
               max_model_len=96)
    cfg.update(kw)
    return ServingEngine(model, EngineConfig(**cfg))


@pytest.mark.parametrize("threshold", THRESHOLDS)
def test_engine_logits_match_the_reference(threshold):
    model = _model(threshold)
    eng = _engine(model)
    # the pools' first axis counts cache entries: passes x layers
    assert eng._kp.shape == eng._vp.shape == (PASSES * LAYERS, 48, 4, 8, 16)
    tel = eng.telemetry()
    assert tel["model"] == {"weight_layers": LAYERS,
                            "cache_entries": PASSES * LAYERS,
                            "cached_token_bytes":
                                2 * PASSES * LAYERS * 4 * 16 * 4}
    assert tel["pool"]["page_bytes"] == 2 * PASSES * LAYERS * 4 * 8 * 16 * 4
    steps = _record(eng)
    # 37 and 21 tokens against a budget of 16: chunks share steps with decode
    reqs = [eng.submit(_ids(s, n).tolist(), max_new_tokens=m)
            for s, n, m in ((5, 37, 9), (6, 5, 12), (7, 21, 7))]
    eng.run_until_idle(max_steps=200)
    assert all(r.done and r.error is None for r in reqs)
    rows, seen = _check(model, steps, reqs)
    assert rows == 9 + 12 + 7
    if threshold < 1:
        assert min(seen) < PASSES, "no row left before the last pass"
    else:
        assert seen == {PASSES}
    assert eng.pool.used_blocks() == 0


@pytest.mark.parametrize("make", [
    lambda: LlamaForCausalLM(LlamaConfig.tiny(vocab_size=61, hidden_size=32,
                                              layers=3, heads=4, kv_heads=2,
                                              seq=64)),
    lambda: GPTForCausalLM(GPTConfig.tiny(vocab_size=61, hidden_size=32,
                                          layers=3, heads=4, seq=64)),
], ids=["llama", "gpt"])
def test_one_pass_decoders_state_the_same_number_twice(make):
    paddle.seed(1)
    dec = G._decoder_for(make())
    assert dec.n_layers == dec.cache_entries == 3


def test_decoder_is_picked_by_the_models_class():
    dec = G._decoder_for(_model(1.0))
    assert type(dec) is G._OuroDecoder
    assert (dec.n_layers, dec.cache_entries) == (LAYERS, PASSES * LAYERS)
    assert dec != G._decoder_for(_model(0.6))       # the threshold is traced


def test_engine_on_the_paged_kernel_matches_the_reference(monkeypatch):
    """The kernel, interpreted, reads its entry out of the joined pools."""
    from paddle_tpu.kernels import ragged_pallas
    monkeypatch.setattr(ragged_pallas, "_INTERPRET", True)
    model = _model(0.6)
    eng = _engine(model, max_seqs=2, num_blocks=16)
    assert eng.telemetry()["attention"] == "paged_kernel"
    steps = _record(eng)
    reqs = [eng.submit(_ids(s, n).tolist(), max_new_tokens=3)
            for s, n in ((8, 19), (9, 6))]
    eng.run_until_idle(max_steps=50)
    rows, _ = _check(model, steps, reqs)
    assert rows == 6



def test_the_page_kernel_writes_what_xla_does_through_the_pass_loop(
        monkeypatch):
    """The page write inside the pass ``scan``, interpreted: the same
    tokens and, bit for bit, the same 2 x 4 entries of pages as XLA's
    write leaves."""
    model = _model(0.6)
    prompts = [_ids(s, n).tolist() for s, n in ((8, 19), (9, 6))]
    runs = engine_record.write_paths(
        monkeypatch, lambda: _engine(model, max_seqs=2, num_blocks=16),
        prompts, 3)
    (path, tokens, pools), (x_path, x_tokens, x_pools) = \
        runs["page_kernel"], runs["xla"]
    assert (path, x_path) == ("page_kernel", "xla")
    assert tokens == x_tokens
    for got, want in zip(pools, x_pools):
        engine_record.assert_same_bits(got, want)


# -- (d) pages move whole: preemption, copy on write, handoff -----------------
def test_preempted_and_resumed_requests_keep_their_logits():
    model = _model(0.6)
    eng = _engine(model, max_seqs=3, num_blocks=9, enable_prefix_cache=False)
    steps = _record(eng)
    reqs = [eng.submit(_ids(s, n).tolist(), max_new_tokens=14)
            for s, n in ((10, 17), (11, 19), (12, 18))]
    eng.run_until_idle(max_steps=500)
    assert all(r.done and r.error is None for r in reqs)
    assert sum(r.preemptions for r in reqs) > 0, "nothing was preempted"
    rows, _ = _check(model, steps, reqs)
    assert rows >= 3 * 14
    assert eng.pool.used_blocks() == 0


def test_a_page_copied_on_write_is_copied_through_every_entry():
    model = _model(0.6)
    eng = _engine(model, enable_prefix_cache=False)
    steps = _record(eng)
    req = eng.submit(_ids(13, 11).tolist(), max_new_tokens=10)
    for _ in range(4):
        eng.step()
    assert not req.done and req.pos % 8
    # another holder appears on the boundary page; the engine's rollback
    # path then gives the sequence a private copy (``_emit_sampled``)
    old = req.pages[-1]
    eng.pool._ref[old] += 1
    kept, released, cow = eng.pool.truncate(req.pages, req.pos)
    assert released == 0 and cow == (old, kept[-1]) and cow[1] != old
    req.pages = kept
    eng._kp, eng._vp = engine_mod._copy_page(eng._kp, eng._vp, *cow)
    for pool in (eng._kp, eng._vp):
        assert pool.shape[0] == PASSES * LAYERS
        np.testing.assert_array_equal(np.asarray(pool[:, cow[1]]),
                                      np.asarray(pool[:, old]))
        assert np.abs(np.asarray(pool[:, old])).sum(axis=(1, 2, 3)).all()
    # the other holder scribbles on its page: the sequence reads its own
    eng._kp = eng._kp.at[:, old].set(7.0)
    eng._vp = eng._vp.at[:, old].set(7.0)
    eng.pool.release([old])
    eng.run_until_idle(max_steps=50)
    assert req.done and req.error is None
    rows, _ = _check(model, steps, [req])
    assert rows == 10


def test_a_handed_off_page_carries_every_entry():
    model = _model(1.0)
    eng = _engine(model)
    eng.generate_batch([_ids(14, 13).tolist()], max_new_tokens=2)
    k_page, v_page = engine_mod._read_page(eng._kp, eng._vp, 0)
    assert k_page.shape == (PASSES * LAYERS, 4, 8, 16)
    other = _engine(model)
    other._kp, other._vp = engine_mod._install_page(
        other._kp, other._vp, k_page, v_page, 5)
    np.testing.assert_array_equal(np.asarray(other._kp[:, 5]),
                                  np.asarray(eng._kp[:, 0]))
    np.testing.assert_array_equal(np.asarray(other._vp[:, 5]),
                                  np.asarray(eng._vp[:, 0]))


# -- (f) the spans' counts -----------------------------------------------------
def _host_spans(trace_dir, prefix):
    """The host plane's complete events whose name starts with ``prefix``."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.trace.json.gz")
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    host = {e["pid"] for e in events if e.get("ph") == "M"
            and e["name"] == "process_name"
            and e["args"]["name"].startswith("/host:")}
    return sorted((e for e in events if e.get("ph") == "X"
                   and e["pid"] in host and e["name"].startswith(prefix)),
                  key=lambda e: e["ts"])


def test_spans_carry_layer_visits_and_exit_counts(tmp_path):
    model = _model(0.6)
    eng = _engine(model)
    eng.generate_batch([_ids(15, 9).tolist()], max_new_tokens=2)   # compiled
    steps = _record(eng)
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = [eng.submit(_ids(s, n).tolist(), max_new_tokens=5)
                for s, n in ((16, 23), (17, 7))]
        while eng.step():
            pass
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path, "serve.")
    runs = [s for s in spans if s["name"] == "serve.run"]
    assert runs and {s["args"]["layer_visits"] for s in runs} == {
        str(PASSES * LAYERS)}
    emits = [s for s in spans if s["name"] == "serve.emit"]
    assert len(emits) == len(runs) == len(steps)
    for span, (_, exits, points) in zip(emits, steps):
        if not points:                       # a chunk that sampled nothing
            assert not span.get("args")
            continue
        assert int(span["args"]["exit_rows"]) == len(points)
        assert int(span["args"]["exit_pass_sum"]) == sum(
            int(exits[row]) for _, _, row in points)
    total = sum(int(s["args"]["exit_pass_sum"]) for s in emits
                if s.get("args"))
    rows = sum(int(s["args"]["exit_rows"]) for s in emits if s.get("args"))
    assert rows == 10 and rows <= total <= PASSES * rows
    assert all(r.done for r in reqs)


# -- (g) the step program does not grow with the number of passes -------------
def _step_equations(passes, layers=48):
    cfg = OuroConfig.tiny(vocab_size=VOCAB, hidden_size=64, layers=layers,
                          heads=4, passes=passes, seq=128)
    cfg.use_flash_attention = False
    model = OuroForCausalLM(cfg)
    dec = G._decoder_for(model)
    w = dec.weights(model)
    t, slots, pages = 16, 4, 8
    i32 = jnp.zeros((t,), jnp.int32)
    pool = jnp.zeros((dec.cache_entries, pages, 4, 8, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda *a: engine_mod._engine_step_impl(
            dec, None, engine_mod._argmax_rows, None, *a))(
        w, i32, jnp.zeros((2 * t,), jnp.int32), i32, i32, i32,
        jnp.zeros((t,), bool), jnp.zeros((slots, 12), jnp.int32), pool, pool)

    def count(j):
        n = 0
        for eqn in j.eqns:
            n += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += count(sub)
        return n

    return count(jaxpr.jaxpr), dec.cache_entries


def test_the_step_program_does_not_grow_with_the_passes():
    two, entries2 = _step_equations(2)
    four, entries4 = _step_equations(4)
    assert (entries2, entries4) == (96, 192)
    # one traced body of 48 layers whatever the passes: the exit selection's
    # few operations on [passes, ...] arrays are all that may differ
    assert abs(four - two) <= 8, (two, four)
    shallow, _ = _step_equations(4, layers=24)
    assert four > 1.8 * shallow                 # it does grow with the layers


def test_tensor_parallel_engine_serves_the_same_tokens():
    """``EngineConfig(mesh=2)``: the column/row split of ``tp_specs()`` and
    per-KV-head pools through the pass loop (the mesh keeps the gather-based
    reference attention); not measured on the chip."""
    model = _model(0.6)
    prompts = [_ids(s, n).tolist() for s, n in ((18, 19), (19, 7))]
    want = _engine(model).generate_batch(prompts, max_new_tokens=6)
    eng = _engine(model, mesh=2)
    assert eng.telemetry()["mesh"]["mp"] == 2
    assert eng._kp.sharding.spec[2] == "mp"
    assert eng._w["model.layers.0.mlp.down_proj.weight"].sharding.spec[0] \
        == "mp"
    assert eng.generate_batch(prompts, max_new_tokens=6) == want
