"""Nemotron-H on the CPU at a small size, float32, seeded weights: the
model's ``forward`` and the serving engine (prefill in chunks that cut a
prompt in two or more steps, then decode, sequences joining and leaving, a
slot reused) against the plain reference's full forward; the share test (the
routed parts of all the shares, the shared expert once, add up to the uncut
expert layer); routed dispatch against the dense every-expert form; the
faults the comparison has to see; what an engine refuses for a decoder that
keeps a state beside its pages, and that preemption and the step-fault
requeue recompute such a request to the same tokens."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.generation import _decoder_for
from paddle_tpu.kernels import grouped_experts_pallas as ge
from paddle_tpu.kernels import ssm_pallas as ssm
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.serving import EngineConfig, ServingEngine

import engine_record

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench.archs import nemotron_h as arch                 # noqa: E402
from bench.reference import nemotron_h_block as ref        # noqa: E402
from bench.tools.nemotron_faults import FAULTS, faulty     # noqa: E402

VOCAB, PATTERN = 256, "MEM*EM"


def _model(seed=0, **kw):
    """A tiny model of every kind of layer holding experts 4..11 of 16, the
    leaves a fresh model has at nought or one drawn away from them."""
    cfg = dataclasses.replace(
        nh.NemotronHConfig.tiny(vocab_size=VOCAB, layers=len(PATTERN),
                                pattern=PATTERN, experts_held=8,
                                first_expert=4), **kw)
    paddle.seed(seed)
    model = nh.NemotronHForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith(("A_log", "dt_bias", "conv1d.bias")):
            p._data = jnp.asarray(rng.normal(0, 0.5, p.shape), jnp.float32)
        elif name.endswith("gate.weight"):
            p._data = jnp.asarray(rng.normal(0, 0.3, p.shape), jnp.float32)
        elif name.endswith("e_score_correction_bias"):
            p._data = jnp.asarray(1 + rng.normal(0, 0.05, p.shape),
                                  jnp.float32)
        elif name.endswith(("norm.weight", "norm_f.weight", ".D")):
            p._data = jnp.asarray(1 + rng.normal(0, 0.05, p.shape),
                                  jnp.float32)
        elif name.endswith("conv1d.weight"):
            p._data = jnp.asarray(rng.normal(0, 0.3, p.shape), jnp.float32)
        else:
            p._data = jnp.asarray(rng.normal(0, 0.1, p.shape), jnp.float32)
    return model


def _ref_cfg(cfg, **kw):
    """The configuration as the reference reads it (the file's keys)."""
    out = {k: getattr(cfg, k) for k in (
        "hybrid_override_pattern", "num_hidden_layers", "layer_norm_epsilon",
        "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
        "conv_kernel", "num_attention_heads", "num_key_value_heads",
        "head_dim", "num_experts_per_tok", "routed_scaling_factor",
        "first_expert")}
    out["n_routed_experts"] = cfg.experts_held
    out["published"] = {"n_routed_experts": cfg.n_routed_experts}
    out.update(kw)
    return out


def _leaves(model):
    """(top, [layer leaves by their names inside the layer])."""
    state = {n: p._data for n, p in model.named_parameters()}
    top = {n: a for n, a in state.items() if ".layers." not in n}
    layers = []
    for i in range(model.config.num_hidden_layers):
        pre = arch.layer_prefix(i)
        layers.append({n[len(pre):]: a for n, a in state.items()
                       if n.startswith(pre)})
    return top, layers


def _reference(model, ids, **kw):
    """The reference's logits [S, vocab] for one sequence of ids, by the
    adapter's walk."""
    cfg = _ref_cfg(model.config, **kw)
    top, layers = _leaves(model)
    x = ref.embed(top, jnp.asarray(ids, jnp.int32), cfg)
    for name, i in arch.walk(cfg):
        x = getattr(ref, name)(top, layers[i], x, cfg)
    return np.asarray(ref.head(top, x, cfg))


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n)


def _engine(model, **kw):
    cfg = dict(max_seqs=3, token_budget=16, block_size=8, num_blocks=48,
               max_model_len=96)
    cfg.update(kw)
    return ServingEngine(model, EngineConfig(**cfg))


_record = engine_record.record


# -- (a) forward and the engine against the reference ---------------------------
def test_forward_matches_the_reference():
    model = _model()
    assert arch.walk(_ref_cfg(model.config)) == [
        ("mamba", 0), ("moe", 1), ("mamba", 2), ("attention", 3), ("moe", 4),
        ("mamba", 5)]
    ids = np.stack([_ids(1, 33), _ids(2, 33)])
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    for b in range(2):
        np.testing.assert_allclose(got[b], _reference(model, ids[b]),
                                   atol=3e-5)


def test_the_routing_weights_are_the_unbiased_scores_normalised():
    model = _model()
    cfg = model.config
    w = {n: p._data for n, p in model.named_parameters()}
    pre = "backbone.layers.1.mixer."
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (64, 32)),
                    jnp.float32)
    chosen, weight = nh.route(x, w[pre + "gate.weight"],
                              w[pre + "gate.e_score_correction_bias"], cfg)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 5.0, rtol=1e-5)
    lw = {"mixer.gate.weight": w[pre + "gate.weight"],
          "mixer.gate.e_score_correction_bias":
              w[pre + "gate.e_score_correction_bias"]}
    want_c, want_w = ref.route(lw, x, _ref_cfg(cfg))
    assert (np.sort(np.asarray(chosen)) == np.sort(np.asarray(want_c))).all()
    np.testing.assert_allclose(np.sort(np.asarray(weight)),
                               np.sort(np.asarray(want_w)), atol=1e-6)
    # a bias used in the weights would be seen here, whatever its spread
    with faulty("bias_in_weight", _ref_cfg(cfg)) as wrong:
        _, biased = ref.route(lw, x, wrong)
    assert np.abs(np.sort(np.asarray(biased))
                  - np.sort(np.asarray(weight))).max() > 1e-3


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernels"])
def test_engine_logits_match_the_reference(kernel, monkeypatch):
    """Five requests through three slots: prompts cut in two or more steps
    (37 and 21 tokens at 16 rows a step), sharing steps with decode rows,
    sequences joining and leaving, slots reused; every sampled row's logits
    against the reference's full forward. With the state update, the
    grouped product and the paged attention interpreted, as on the chip."""
    if kernel:
        from paddle_tpu.kernels import ragged_pallas
        for mod in (ssm, ge, ragged_pallas):
            monkeypatch.setattr(mod, "_INTERPRET", True)
    model = _model()
    eng = _engine(model)
    dec = eng.dec
    assert dec.state_shapes == (((2, 16, 32), "float32"), ((3 * 128,), None))
    assert (dec.cache_entries, dec.state_layers) == (1, 3)
    assert eng._kp.shape == eng._vp.shape == (1, 48, 1, 8, 8)
    (states, tails), = eng._state
    assert states.shape == (3, 3, 2, 16, 32) and states.dtype == jnp.float32
    assert tails.shape == (3, 3, 3 * 128)
    tel = eng.telemetry()
    per_seq = 3 * (2 * 16 * 32 * 4 + 3 * 128 * 4)
    assert tel["model"]["state_bytes_a_sequence"] == per_seq
    assert tel["model"]["state_layers"] == 3
    assert tel["model"]["prefix_reuse"].startswith("off")
    assert tel["pool"]["page_bytes"] == 2 * 8 * 8 * 4    # pages stay pages
    assert not eng.pool.enable_prefix_cache
    steps = _record(eng)
    plan = ((5, 37, 9), (6, 5, 12), (7, 21, 7), (8, 3, 5), (9, 11, 6))
    reqs = [eng.submit(_ids(s, n).tolist(), max_new_tokens=m)
            for s, n, m in plan]
    slots = set()
    while eng.step():
        slots |= {(r.slot, id(r)) for r in eng.sched.running}
    assert all(r.done and r.error is None for r in reqs)
    assert max(sum(1 for s, _ in slots if s == k) for k in range(3)) >= 2
    want = {id(r): _reference(model, list(r.prompt) + list(r.output))
            for r in reqs}
    rows = 0
    for logits, counters, points in steps:
        pairs, held, zero, peak, touched = (int(c) for c in counters)
        assert 0 <= held <= pairs and zero == 0 and peak <= held
        assert touched <= 8 * 2
        for req, pos, row_i in points:
            np.testing.assert_allclose(logits[row_i], want[id(req)][pos],
                                       atol=3e-5)
            rows += 1
    assert rows == sum(m for _, _, m in plan)
    tot = np.sum([c for _, c, _ in steps], axis=0)
    assert 0.25 < tot[1] / tot[0] < 0.75        # half the experts are here


def test_the_state_counts_ride_on_the_run_span(tmp_path):
    import glob
    import gzip
    import json
    model = _model()
    eng = _engine(model)
    eng.generate_batch([_ids(15, 9).tolist()], max_new_tokens=2)   # compiled
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit(_ids(16, 23).tolist(), max_new_tokens=3)
        while eng.step():
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                     "*.trace.json.gz"))
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    runs = sorted((e for e in events if e.get("name") == "serve.run"
                   and e.get("ph") == "X"), key=lambda e: e["ts"])
    got = [tuple(int(e["args"][k]) for k in (
        "state_slots", "state_slots_max", "state_rows_prefill",
        "state_resets")) for e in runs]
    # 23 tokens at 16 rows a step: two prefill steps, then two decode steps
    assert got == [(1, 3, 16, 1), (1, 3, 7, 0), (1, 3, 0, 0), (1, 3, 0, 0)]
    emits = [e for e in events if e.get("name") == "serve.emit"
             and e.get("ph") == "X" and "moe_pairs" in e.get("args", {})]
    assert len(emits) == 3 and all(
        set(eng.dec.COUNTERS) <= set(e["args"]) for e in emits)


def test_a_decoder_without_a_state_adds_nothing_to_a_span_or_a_step():
    from paddle_tpu.models import llama
    paddle.seed(0)
    model = llama.LlamaForCausalLM(llama.LlamaConfig.tiny())
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=8,
                                            block_size=4, num_blocks=16))
    assert eng._state == [] and eng._state_counts(None) == {}
    assert eng.pool.enable_prefix_cache
    assert [n for n, _ in eng._watched_pools()] == ["params", "kv_pages"]
    assert "state_bytes" not in eng.telemetry()["model"]



def test_the_page_kernel_writes_the_attention_layers_pages_xla_does(
        monkeypatch):
    """The one attention layer's pages beside the recurrent state, through
    the page kernel (interpreted): the same tokens and pages, bit for bit,
    as XLA's write."""
    model = _model()
    prompts = [_ids(s, n).tolist() for s, n in ((5, 37), (6, 5), (7, 21))]
    runs = engine_record.write_paths(monkeypatch, lambda: _engine(model),
                                     prompts, 5)
    (path, tokens, pools), (x_path, x_tokens, x_pools) = \
        runs["page_kernel"], runs["xla"]
    assert (path, x_path) == ("page_kernel", "xla")
    assert tokens == x_tokens
    for got, want in zip(pools, x_pools):
        engine_record.assert_same_bits(got, want)


# -- (b) the share test ------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_expert_layer():
    """The routed parts the four shares of 4 experts give, each projected
    up, plus the shared expert ONCE, are the uncut layer's output."""
    whole = _model(experts_held=16, first_expert=0)
    cfg = whole.config
    w = {n[len("backbone.layers.1.mixer."):]: p._data
         for n, p in whole.named_parameters()
         if n.startswith("backbone.layers.1.mixer.")}
    h = jnp.asarray(np.random.default_rng(5).normal(0, 1, (40, 32)),
                    jnp.float32)
    uncut = np.asarray(nh.latent_moe(h, w, cfg))
    shared = np.asarray(nh.relu2_ffn(h, w["shared_experts.up_proj.weight"],
                                     w["shared_experts.down_proj.weight"]))
    total = shared.copy()
    for first in (0, 4, 8, 12):
        part = dataclasses.replace(cfg, experts_held=4, first_expert=first)
        mine = dict(w, **{k: w[k][first:first + 4]
                          for k in ("experts.up_proj", "experts.down_proj")})
        total += np.asarray(nh.latent_moe(h, mine, part)) - shared
        # and the reference's share is the program's
        lw = {"mixer." + k: v for k, v in mine.items()}
        lw["norm.weight"] = jnp.ones(32, jnp.float32)
        rc = _ref_cfg(part)
        chosen, weight = ref.route(lw, h, rc)
        np.testing.assert_allclose(
            np.asarray(ref.routed_part(lw, h, chosen, weight, rc))
            + shared, np.asarray(nh.latent_moe(h, mine, part)), atol=3e-5)
    np.testing.assert_allclose(total, uncut, atol=3e-5)
    assert np.abs(uncut - shared).max() > 0.1        # the routed part weighs


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_routed_dispatch_equals_the_dense_form_when_routing_is_uneven(
        kernel, monkeypatch):
    if kernel:
        monkeypatch.setattr(ge, "_INTERPRET", True)
    model = _model(moe_latent_size=128, moe_intermediate_size=384)
    cfg = model.config
    dec = _decoder_for(model)
    w = dict(dec.weights(model))
    pre = "backbone.layers.1.mixer."
    bias = np.ones(16, np.float32)
    bias[5], bias[6] = 3.0, -3.0          # expert 5 by every token, 6 by none
    w[pre + "gate.e_score_correction_bias"] = jnp.asarray(bias)
    t = 72
    h = jnp.asarray(np.random.default_rng(13).normal(0, 1, (t, 32)),
                    jnp.float32)
    valid = jnp.asarray(np.arange(t) % 9 != 8)
    y, counters = dec._moe(w, 1, h, valid, True)
    pairs, held, zero, peak, touched = (int(c) for c in counters)
    n_valid = int(valid.sum())
    assert pairs == 4 * n_valid and peak == n_valid and zero == 0
    assert touched < 8                               # expert 6 untouched
    want = nh.latent_moe(h, dec._part(w, 1), cfg)
    np.testing.assert_allclose(np.asarray(y)[np.asarray(valid)],
                               np.asarray(want)[np.asarray(valid)], atol=3e-5)


# -- (c) faults ---------------------------------------------------------------------
@pytest.mark.parametrize("fault", FAULTS)
def test_a_part_left_out_or_wrong_moves_the_logits(fault):
    model = _model()
    ids = _ids(21, 40)
    right = _reference(model, ids)
    with faulty(fault, _ref_cfg(model.config)) as wrong:
        got = _reference(model, ids, **{k: wrong[k] for k in wrong
                                        if k not in ("published",)})
    assert np.abs(got - right).max() > 30 * 3e-5


def _served_logits(eng, prompt, new):
    steps = _record(eng)
    req = eng.submit(prompt, max_new_tokens=new)
    eng.run_until_idle(max_steps=100)
    assert req.done and req.error is None
    rows = [logits[row] for logits, _, points in steps
            for r, _, row in points if r is req]
    return req, np.stack(rows)


@pytest.mark.parametrize("fault", ["dropped", "another_slots"])
def test_a_state_not_carried_or_from_another_slot_moves_the_logits(fault):
    """The engine itself with its state pool tampered between steps: zeroed
    after every step (a state not carried), or rolled by one slot (a state
    read from another slot)."""
    model = _model()
    prompt = _ids(31, 21).tolist()
    _, right = _served_logits(_engine(model), prompt, 6)
    eng = _engine(model)
    other = eng.submit(_ids(32, 9).tolist(), max_new_tokens=40)   # a neighbour
    eng.step()
    steps = _record(eng)
    call = eng._step_call

    def tampered(*args):
        out = call(*args)
        (states, tails), = eng._state
        eng._state = [(jnp.zeros_like(states) if fault == "dropped"
                       else jnp.roll(states, 1, axis=1), tails)]
        return out

    eng._step_call = tampered
    req = eng.submit(prompt, max_new_tokens=6)
    for _ in range(12):
        eng.step()
    rows = [logits[row] for logits, _, points in steps
            for r, _, row in points if r is req]
    assert rows and not other.done
    gap = max(np.abs(a - b).max() for a, b in zip(rows, right))
    assert gap > 30 * 3e-5


# -- (d) what the engine refuses, and what it recomputes -----------------------------
@pytest.mark.parametrize("kw,what", [
    (dict(spec_method="ngram"), "spec_method"),
    (dict(role="prefill"), "a prefill or decode role"),
    (dict(role="decode"), "a prefill or decode role"),
    (dict(mesh=2), "a mesh"),
    (dict(quant="weight_only_int8"), "quant"),
])
def test_what_moves_pages_only_is_refused_in_words(kw, what):
    with pytest.raises((ValueError, NotImplementedError)) as e:
        _engine(_model(num_key_value_heads=2), **kw)
    if what != "quant":                   # refused by the decoder's plan
        assert what in str(e.value) and "recurrent state" in str(e.value)


def test_a_role_flip_a_hand_off_and_generate_are_refused():
    model = _model()
    eng = _engine(model)
    with pytest.raises(ValueError, match="recurrent state"):
        eng.set_role("decode")
    with pytest.raises(RuntimeError, match="a page hand-off"):
        eng.import_handoff(None, None)
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        model.generate(paddle.to_tensor(np.array([_ids(1, 5)])),
                       max_new_tokens=2)


def test_preemption_recomputes_a_stateful_request_to_the_same_tokens():
    """A pool too small for both: the younger request is preempted, starts
    again from position 0 in whatever slot is free (no prefix is kept) and
    ends on the tokens it has alone."""
    model = _model()
    prompts = [_ids(41, 30).tolist(), _ids(42, 28).tolist()]
    alone = [_served_logits(_engine(model), p, 14)[0].output for p in prompts]
    eng = _engine(model, num_blocks=9, max_model_len=48)
    reqs = [eng.submit(p, max_new_tokens=14) for p in prompts]
    eng.run_until_idle(max_steps=400)
    assert sum(r.preemptions for r in reqs) >= 1
    assert [r.output for r in reqs] == alone
    assert eng.pool.stats["prefix_hits"] == 0


def test_a_step_fault_requeues_a_stateful_request_to_the_same_tokens():
    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import resilience as res
    model = _model()
    prompt = _ids(51, 19).tolist()
    alone = _served_logits(_engine(model), prompt, 8)[0].output
    eng = _engine(model, resilience=res.ResilienceConfig(max_step_retries=3))
    req = eng.submit(prompt, max_new_tokens=8)
    eng.step()
    eng.step()
    (states, tails), = eng._state
    states.delete()                       # as a fault after the launch leaves them
    chaos.install_plan(chaos.FaultPlan(seed=0).add(
        "serve.engine_step", "error", at=(1,)))
    try:
        eng.step()
    finally:
        chaos.clear_plan()
    assert eng.step_faults == 1 and not eng._state[0][0].is_deleted()
    eng.run_until_idle(max_steps=100)
    assert req.done and req.error is None and req.output == alone
