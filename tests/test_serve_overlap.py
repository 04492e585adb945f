"""The engine launches step n+1 before it reads step n's tokens back
(``ServingEngine._step``): a decode row whose token is step n's sample takes
it on the device (``feed``), the program samples every row itself, and the
host reads step n while the chip runs n+1.

Oracles: ``generate()``'s one-shot greedy tokens, and for the decoder that
keeps a recurrent state (no ``generate()``) the model's own full forward over
prompt and output, whose argmax at every position is the next output token.
The engagement counters (``telemetry()["overlap"]``) say which path ran: the
decode rows fed from the device, and the steps launched with one in flight.
"""
import dataclasses

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import EngineConfig, ServingEngine
from paddle_tpu.serving import engine as E
from paddle_tpu.serving.resilience import RequestFailed, ResilienceConfig

pytestmark = pytest.mark.serve

VOCAB = 61


def _llama(kv_heads=2):
    paddle.seed(3)
    cfg = LlamaConfig.tiny(vocab_size=VOCAB, hidden_size=32, layers=2,
                           heads=4, kv_heads=kv_heads, seq=64)
    cfg.use_flash_attention = False
    return LlamaForCausalLM(cfg)


def _gpt():
    paddle.seed(5)
    return GPTForCausalLM(GPTConfig.tiny(vocab_size=VOCAB, hidden_size=32,
                                         layers=2, heads=4, seq=64))


def _ouro():
    from paddle_tpu.models import OuroConfig, OuroForCausalLM
    paddle.seed(7)
    cfg = OuroConfig.tiny(vocab_size=VOCAB, hidden_size=32, layers=2,
                          heads=4, passes=2, seq=64, threshold=0.6)
    cfg.use_flash_attention = False
    return OuroForCausalLM(cfg)


def _longcat():
    from paddle_tpu.models import longcat_flash as lf
    paddle.seed(9)
    cfg = dataclasses.replace(lf.LongcatFlashConfig.tiny(
        vocab_size=VOCAB, layers=2, experts_held=4, first_expert=4))
    return lf.LongcatFlashForCausalLM(cfg)


def _nemotron():
    from paddle_tpu.models import nemotron_h as nh
    paddle.seed(11)
    cfg = nh.NemotronHConfig.tiny(vocab_size=VOCAB, layers=6,
                                  pattern="MEM*EM", experts_held=8,
                                  first_expert=4)
    return nh.NemotronHForCausalLM(cfg)


def _prompts(n, lens=(7, 4, 11, 5, 9, 3), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, lens[i % len(lens)]).tolist()
            for i in range(n)]


def _generate(model, prompts, new):
    out = []
    for p in prompts:
        toks, _ = model.generate(paddle.to_tensor(np.asarray([p], np.int32)),
                                 max_new_tokens=new)
        out.append(toks.numpy()[0].tolist()[-new:])
    return out


def _greedy_by_forward(model, prompt, output):
    """The argmax of the model's own forward over prompt + output at every
    position that predicts an output token."""
    ids = np.asarray([list(prompt) + list(output)], np.int32)
    logits = np.asarray(model(paddle.to_tensor(ids))._data)[0]
    return logits[len(prompt) - 1:-1].argmax(-1).tolist()


def _engine(model, **kw):
    cfg = dict(max_seqs=3, token_budget=16, block_size=4, num_blocks=64)
    cfg.update(kw)
    return ServingEngine(model, EngineConfig(**cfg))


def _program(eng):
    return E._engine_step_state if eng._state else E._engine_step


def _engaged(eng):
    """The decode rows were fed on the device, every one of them."""
    ov = eng.telemetry()["overlap"]
    assert ov["steps_ahead"] > 0 and ov["device_fed_rows"] > 0
    assert ov["device_fed_rows"] == ov["decode_rows"]


def _run(eng, prompts, new):
    """Serve ``prompts``; no program is compiled after the first launch
    (``prev`` of the first launch has the output's shape). On a mesh the
    second launch compiles once more, as it did before (PR 37's engine)."""
    reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
    for _ in range(1 if eng.mesh is None else 2):
        eng.step()
    compiled = _program(eng)._cache_size()
    eng.run_until_idle(max_steps=500)
    assert _program(eng)._cache_size() == compiled
    assert all(r.done and r.error is None for r in reqs)
    assert eng.pool.used_blocks() == 0
    return [r.output for r in reqs]


@pytest.mark.parametrize("build,mesh", [
    (_llama, None), (_gpt, None), (_ouro, None), (_longcat, None),
    (_llama, 2)], ids=["llama-gqa", "gpt", "ouro", "longcat", "llama-mp2"])
def test_one_step_ahead_matches_generate(build, mesh):
    model = build()
    prompts = _prompts(5)
    eng = _engine(model, mesh=mesh)
    assert _run(eng, prompts, 7) == _generate(model, prompts, 7)
    _engaged(eng)


def test_the_state_decoder_one_step_ahead_is_greedy():
    """Nemotron-H keeps a recurrent state a slot beside its pages (no
    ``generate()``): every token is the forward's argmax over what came
    before it, and slots are reused by later requests."""
    model = _nemotron()
    prompts = _prompts(5, lens=(9, 5, 12, 6, 4))
    eng = _engine(model, token_budget=16, block_size=8, num_blocks=48,
                  max_model_len=96)
    outs = _run(eng, prompts, 6)
    for p, o in zip(prompts, outs):
        assert len(o) == 6 and o == _greedy_by_forward(model, p, o)
    _engaged(eng)


def test_max_new_tokens_is_exact_and_no_row_is_planned_past_it():
    """A sequence whose last token is in flight is known finished by count:
    it is not planned again, so every decode row feeds a token that is
    output."""
    model = _llama()
    eng = _engine(model)
    news = (1, 2, 5, 3)
    reqs = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(_prompts(4), news)]
    eng.run_until_idle(max_steps=100)
    assert [len(r.output) for r in reqs] == list(news)
    assert eng.telemetry()["overlap"]["decode_rows"] == sum(
        n - 1 for n in news)
    assert eng.pool.used_blocks() == 0


def _late_eos_case(model, bs):
    """A prompt, its greedy output and an index k > 0 whose token is not
    earlier in the output, at a position that opens a page (the row after
    it grows one)."""
    for seed in range(40):
        prompt = _prompts(1, lens=(5 + seed % 7,), seed=seed)[0]
        want = _generate(model, [prompt], 10)[0]
        for k in range(1, 9):
            if want[k] not in want[:k] and (len(prompt) + k) % bs == 0:
                return prompt, want, k
    raise AssertionError("no case found")


def test_an_eos_read_one_step_late_drops_the_extra_row_and_its_pages():
    model = _llama()
    bs = 4
    prompt, want, k = _late_eos_case(model, bs)
    eng = _engine(model, block_size=bs, enable_prefix_cache=False)
    req = eng.submit(prompt, max_new_tokens=10, eos_id=want[k])
    held, schedule = [], eng.sched.schedule

    def planned():
        plan = schedule()
        held.append(len(req.pages))
        return plan

    eng.sched.schedule = planned
    while not req.done:
        eng.step()
    assert req.output == want[:k + 1] and req.finish_reason == "eos"
    # the row after the EOS was planned, with a page grown for it, and
    # dropped: the pool holds nothing once the EOS is read
    eos_pos = len(prompt) + k
    assert max(held) == eos_pos // bs + 1
    assert eng.telemetry()["overlap"]["decode_rows"] == k + 1
    assert eng.pool.used_blocks() == 0
    assert not eng.has_work() or not eng.step()
    assert eng.pool.used_blocks() == 0 and req.output == want[:k + 1]


def test_a_request_submitted_after_a_finish_is_served():
    model = _llama()
    eng = _engine(model)
    first, later = _prompts(2, lens=(6, 9))
    a = eng.submit(first, max_new_tokens=3)
    while not a.done:
        eng.step()
    b = eng.submit(later, max_new_tokens=5)     # a step may be in flight
    eng.run_until_idle(max_steps=50)
    assert [a.output, b.output] == _generate(model, [first], 3) + \
        _generate(model, [later], 5)
    assert eng.pool.used_blocks() == 0


@pytest.mark.parametrize("kw", [
    dict(spec_method="ngram", num_draft_tokens=3),
    dict(role="decode"),
    dict(resilience=ResilienceConfig(nan_guard=True)),
], ids=["speculation", "role", "nan_guard"])
def test_where_the_host_reads_first_every_step_is_read_at_once(kw):
    model = _llama()
    prompts = _prompts(4)
    eng = _engine(model, token_budget=24, **kw)
    assert _run(eng, prompts, 6) == _generate(model, prompts, 6)
    ov = eng.telemetry()["overlap"]
    assert ov["steps_ahead"] == ov["device_fed_rows"] == 0
    assert ov["decode_rows"] > 0


def test_resilience_without_the_nan_guard_runs_ahead():
    model = _llama()
    prompts = _prompts(3)
    eng = _engine(model, resilience=ResilienceConfig(nan_guard=False))
    assert _run(eng, prompts, 5) == _generate(model, prompts, 5)
    _engaged(eng)


def test_a_plan_that_would_preempt_a_token_in_flight_reads_it_first():
    model = _llama()
    prompts = _prompts(3, lens=(9, 11, 10))
    eng = _engine(model, num_blocks=9, enable_prefix_cache=False)
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle(max_steps=500)
    assert sum(r.preemptions for r in reqs) > 0
    assert [r.output for r in reqs] == _generate(model, prompts, 12)
    assert eng.pool.used_blocks() == 0


def _in_flight(eng, prompts, new):
    reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
    for _ in range(4):
        eng.step()
    assert eng._ahead is not None and any(r.unread for r in reqs)
    return reqs


def test_drain_with_a_step_in_flight_reads_it_and_finishes():
    model = _llama()
    prompts = _prompts(3)
    eng = _engine(model, resilience=ResilienceConfig(nan_guard=False))
    reqs = _in_flight(eng, prompts, 6)
    manifest = eng.drain()
    assert manifest["requests"] == [] and eng._ahead is None
    assert [r.output for r in reqs] == _generate(model, prompts, 6)
    assert not eng.has_work() and eng.pool.used_blocks() == 0


def test_a_drain_cut_by_its_deadline_reads_the_step_in_flight():
    model = _llama()
    eng = _engine(model)
    reqs = _in_flight(eng, _prompts(3), 8)
    manifest = eng.drain(deadline_s=0)
    assert eng._ahead is None and not any(r.unread for r in reqs)
    by_rid = {m["rid"]: m for m in manifest["requests"]}
    for r in reqs:
        if not r.done:
            assert by_rid[r.rid]["generated"] == r.output


def test_abort_all_with_a_step_in_flight_parks_nothing():
    model = _llama()
    eng = _engine(model)
    reqs = _in_flight(eng, _prompts(3), 8)
    out = [list(r.output) for r in reqs]
    assert eng.abort_all() == 3
    assert eng._ahead is None
    for r, before in zip(reqs, out):
        assert r.done and r.output == before
        with pytest.raises(RequestFailed):
            r.result(0)
    assert not eng.has_work() and not eng.step()
    assert eng.pool.used_blocks() == 0


def test_a_contained_fault_with_a_step_in_flight_keeps_the_tokens():
    """The chaos fault hits the launch of step n+1: step n, in flight, is
    read first, then every request recomputes; the output is greedy."""
    model = _llama()
    prompts = _prompts(3)
    eng = _engine(model, resilience=ResilienceConfig(nan_guard=False,
                                                     max_step_retries=3))
    reqs = _in_flight(eng, prompts, 7)
    chaos.install_plan(chaos.FaultPlan(seed=0).add(
        "serve.engine_step", "error", at=(1,)))
    try:
        eng.step()
    finally:
        chaos.clear_plan()
    assert eng.step_faults == 1 and eng._ahead is None
    eng.run_until_idle(max_steps=200)
    assert [r.output for r in reqs] == _generate(model, prompts, 7)
    assert eng.pool.used_blocks() == 0
