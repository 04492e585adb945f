"""What a serving engine's step program computed, step by step, for the tests
that hold its logits against a reference. The program hands back sampled
tokens only; the sampler it is given here (a static argument of the program)
keeps every step's logits on the host as well."""
import jax
import numpy as np

from paddle_tpu.serving import engine as E


def record(eng):
    """A list that gets, for every step ``eng`` launches from now on and
    reads back, in that order: [logits [T, V], what the decoder returned
    beside them, [(request, position, row) of each sampling row]]."""
    logits, launched, steps = [], [], []

    def sample(rows):
        jax.debug.callback(lambda a: logits.append(np.asarray(a)), rows)
        return E._argmax_rows(rows)

    eng._sample = sample
    call = eng._plain_step_call()

    def step_call(*args):
        got = call(*args)
        launched.append(got[0])
        return got

    emit = eng._emit_sampled
    t_max = eng.config.token_budget

    def emit_sampled(step, all_tok, armed, out, now):
        k = next((k for k, o in enumerate(launched) if o is step.out), None)
        if k is not None:
            beside = np.asarray(step.out)[t_max:]      # the step has run
            steps.append([logits[k], beside,
                          [(e.req, e.start + e.n - 1, i)
                           for e, i in step.sample_points]])
        return emit(step, all_tok, armed, out, now)

    eng._step_call, eng._emit_sampled = step_call, emit_sampled
    return steps


def write_paths(monkeypatch, engine, prompts, max_new_tokens):
    """Serve ``prompts`` on two fresh engines (``engine()`` makes one) with
    the serving kernels interpreted (``ragged_pallas._INTERPRET``): the
    first writes its K/V with the page kernel, the rule's choice there;
    the second with XLA's write, the rule steered here. Returns {path:
    (``telemetry()["kv_write"]["path"]``, the tokens, the K and V pools as
    numpy arrays)}."""
    from paddle_tpu.kernels import ragged_pallas
    from paddle_tpu.serving import ragged
    monkeypatch.setattr(ragged_pallas, "_INTERPRET", True)
    runs = {}
    for path in ("page_kernel", "xla"):
        if path == "xla":
            monkeypatch.setattr(ragged, "write_path", lambda *a: "xla")
        # each run traces and compiles its programs anew: a program cached
        # by an earlier test is not the one the other run compiles
        jax.clear_caches()
        eng = engine()
        tokens = eng.generate_batch(prompts, max_new_tokens=max_new_tokens)
        runs[path] = (eng.telemetry()["kv_write"]["path"], tokens,
                      [np.asarray(p) for p in (eng._kp, eng._vp)])
    return runs


def assert_same_bits(a, b):
    """Two arrays hold the same bits (NaN too)."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype
    if a.size:
        width = {2: np.uint16, 4: np.uint32}[a.dtype.itemsize]
        np.testing.assert_array_equal(a.view(width), b.view(width))
