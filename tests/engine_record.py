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
