"""Traffic kind ``train-batches``: a new seeded batch into
``SpmdTrainer.train_step`` every step, each step ended by ``block()``.

The traffic file gives ``batch`` (sequences), ``seq`` and the AdamW settings.
Set-up builds ONE trainer, drives it from the seed through its first three
steps with the window's own call and feed while the numbers for ``correct``
are read off it, and hands the same object to the window. The plain
reference follows those three steps once the window has closed and the
trainer is freed.
"""
from __future__ import annotations

import gc

import jax.numpy as jnp
import numpy as np

from ..lib import compare, system, weights as W
from ..lib.window import TraceSlice, clock, memory_peak_bytes

FOLLOWED = 3          # steps the reference follows
ADAM = {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}


class Feed:
    """The seed's endless stream of batches: int32 [batch, seq], every row
    different."""

    def __init__(self, traffic, vocab, seed):
        self.rng = np.random.default_rng([int(seed), 0x7261696E])
        self.shape = (traffic["batch"], traffic["seq"])
        self.vocab = vocab

    def next(self):
        return self.rng.integers(0, self.vocab, self.shape, dtype=np.int32)


def batches(traffic, vocab, seed, n):
    """The first ``n`` batches of the seed's stream, as the reference is fed."""
    feed = Feed(traffic, vocab, seed)
    return [feed.next() for _ in range(n)]


def hyper(traffic):
    return dict(ADAM, lr=traffic["lr"], weight_decay=traffic["weight_decay"],
                clip_norm=traffic["clip_norm"])


def make_trainer(cell, seed):
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt
    from paddle_tpu.parallel import SpmdTrainer
    arch, cfg, t = cell.arch(), cell.config, cell.traffic
    model = system.build_model(arch, cfg, seed)
    h = hyper(t)
    optimizer = opt.AdamW(
        learning_rate=h["lr"], beta1=h["beta1"], beta2=h["beta2"],
        epsilon=h["epsilon"], weight_decay=h["weight_decay"],
        parameters=model.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(h["clip_norm"]))
    return SpmdTrainer(model, optimizer, arch.loss,
                       remat_layers=arch.blocks(model), remat_policy=t["remat"])


def first_gradient(trainer, arch):
    """Per leaf, the norm of the first gradient as the optimizer got it,
    from its first moment after one step: m1 = (1 - beta1) g."""
    m1 = {n: s["moment1"] for n, s in trainer._opt_state.items()}
    sq = W.part_squares(W.fused_of(arch), m1)
    return {k: float(jnp.sqrt(v)) / (1 - ADAM["beta1"]) for k, v in sq.items()}


def parameter_change(trainer, arch, cfg, seed):
    """Per leaf, the norm of (parameter now - the seed's), group by group so
    that one block's first weights exist at a time."""
    now = {n: p._data for n, p in trainer.model.named_parameters()}
    out = {}
    groups = [("", W.top_weights(arch, cfg, seed))] + [
        (arch.layer_prefix(i), W.layer_weights(arch, cfg, seed, i))
        for i in range(arch.n_layers(cfg))]
    for pre, was in groups:
        got = W.part_squares(W.fused_of(arch),
                             W.difference({k: now[pre + k] for k in was}, was))
        out.update({pre + k: float(jnp.sqrt(v)) for k, v in got.items()})
    return out


def run(cell, args, run):
    """``run`` is the harness's per-run state (clock zero, counters)."""
    import jax.profiler as prof
    arch, cfg, t = cell.arch(), cell.config, cell.traffic
    trainer = make_trainer(cell, args.seed)
    feed = Feed(t, cfg["vocab_size"], args.seed)

    def step():
        with prof.TraceAnnotation("bench.train_step"):
            ids = jnp.asarray(feed.next())
            loss = trainer.train_step(ids, ids)
        with prof.TraceAnnotation("bench.block"):
            trainer.block()
        return loss

    program = {"losses": []}
    for i in range(1, FOLLOWED + 1):
        program["losses"].append(float(step().numpy()))
        if i == 1:
            program["grad_norm"] = first_gradient(trainer, arch)
    program["change_norm"] = parameter_change(trainer, arch, cfg, args.seed)
    step()                                # one more, past every first-call path
    run.note(step_tokens=t["batch"] * t["seq"])

    slice_ = TraceSlice(args.trace, run.trace_dir, args.seconds)
    run.open_window()
    times, t0 = [], clock()
    while True:
        slice_.boundary(clock() - t0, len(times))
        a = clock()                       # after the profiler's start or stop
        if a - t0 >= args.seconds:
            break
        step()
        times.append(clock() - a)
    slice_.stop(len(times))
    elapsed = clock() - t0
    run.close_window()
    peak = memory_peak_bytes()

    tokens = len(times) * t["batch"] * t["seq"]
    measured = {
        "train_tok_s_chip": tokens / elapsed / cell.chips,
        "train_step_s": float(np.median(times)),
        # over the steps' own seconds: a traced run's window also holds the
        # profiler's start and stop, which are not the step's
        "step_tok_s_chip": tokens / sum(times) / cell.chips,
        "steps": len(times), "tokens": tokens, "elapsed_s": elapsed,
    }
    if slice_.t0 is not None:
        measured["traced_steps"] = slice_.last_step - slice_.first_step
    del trainer, step
    gc.collect()
    from ..reference import train_steps
    t_ref = clock()
    reference = train_steps.follow(
        arch, cfg, args.seed, batches(t, cfg["vocab_size"], args.seed, FOLLOWED),
        hyper(t))
    run.note(reference_s=clock() - t_ref)
    numbers = compare.training_numbers(program, reference)
    return {"measured": measured, "numbers": numbers, "attempted": len(times),
            "failed": 0, "memory_peak_bytes": peak, "trace": slice_,
            "reference": reference}

