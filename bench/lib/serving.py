"""What the serving kinds share: the engine for a cell, the bookkeeping of
every request on the benchmark's own clock, and the comparison of served
tokens with the plain reference.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from . import system, weights as W
from .window import TraceSlice, clock


def build_engine(cell, seed):
    from paddle_tpu.serving import EngineConfig, ServingEngine
    model = system.build_model(cell.arch(), cell.config, seed)
    e = cell.traffic["engine"]
    return ServingEngine(model, EngineConfig(
        max_seqs=e["max_seqs"], token_budget=e["token_budget"],
        block_size=e["block_size"], num_blocks=e["num_blocks"],
        max_model_len=e["max_model_len"]))


class Record:
    """One request as the benchmark saw it, on the benchmark's clock."""

    __slots__ = ("prompt", "new", "due", "submitted", "started", "times",
                 "handle", "pos", "emitted", "failed")

    def __init__(self, prompt, new, due):
        self.prompt, self.new, self.due = prompt, new, due
        self.submitted = self.started = None
        self.times = []              # one clock reading per output token
        self.handle = None
        self.pos = 0                 # prompt+output tokens known to be cached
        self.emitted = 0
        self.failed = False


class Book:
    """Submits, steps and keeps the records. One thread drives everything:
    the engine serialises ``submit`` and ``step`` under one lock anyway."""

    def __init__(self, engine, prof):
        self.engine, self.prof = engine, prof
        self.live, self.done = [], []
        self.steps = []              # (start, seconds, tokens, sampled,
        #                               attention context, live context, pool,
        #                               requests still in flight)

    def submit(self, rec: Record):
        rec.submitted = clock()
        with self.prof.TraceAnnotation("bench.submit"):
            try:
                rec.handle = self.engine.submit(
                    rec.prompt, max_new_tokens=rec.new,
                    on_token=lambda _t, r=rec: r.times.append(clock()))
            except Exception:   # noqa: BLE001 - a refused request is a failed one
                rec.failed = True
                self.done.append(rec)
                return
        self.live.append(rec)

    def step(self, pool_sample: bool):
        a = clock()
        with self.prof.TraceAnnotation("bench.engine_step"):
            self.engine.step()
        b = clock()
        tokens = sampled = 0
        attn = live_ctx = 0
        still = []
        for r in self.live:
            h = r.handle
            pos = h.pos
            if pos > r.pos:
                if r.started is None:
                    r.started = a
                tokens += pos - r.pos
                # rows at positions r.pos .. pos-1, each over position+1 keys
                attn += (pos * (pos + 1) - r.pos * (r.pos + 1)) // 2
                live_ctx += pos
                r.pos = pos
            if len(r.times) > r.emitted:
                sampled += 1
                r.emitted = len(r.times)
            if h.done:
                r.failed = h.error is not None
                self.done.append(r)
            else:
                still.append(r)
        self.live = still
        pool = None
        if pool_sample:
            p = self.engine.telemetry()["pool"]
            pool = p["used"] / p["size"]
        self.steps.append((a, b - a, tokens, sampled, attn, live_ctx, pool,
                           len(self.live)))
        return b


def warm(engine, vocab, prof):
    """Every program the window uses, compiled before it: two short
    requests through prefill and a few decode steps, then a clean engine."""
    rng = np.random.default_rng(0)
    book = Book(engine, prof)
    for n in (40, 70):
        book.submit(Record(rng.integers(0, vocab, n).tolist(), 4, 0.0))
    while engine.has_work():
        book.step(True)
    if len(book.done) != 2 or any(r.failed for r in book.done):
        raise RuntimeError("warm-up requests did not finish")


def gaps_between_tokens(records, t_from, t_to):
    """Every gap between successive output tokens of every request, over the
    tokens produced in [t_from, t_to]."""
    out = [np.diff([x for x in r.times if t_from <= x <= t_to])
           for r in records]
    return np.concatenate(out) if out else np.zeros(0)


def warm_again(engine, vocab, prof):
    """``(step, drain)`` for ``traced``: two warm-up requests once more (other
    ids: no prefix hit), submitted when ``step`` is first called, so that an
    untraced run, which never calls it, submits nothing."""
    made = []

    def book():
        if not made:
            rng = np.random.default_rng(1)
            made.append(Book(engine, prof))
            for n in (40, 70):
                made[0].submit(Record(rng.integers(0, vocab, n).tolist(), 4, 0.0))
        return made[0]

    def drain():
        while engine.has_work():
            book().step(True)

    return (lambda: book().step(True)), drain


def traced(args, run, step, drain=None):
    """The run's profiler slice. In a traced run ``TraceSlice.calibrate``
    first traces three calls of ``step`` (an engine step on the cell's own
    program: one fixed shape, so any step leaves the events a window's step
    does), counts what the export holds of them and sets the slice's limit by
    steps; ``drain`` then empties the engine again; the count and the limit go
    into the run's notes. An untraced run makes the slice and nothing else."""
    slice_ = TraceSlice(args.trace, run.trace_dir, args.seconds)
    if slice_.on:
        slice_.calibrate(step)
        if drain is not None:
            drain()
        run.note(trace_events_a_step=slice_.events_a_step,
                 trace_max_steps=slice_.max_steps)
    return slice_


# -- the comparison ------------------------------------------------------------
def sample(records, seed, k):
    """``k`` of ``records`` drawn from the seed, the longest among them; of
    those only the ones that finished whole. Hand it requests that EVERY run
    finishes, in the order they were submitted: the draw is then the same in
    two runs of one seed, however many requests each window got through."""
    done = [r for r in records if r.handle is not None and r.handle.done
            and not r.failed and len(r.handle.output) == r.new]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + r.new)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0x73616D70])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _bucket(n, step):
    return -(-n // step) * step


def reference_logits(arch, cfg, seed, sequences, q=None):
    """The reference's logits for every served token: one full causal
    forward over each ``(prompt, served)``, ``embed``, then the stops of the
    adapter's ``walk`` in order, then ``head``. A stop ``(name, i)`` calls the
    reference module's function ``name`` on ``(top, layer i's leaves, x)``;
    the layer's leaves are made from the seed again at every visit, so one
    layer's weights are alive at a time however often the walk comes back to
    it. Returns one [len(served), vocab] float32 array per sequence (row i
    predicts served[i])."""
    ref = importlib.import_module(arch.REFERENCE)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    walk = list(arch.walk(cfg))

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    @jax.jit
    def embed(top, ids):
        return ref.embed(f32(top), ids, dict(items))

    def stop(fn):
        return jax.jit(lambda top, lw, x: fn(f32(top), f32(lw), x,
                                             dict(items), q))

    steps = {name: stop(getattr(ref, name)) for name in {n for n, _ in walk}}

    @jax.jit
    def head(top, x):
        return ref.head(f32(top), x, dict(items), q)

    top = W.top_weights(arch, cfg, seed)
    # one padded length for all of them: one program per step, few shapes
    width = _bucket(max(len(p) + len(s) - 1 for p, s in sequences), 256)
    rows_width = _bucket(max(len(s) for _, s in sequences), 64)
    xs = []
    for prompt, served in sequences:
        fed = list(prompt) + list(served[:-1])
        ids = np.zeros(width, np.int32)
        ids[:len(fed)] = fed
        xs.append(embed(top, jnp.asarray(ids)))
    for name, i in walk:
        lw = None if i is None else W.layer_weights(arch, cfg, seed, i)
        xs = [steps[name](top, lw, x) for x in xs]
    out = []
    for (prompt, served), x in zip(sequences, xs):
        rows = x[len(prompt) - 1: len(prompt) - 1 + len(served)]
        logits = head(top, jnp.pad(rows, ((0, rows_width - len(served)), (0, 0))))
        out.append(np.asarray(logits[:len(served)]))
    return out


def token_gaps(logits, tokens):
    """For each served token, how far its reference logit lies below the
    reference's best, in standard deviations of that row's logits."""
    logits = np.asarray(logits, np.float64)
    tokens = np.asarray(tokens)
    best = logits.max(axis=-1)
    mine = logits[np.arange(len(tokens)), tokens]
    return (best - mine) / logits.std(axis=-1)


def sequences(picked):
    return [(r.prompt, list(r.handle.output)) for r in picked]


def _numbers(gaps):
    return {"token_gap_max": float(gaps.max()),
            "token_gap_mean": float(gaps.mean())}


def served_numbers(arch, cfg, seed, seqs):
    """The numbers for ``correct`` from the sampled ``(prompt, served)``."""
    if not seqs:
        return {"token_gap_max": None, "token_gap_mean": None}   # nothing served
    return _numbers(np.concatenate([token_gaps(lg, s[1]) for lg, s in zip(
        reference_logits(arch, cfg, seed, seqs), seqs)]))


def compare_served(cell, seed, seqs, run):
    """``served_numbers`` for a run, its seconds and tokens in the run's notes.
    Call it once the window has closed and the engine is freed."""
    t_ref = clock()
    numbers = served_numbers(cell.arch(), cell.config, seed, seqs)
    run.note(reference_s=clock() - t_ref,
             checked_tokens=sum(len(s) for _, s in seqs))
    return numbers


def control_numbers(arch, cfg, seed, seqs, q):
    """The same numbers for the control: the reference computed in the
    precision ``q`` stands in the program's place. It need not decode: at
    each position of the same prompts and tokens, the token the lower
    precision puts first is read against the float32 reference."""
    ref = reference_logits(arch, cfg, seed, seqs)
    low = reference_logits(arch, cfg, seed, seqs, q)
    return _numbers(np.concatenate([
        token_gaps(r, np.argmax(l, axis=-1)) for r, l in zip(ref, low)]))
