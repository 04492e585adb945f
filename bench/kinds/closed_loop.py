"""Traffic kind ``closed-loop``: ``clients`` callers, each sending its next
request when its last one ends, into ``ServingEngine.submit`` / ``step``.

The request set is the same multiset for every seed, dealt to the clients in
the order the traffic file's ``order_seed`` draws, and cycled. The pipeline is
filled during set-up: every client's first request is in flight, with its
output cut to a different share so that the clients do not finish in step,
and ``fill_steps`` steps have run before the window opens.
"""
from __future__ import annotations

import gc

import numpy as np

from ..lib import lengths, serving
from ..lib.window import clock, memory_peak_bytes


class Clients:
    def __init__(self, traffic, vocab, seed):
        self.n = traffic["clients"]
        self.reqs = lengths.request_set(traffic, traffic["requests"], vocab, seed)
        self.next = 0
        self.records = []

    def take(self):
        prompt, new = self.reqs[self.next % len(self.reqs)]
        self.next += 1
        rec = serving.Record(prompt, new, None)
        self.records.append(rec)
        return rec


def run(cell, args, run):
    import jax.profiler as prof
    cfg, t = cell.config, cell.traffic
    engine = serving.build_engine(cell, args.seed)
    serving.warm(engine, cfg["vocab_size"], prof)
    clients = Clients(t, cfg["vocab_size"], args.seed)
    book = serving.Book(engine, prof)
    for c in range(clients.n):                   # fill, staggered
        rec = clients.take()
        rec.new = max(2, rec.new * (c + 1) // clients.n)
        book.submit(rec)

    def step(sample_pool):
        done_before = len(book.done)
        book.step(sample_pool)
        for _ in range(len(book.done) - done_before):
            book.submit(clients.take())          # the caller's next request

    for _ in range(t["fill_steps"]):
        step(False)
    slice_ = serving.traced(args, run, lambda: step(False))   # 3 more, traced
    fill = len(book.steps)
    run.open_window()
    t0 = clock()
    while True:
        now = clock() - t0
        slice_.boundary(now, len(book.steps) - fill)
        if clock() - t0 >= args.seconds:
            break
        step(slice_.on)
    slice_.stop(len(book.steps) - fill)
    t_end = clock()
    run.close_window()
    peak = memory_peak_bytes()
    window = t_end - t0

    out_tokens = sum(t0 <= x <= t_end for r in clients.records for x in r.times)
    # a traced run takes its gaps from before the profiler's first stall
    gaps = serving.gaps_between_tokens(clients.records, t0,
                                       slice_.quiet_end(t_end))
    in_window = lambda r: bool(r.handle is not None and r.handle.done
                               and r.times and r.times[-1] >= t0)
    finished = [r for r in clients.records if in_window(r)]
    measured = {
        "serve_tok_s": out_tokens / window,
        "itl_p95_s": float(np.percentile(gaps, 95)),
        "itl_mean_s": float(gaps.mean()),
        "engine_step_s": float(np.median([x[1] for x in book.steps[fill:]])),
        "requests": len(finished), "gaps": int(gaps.size), "window_s": window,
        "steps": book.steps[fill:], "slice": (slice_.first_step, slice_.last_step),
    }
    failed = sum(r.failed for r in clients.records)
    # compared: drawn from the first half of the fill, in flight when the
    # window opens with at most half their outputs to go, so a window at half
    # this speed still finishes them and two runs of a seed compare the same
    # tokens; which of them ended before the window is a matter of steps
    sure = [r for r in clients.records[:clients.n // 2] if in_window(r)]
    seqs = serving.sequences(serving.sample(sure, args.seed, t["check_requests"]))
    del engine, book.engine, book
    gc.collect()
    numbers = serving.compare_served(cell, args.seed, seqs, run)
    return {"measured": measured, "numbers": numbers,
            "attempted": len(finished) + int(failed), "failed": int(failed),
            "memory_peak_bytes": peak, "trace": slice_, "sequences": seqs}
