"""Traffic kind ``open-loop-paced``: requests due on a fixed schedule whether
or not earlier ones have finished, into ``ServingEngine.submit`` / ``step``.

The traffic file gives the engine's shape, the ``rate`` (requests a second, a
number found once by a sweep on the chip), the ``tail_s`` at the window's end
in which nothing new is due, the length distributions, the ``jitter`` of the
arrivals (a share of a gap) and the ``order_seed`` that fixes pairing and
order for every run: every mix states both. The multiset of lengths is the
same for every seed (``lib.lengths``); each request is timed from when it
was DUE. One thread submits and steps.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from ..lib import lengths, serving
from ..lib.window import clock, memory_peak_bytes


def schedule(traffic, vocab, seconds, seed):
    n = int(traffic["rate"] * (seconds - traffic["tail_s"]))
    reqs = lengths.request_set(traffic, n, vocab, seed)
    due = lengths.paced_arrivals(traffic["rate"], n, seed, traffic["jitter"])
    return [serving.Record(p, o, d) for (p, o), d in zip(reqs, due)]


def drive(book, records, seconds, slice_):
    """The measured window. Returns (t0, t_end)."""
    t0 = clock()
    nxt, n = 0, len(records)
    while True:
        now = clock() - t0
        slice_.boundary(now, len(book.steps))
        now = clock() - t0
        if now >= seconds:
            break
        while nxt < n and records[nxt].due <= now:
            book.submit(records[nxt])
            nxt += 1
        if book.engine.has_work():
            book.step(slice_.on)
        else:
            wake = min(records[nxt].due if nxt < n else seconds, seconds)
            time.sleep(max(0.0, min(wake - now, 0.002)))
    slice_.stop(len(book.steps))
    return t0, clock()


def run(cell, args, run):
    import jax.profiler as prof
    cfg, t = cell.config, cell.traffic
    engine = serving.build_engine(cell, args.seed)
    serving.warm(engine, cfg["vocab_size"], prof)
    records = schedule(t, cfg["vocab_size"], args.seconds, args.seed)
    book = serving.Book(engine, prof)
    slice_ = serving.traced(args, run, *serving.warm_again(engine, cfg["vocab_size"], prof))
    run.open_window()
    t0, t_end = drive(book, records, args.seconds, slice_)
    run.close_window()
    peak = memory_peak_bytes()
    window = t_end - t0

    # every request due in the window; a traced run keeps to those that had
    # their tail before the profiler's first stall
    quiet = slice_.quiet_end(t_end)
    timed = [r for r in records
             if quiet == t_end or t0 + r.due + t["tail_s"] <= quiet]
    ttft = np.array([(r.times[0] - t0 - r.due) if r.times and not r.failed
                     else window for r in timed])
    gaps = serving.gaps_between_tokens(records, t0, quiet)
    waits = np.array([(r.started - t0 - r.due) if r.started is not None
                      else window for r in timed])
    late = np.array([r.submitted - t0 - r.due for r in timed
                     if r.submitted is not None])
    prompt_tokens = sum(len(r.prompt) for r in records if r.handle is not None)
    out_tokens = sum(len(r.times) for r in records)
    arrivals_end = t0 + args.seconds - t["tail_s"]
    backlog = lambda at: next((s[7] for s in book.steps if s[0] >= at), 0)
    measured = {
        "backlog_mid": backlog((t0 + arrivals_end) / 2),
        "backlog_end": backlog(arrivals_end),
        "ttft_mean_s": float(ttft.mean()),
        "ttft_p50_s": float(np.percentile(ttft, 50)),
        "ttft_p95_s": float(np.percentile(ttft, 95)),
        "itl_p95_s": float(np.percentile(gaps, 95)),
        "itl_mean_s": float(gaps.mean()),
        "engine_step_s": float(np.median([x[1] for x in book.steps])),
        "serve_tok_s": out_tokens / window,
        "queue_wait_mean_s": float(waits.mean()),
        "gen_late_p99_s": float(np.percentile(late, 99)),
        "prefill_token_share": 100.0 * prompt_tokens
        / max(1, prompt_tokens + out_tokens),
        "requests": len(records), "gaps": int(gaps.size), "window_s": window,
        "steps": book.steps, "slice": (slice_.first_step, slice_.last_step),
    }
    failed = sum(r.failed for r in records)
    seqs = serving.sequences(serving.sample(records, args.seed, t["check_requests"]))
    del engine, book.engine, book
    gc.collect()
    numbers = serving.compare_served(cell, args.seed, seqs, run)
    return {"measured": measured, "numbers": numbers,
            "attempted": len(records), "failed": int(failed),
            "memory_peak_bytes": peak, "trace": slice_, "sequences": seqs}
