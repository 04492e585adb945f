#!/usr/bin/env python
"""Poisson open-loop serving benchmark: continuous vs static batching.

A seeded open-loop load generator (arrivals
are a Poisson process — exponential gaps at --rate requests/s — fixed by
the seed BEFORE either run, so both policies face the identical
schedule) drives the ServingEngine twice over the same request set:

  * ``continuous`` — the real scheduler: admit/evict every decode step,
    prefill chunks and decode sharing one token budget;
  * ``static``     — the same engine machinery with gang admission
    (fill the batch only when it is empty, run it dry), i.e. the
    BatchingServer micro-batching policy. Identical per-step dispatch
    cost, so the measured delta is the SCHEDULING POLICY, not harness
    overhead.

``--spec`` adds the speculative-decoding pair: the same engine driven
twice over one seeded repetitive/code-like workload (prompts built from
repeated token patterns, decode-heavy max_new), once plain
(``nonspec``) and once with the n-gram self-drafting drafter
(``spec``) — identical compiled program (the packed verify batch has
the same static shape), so ``vs_nonspec`` measures the SPECULATION
delta: fewer engine steps for the same bit-identical tokens. The spec
row reports accept_rate and rollback pages.

Success metric (ROADMAP items 2/4b): tokens/s and p99 end-to-end
latency. Every row also carries SLO columns sourced from
``engine.telemetry()`` (serving/obs.py): attainment and goodput under
per-request TTFT/TPOT deadlines, plus the engine's STREAMING sketch
p50/p99 TTFT — cross-checked in-run against the bench's own offline
percentiles of the identical values and asserted within the sketch's
published error bound. Writes a BENCH_SERVE_<tag>.json artifact
(schema_version 2); ``--fast`` is the seeded tier-1 mode (tiny model,
seconds on CPU) whose throughput floors (continuous > static; with
--spec, spec > nonspec) tests/test_serve_engine.py asserts.

``--chaos`` adds the resilience pair (ROADMAP serving-resilience):
the same seeded OVERLOAD schedule (arrival rate far past capacity,
every request deadline-tracked) with a seeded ``serve.engine_step``
fault injected mid-run, driven twice. ``chaos_baseline`` is the PR 6
engine: unbounded queue, no containment — the fault escapes ``step()``
and wedges the driver (the bench models the dead thread by stopping
the drive loop), parking every in-flight request. ``chaos_resilient``
arms the resilience plane (bounded queue, SLO-aware shed, retry
budget): the fault is contained and retried, overload is refused as
typed ``AdmissionRejected`` sheds, and every accepted request FINISHES
— the row asserts zero parked requests and strictly more goodput than
the baseline. Both rows face the identical schedule and fault plan.

``--router`` adds the scale-out rows (ROADMAP item 2 rung c): ONE
seeded shared-prefix open-loop schedule (thousands of requests in full
mode) driven three ways on identical per-engine configs — a single
engine, an N-replica ``ReplicaRouter`` under RANDOM placement, and the
same fleet under PREFIX-AFFINITY placement. A replica is one chip, so
what the fleet adds is aggregate KV/prefix-cache capacity: the workload's
prefix working set fits the affinity-PARTITIONED caches but thrashes one
pool's LRU (and every replica's, under random placement). The rows pin
router-vs-single tokens/s scaling and the affinity-vs-random prefix-hit
uplift; greedy output crc equality across all three is asserted in-run
(routing moves requests, never changes tokens).

``--disagg`` adds the disaggregation rows (ROADMAP item 2 rung b): ONE
seeded bursty-prompt open-loop schedule — a steady decode-heavy stream
with per-request TPOT deadlines, overlaid with periodic long-prompt
bursts — driven through an N-replica UNIFIED fleet and an equal-size
DISAGGREGATED fleet (N/2 prefill-role + N/2 decode-role engines, KV
pages handed off over the router). On a unified engine every decode
token rides a step program wide enough for chunked prefill, and bursts
contend with decode for the KV pool; the split lets decode run the
token-thin program on an interference-free pool. The rows pin decode
TPOT p99 and SLO goodput improving at equal load, with greedy-output
crc equality asserted in-run (disaggregation moves work, never changes
tokens).

Usage:
  python tools/bench_serve.py --fast --spec         # tier-1 smoke
  python tools/bench_serve.py --spec --tag r07
  python tools/bench_serve.py --chaos --tag r13
  python tools/bench_serve.py --router --tag r14
  python tools/bench_serve.py --disagg --tag r15
"""
import argparse
import json
import os
import sys
import time
import zlib

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402


def _build_model(fast: bool):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(11)
    if fast:
        cfg = LlamaConfig.tiny(vocab_size=128, hidden_size=32, layers=2,
                               heads=4, kv_heads=2, seq=128)
    else:
        cfg = LlamaConfig.tiny(vocab_size=1024, hidden_size=256, layers=4,
                               heads=8, kv_heads=4, seq=512)
    cfg.use_flash_attention = False
    return LlamaForCausalLM(cfg)


def make_workload(seed: int, n_requests: int, rate: float, vocab: int,
                  prompt_lens=(6, 24), max_new=(4, 16)):
    """Seeded Poisson open-loop schedule: (arrival_s, prompt, max_new)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, n_requests)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(n_requests):
        plen = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        mnew = int(rng.integers(max_new[0], max_new[1] + 1))
        prompt = rng.integers(1, vocab, (plen,)).tolist()
        reqs.append({"arrival_s": float(arrivals[i]), "prompt": prompt,
                     "max_new": mnew})
    return reqs


def make_repetitive_workload(seed: int, n_requests: int, rate: float,
                             vocab: int, n_patterns: int = 4,
                             period=(3, 6), prompt_lens=(12, 24),
                             max_new=(16, 32)):
    """Seeded Poisson schedule over repetitive/code-like prompts: each
    prompt is one of ``n_patterns`` short token patterns tiled to its
    length — the shape boilerplate-heavy serving traffic takes, and the
    one a prompt-lookup drafter feeds on."""
    rng = np.random.default_rng(seed)
    pats = [rng.integers(1, vocab,
                         (int(rng.integers(period[0], period[1] + 1)),)
                         ).tolist() for _ in range(n_patterns)]
    gaps = rng.exponential(1.0 / rate, n_requests)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(n_requests):
        plen = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        mnew = int(rng.integers(max_new[0], max_new[1] + 1))
        pat = pats[int(rng.integers(0, n_patterns))]
        prompt = (pat * (plen // len(pat) + 1))[:plen]
        reqs.append({"arrival_s": float(arrivals[i]), "prompt": prompt,
                     "max_new": mnew})
    return reqs


def make_shared_prefix_workload(seed: int, n_requests: int, rate: float,
                                vocab: int, n_prefixes: int,
                                prefix_len: int, suffix_lens=(3, 8),
                                max_new=(3, 6)):
    """Seeded Poisson schedule over shared-system-prompt traffic: each
    request is one of ``n_prefixes`` page-aligned shared prefixes plus a
    short unique suffix — the workload shape where serving throughput is
    prefill-dominated and the prefix cache (and who HOLDS it) decides
    how much of that prefill is ever recomputed. This is the router
    bench's working set: all prefixes fit in the FLEET's pooled cache
    but not in one replica's."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(1, vocab, (prefix_len,)).tolist()
                for _ in range(n_prefixes)]
    gaps = rng.exponential(1.0 / rate, n_requests)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(n_requests):
        pre = prefixes[int(rng.integers(0, n_prefixes))]
        tail = rng.integers(
            1, vocab,
            (int(rng.integers(suffix_lens[0], suffix_lens[1] + 1)),)
        ).tolist()
        mnew = int(rng.integers(max_new[0], max_new[1] + 1))
        reqs.append({"arrival_s": float(arrivals[i]),
                     "prompt": pre + tail, "max_new": mnew})
    return reqs


def make_bursty_workload(seed: int, n_steady: int, steady_rate: float,
                         vocab: int, burst_every_s: float,
                         burst_size: int, steady_prompt=(6, 12),
                         steady_new=(14, 22), burst_prompt=(64, 96),
                         burst_new=(2, 3)):
    """Seeded bursty-prompt open-loop schedule: a steady Poisson stream
    of DECODE-HEAVY requests (short prompt, long output — the
    interactive traffic whose TPOT the SLO tracks) overlaid with
    periodic BURSTS of long-prompt, short-output arrivals (the ingest
    traffic whose chunked prefill steals the token budget — and the KV
    pool — from decode on a unified engine). Every request carries a
    ``kind`` tag so the bench accounts decode TPOT on exactly the
    steady stream; the schedule is fixed by the seed BEFORE either
    fleet runs, so unified and disaggregated face identical load."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / steady_rate, n_steady)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(n_steady):
        plen = int(rng.integers(steady_prompt[0], steady_prompt[1] + 1))
        mnew = int(rng.integers(steady_new[0], steady_new[1] + 1))
        reqs.append({"arrival_s": float(arrivals[i]), "kind": "steady",
                     "prompt": rng.integers(1, vocab, (plen,)).tolist(),
                     "max_new": mnew})
    span = float(arrivals[-1])
    n_bursts = max(int(span / burst_every_s), 1)
    for b in range(n_bursts):
        t = burst_every_s * (b + 0.5)
        for _ in range(burst_size):
            plen = int(rng.integers(burst_prompt[0], burst_prompt[1] + 1))
            mnew = int(rng.integers(burst_new[0], burst_new[1] + 1))
            reqs.append({
                "arrival_s": t + float(rng.uniform(0, 0.02)),
                "kind": "burst",
                "prompt": rng.integers(1, vocab, (plen,)).tolist(),
                "max_new": mnew})
    reqs.sort(key=lambda r: r["arrival_s"])
    return reqs


def _order_stat(values, q: float) -> float:
    """The ceil(q*n)-th order statistic — EXACTLY what the engine's
    bounded quantile sketch estimates, so the cross-check below compares
    like with like (np.percentile interpolates between order stats,
    which would loosen the assertable bound for no reason)."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(1, int(np.ceil(q * len(v)))) - 1])


def _crosscheck_sketch(row, tel, engine_ttfts):
    """Assert the engine's streaming sketch p50/p99 TTFT agree with the
    offline percentiles computed from the SAME per-request values within
    the sketch's published error bound: a value v lands in a bucket whose
    upper edge e obeys v <= e <= v * rel_err, so the sketch estimate of
    the q-th order statistic o is bounded by o <= sketch <= o * rel_err
    (tiny absolute slack absorbs float rounding)."""
    lat = tel["latency"]["ttft"]
    rel = tel["latency"]["quantile_rel_error"]
    assert lat["count"] == len(engine_ttfts), \
        f"sketch saw {lat['count']} TTFTs, offline saw {len(engine_ttfts)}"
    for name, q in (("p50", 0.50), ("p99", 0.99)):
        off = _order_stat(engine_ttfts, q)
        got = lat[name]
        lo, hi = off * (1 - 1e-9) - 1e-9, off * rel * (1 + 1e-6) + 1e-9
        assert lo <= got <= hi, \
            (f"engine sketch TTFT {name}={got:.6f}s outside the sketch "
             f"error bound [{lo:.6f}, {hi:.6f}] of offline {off:.6f}s")
        row[f"ttft_{name}_engine_s"] = round(got, 6)
        row[f"ttft_{name}_offline_s"] = round(off, 6)


def drive(model, workload, policy: str, engine_kw: dict, spec_kw=None,
          slo=None):
    """One open-loop run: submit each request when the run clock passes
    its arrival time, step the engine whenever it has work. Returns the
    stats row for the artifact. ``slo=(ttft_deadline_s, tpot_deadline_s)``
    attaches deadlines to every request; the row then carries
    SLO-attainment/goodput columns sourced from ``engine.telemetry()``
    and the engine's streaming quantiles are cross-checked against the
    offline percentiles of the same values."""
    from paddle_tpu.serving import EngineConfig, ObsConfig, ServingEngine
    eng = ServingEngine(model, EngineConfig(
        policy=policy, obs=ObsConfig(flight_steps=64, flight_requests=32),
        **engine_kw, **(spec_kw or {})))
    ttft_d, tpot_d = slo if slo else (None, None)
    pending = sorted(workload, key=lambda r: r["arrival_s"])
    handles = []
    t0 = time.monotonic()
    i = 0
    while i < len(pending) or eng.has_work():
        now = time.monotonic() - t0
        while i < len(pending) and pending[i]["arrival_s"] <= now:
            r = pending[i]
            handles.append((r, eng.submit(r["prompt"],
                                          max_new_tokens=r["max_new"],
                                          ttft_deadline=ttft_d,
                                          tpot_deadline=tpot_d)))
            i += 1
        if eng.has_work():
            eng.step()
        elif i < len(pending):
            time.sleep(min(pending[i]["arrival_s"] - now, 0.005))
    wall = time.monotonic() - t0
    lats, ttfts, engine_ttfts, tokens = [], [], [], 0
    crc = 0
    for spec, req in handles:
        assert req.done, f"request {req.rid} never finished"
        tokens += len(req.output)
        crc = zlib.crc32(np.asarray(req.output, np.int32).tobytes(), crc)
        lats.append((req.finished_at - t0) - spec["arrival_s"])
        ttfts.append((req.first_token_at - t0) - spec["arrival_s"])
        # the engine-side TTFT (submit -> first token): the exact values
        # its quantile sketch summarized, for the cross-check
        engine_ttfts.append(req.first_token_at - req.arrival)
    lats = np.asarray(lats)
    tel = eng.telemetry()
    goodput = tel["slo"]["goodput_tokens"]
    row = {
        "policy": policy,
        "requests": len(handles),
        "output_tokens": int(tokens),
        "wall_s": round(wall, 4),
        "tokens_per_s": round(tokens / wall, 2),
        "p50_latency_s": round(float(np.percentile(lats, 50)), 4),
        "p99_latency_s": round(float(np.percentile(lats, 99)), 4),
        "mean_ttft_s": round(float(np.mean(ttfts)), 4),
        "engine_steps": eng.steps,
        "preemptions": sum(1 for _, r in handles if r.preemptions),
        "prefix_hits": eng.pool.stats["prefix_hits"],
        "kv_evictions": eng.pool.stats["evicted"],
        "output_crc32": crc,
        "slo_attainment": tel["slo"]["attainment"],
        "slo_violations": tel["slo"]["violations"],
        "goodput_tokens": goodput,
        "goodput_tokens_per_s": round(goodput / wall, 2),
        "goodput_fraction": tel["slo"]["goodput_fraction"],
    }
    _crosscheck_sketch(row, tel, engine_ttfts)
    if spec_kw:
        s = eng.spec_stats()
        row["speculative"] = spec_kw
        row["spec_proposed_tokens"] = s["proposed"]
        row["spec_accepted_tokens"] = s["accepted"]
        row["accept_rate"] = round(s["accept_rate"], 3)
        row["spec_rollback_pages"] = s["rollback_pages"]
    return row


def drive_router(model, workload, n_replicas: int, policy: str,
                 engine_kw: dict, seed: int):
    """One open-loop run through a ``ReplicaRouter`` of ``n_replicas``
    identical engines (``n_replicas=1`` IS the single-engine baseline on
    the same machinery, so the measured delta is the fleet + routing
    policy, not harness overhead). Single-threaded round-robin driving:
    on this box the honest scale-out win is aggregate KV/prefix-cache
    capacity — compute is one core either way — so the row reports both
    tokens/s and the prefix-cache hit economics that produce it."""
    from paddle_tpu.serving import (EngineConfig, ReplicaRouter,
                                    ServingEngine)
    engines = [ServingEngine(model, EngineConfig(policy="continuous",
                                                 **engine_kw))
               for _ in range(n_replicas)]
    router = ReplicaRouter(engines, policy=policy, seed=seed)
    pending = sorted(workload, key=lambda r: r["arrival_s"])
    handles = []
    t0 = time.monotonic()
    i = 0
    while i < len(pending) or router.has_work():
        now = time.monotonic() - t0
        while i < len(pending) and pending[i]["arrival_s"] <= now:
            r = pending[i]
            handles.append((r, router.submit(r["prompt"],
                                             max_new_tokens=r["max_new"],
                                             tag=i)))
            i += 1
        if router.has_work():
            router.step_all()
        elif i < len(pending):
            time.sleep(min(pending[i]["arrival_s"] - now, 0.005))
    wall = time.monotonic() - t0
    lats, ttfts, tokens = [], [], 0
    crc = 0
    for spec, req in handles:
        assert req.done, f"request {req.rid} never finished"
        tokens += len(req.output)
        crc = zlib.crc32(np.asarray(req.output, np.int32).tobytes(), crc)
        lats.append((req.finished_at - t0) - spec["arrival_s"])
        ttfts.append((req.first_token_at - t0) - spec["arrival_s"])
    lats = np.asarray(lats)
    tel = router.telemetry()
    prompt_tokens = sum(len(r["prompt"]) for r, _ in handles)
    hit_tokens = tel["fleet"]["prefix"]["hit_tokens"]
    return {
        "policy": policy,
        "replicas": n_replicas,
        "requests": len(handles),
        "output_tokens": int(tokens),
        "prompt_tokens": int(prompt_tokens),
        "wall_s": round(wall, 4),
        "tokens_per_s": round(tokens / wall, 2),
        "p50_latency_s": round(float(np.percentile(lats, 50)), 4),
        "p99_latency_s": round(float(np.percentile(lats, 99)), 4),
        "mean_ttft_s": round(float(np.mean(ttfts)), 4),
        "engine_steps": tel["fleet"]["steps"],
        "prefix_queries": tel["fleet"]["prefix"]["queries"],
        "prefix_hits": tel["fleet"]["prefix"]["hits"],
        "prefix_hit_rate": tel["fleet"]["prefix"]["hit_rate"],
        "prefix_hit_tokens": int(hit_tokens),
        # the load-bearing economics: what fraction of offered prompt
        # tokens the fleet's caches served instead of re-prefilling
        "prefix_hit_token_rate": round(hit_tokens
                                       / max(prompt_tokens, 1), 4),
        "routed": tel["router"]["routed"],
        "affinity_hits": tel["router"]["affinity_hits"],
        "output_crc32": crc,
    }


def drive_fleet(workload, engines, seed: int, slo):
    """Open-loop drive of one pre-built fleet behind an affinity
    ``ReplicaRouter`` (role-less engines = the unified fleet; prefill/
    decode-role engines = the disaggregated fleet with KV-page
    hand-off). SLO deadlines attach to the STEADY stream only — the
    decode-latency contract disaggregation exists to protect. Returns
    the stats row: tokens/s, steady-stream decode TPOT order-stat
    percentiles, the fleet SLO roll-up, hand-off economics, crc, and
    the fleet signal-bus summary (pressure ratio, finished-weighted
    attainment, per-role queue percentiles from the signal ring) —
    BENCH_SERVE artifacts carry fleet evidence."""
    from paddle_tpu.serving import FleetObsConfig, ReplicaRouter
    router = ReplicaRouter(engines, policy="affinity", seed=seed,
                           fleet_obs=FleetObsConfig(window=256))
    ttft_d, tpot_d = slo
    pending = sorted(workload, key=lambda r: r["arrival_s"])
    handles = []
    t0 = time.monotonic()
    i = 0
    while i < len(pending) or router.has_work():
        now = time.monotonic() - t0
        while i < len(pending) and pending[i]["arrival_s"] <= now:
            r = pending[i]
            steady = r.get("kind") != "burst"
            handles.append((r, router.submit(
                r["prompt"], max_new_tokens=r["max_new"],
                ttft_deadline=ttft_d if steady else None,
                tpot_deadline=tpot_d if steady else None, tag=i)))
            i += 1
        if router.has_work():
            router.step_all()
        elif i < len(pending):
            time.sleep(min(pending[i]["arrival_s"] - now, 0.005))
    wall = time.monotonic() - t0
    tokens, crc = 0, 0
    lats, tpots = [], []
    for spec, req in handles:
        assert req.done and req.error is None, \
            f"request {req.rid} parked/failed across the fleet"
        tokens += len(req.output)
        crc = zlib.crc32(np.asarray(req.output, np.int32).tobytes(), crc)
        lats.append((req.finished_at - t0) - spec["arrival_s"])
        if spec.get("kind") != "burst" and len(req.output) > 1 \
                and req.first_token_at is not None:
            # per-request decode TPOT: mean seconds per output token
            # AFTER the first — the quantity prefill interference taxes
            tpots.append((req.finished_at - req.first_token_at)
                         / (len(req.output) - 1))
    tel = router.telemetry()
    slo_agg = tel["fleet"].get("slo", {})
    goodput = slo_agg.get("goodput_tokens", 0)
    sig = router.signals()
    per_role_q = {}
    for rep in sig["replicas"]:
        role = rep["role"] or "unified"
        per_role_q.setdefault(role, []).extend(
            rep["window"]["queue_depth"])
    fleet_signals = {
        "schema_version": sig["version"],
        "samples": sig["samples"],
        "pressure": sig["fleet"]["pressure"],
        "slo_attainment_weighted": sig["fleet"]["slo"]["attainment"],
        "queue_depth": {
            role: {"p50": round(_order_stat(v, 0.50), 2),
                   "p99": round(_order_stat(v, 0.99), 2)}
            for role, v in sorted(per_role_q.items())},
    }
    return {
        "replicas": len(engines),
        "roles": [getattr(e, "role", None) for e in engines],
        "requests": len(handles),
        "output_tokens": int(tokens),
        "wall_s": round(wall, 4),
        "tokens_per_s": round(tokens / wall, 2),
        "p99_latency_s": round(float(np.percentile(np.asarray(lats),
                                                   99)), 4),
        "steady_requests": len(tpots),
        "decode_tpot_p50_s": round(_order_stat(tpots, 0.50), 5),
        "decode_tpot_p99_s": round(_order_stat(tpots, 0.99), 5),
        "engine_steps": tel["fleet"]["steps"],
        "slo_attainment": slo_agg.get("attainment"),
        "goodput_tokens": goodput,
        "goodput_tokens_per_s": round(goodput / wall, 2),
        "goodput_fraction": slo_agg.get("goodput_fraction"),
        "prefix_hit_tokens": int(tel["fleet"]["prefix"]["hit_tokens"]),
        "kv_handoffs": dict(router.kv_handoffs)
        if router.disaggregated else None,
        "fleet_signals": fleet_signals,
        "output_crc32": crc,
    }


def run_disagg_pair(seed: int, fast: bool):
    """The disaggregation rows (ROADMAP item 2 rung b): ONE seeded
    bursty-prompt schedule driven through (a) an N-replica UNIFIED
    fleet — every engine serves both phases, so every decode token
    rides a step program wide enough for chunked prefill, and bursts
    contend with decode for each engine's KV pool — and (b) an
    EQUAL-SIZE disaggregated fleet: N/2 prefill-role engines at the
    same wide budget feeding N/2 decode-role engines that run the
    token-thin decode program, KV pages handed off over the router.
    The honest one-core mechanism: a decode token's latency is the
    wall time of the step that carries it, and disaggregation is what
    lets decode steps stop paying for prefill width (plus pool
    isolation: bursts can no longer evict or preempt decode KV). On
    real silicon the pools also separate compute. Greedy output crc
    equality between the fleets is asserted in-run — disaggregation
    moves work, never changes tokens."""
    from paddle_tpu.serving import EngineConfig, ObsConfig, ServingEngine
    model = _build_router_model(fast)
    vocab = model.config.vocab_size
    if fast:
        n_replicas = 2
        n_steady, steady_rate = 28, 40.0
        burst_every, burst_size = 0.25, 3
        burst_prompt = (56, 80)
        slo = (8.0, 0.15)
        steady_new = (14, 22)
        uni_kw = {"max_seqs": 4, "token_budget": 24, "block_size": 8,
                  "num_blocks": 48}
        dec_budget = 6
    else:
        n_replicas = 4
        n_steady, steady_rate = 400, 90.0
        burst_every, burst_size = 0.3, 9
        burst_prompt = (160, 224)
        # long steady outputs: each request's TPOT is a mean over 24-32
        # tokens, so the per-request distribution is tight and the p99
        # separates structurally instead of by sampling noise
        steady_new = (24, 32)
        # the TPOT deadline sits BETWEEN the two fleets' observed
        # distributions (unified p50 ~9-14ms, split p99 ~9.5ms on this
        # host): a deadline both fleets trivially meet — or both blow —
        # would measure nothing
        slo = (10.0, 0.010)
        # pool sized for a full batch of burst prompts (8 x 28 pages)
        # PLUS decode growth slack: pressure without preemption thrash
        # — a preempted 28-page request recomputing through the budget
        # only to be preempted again would measure the thrash, not the
        # split
        uni_kw = {"max_seqs": 8, "token_budget": 64, "block_size": 8,
                  "num_blocks": 320}
        dec_budget = 8
    pre_kw = dict(uni_kw)
    dec_kw = dict(uni_kw, token_budget=dec_budget)
    workload = make_bursty_workload(seed + 7, n_steady, steady_rate,
                                    vocab, burst_every, burst_size,
                                    steady_new=steady_new,
                                    burst_prompt=burst_prompt)
    obs = lambda: ObsConfig(flight_steps=32, flight_requests=16)  # noqa: E731

    def unified():
        return [ServingEngine(model, EngineConfig(obs=obs(), **uni_kw))
                for _ in range(n_replicas)]

    # the split keeps the replica COUNT equal: half the fleet prefills
    # at the unified fleet's wide budget, half decodes token-thin
    n_prefill = max(n_replicas // 2, 1)

    def split():
        pre = [ServingEngine(model, EngineConfig(
            obs=obs(), role="prefill", **pre_kw))
            for _ in range(n_prefill)]
        dec = [ServingEngine(model, EngineConfig(
            obs=obs(), role="decode", **dec_kw))
            for _ in range(n_replicas - n_prefill)]
        return pre + dec

    # compile every program shape (unified/prefill width, decode width,
    # page export/import) outside the timed rows
    ServingEngineWarmup(model, uni_kw)
    ServingEngineWarmup(model, dec_kw)
    drive_fleet(make_bursty_workload(seed + 8, 4, 200.0, vocab, 0.1, 1,
                                     burst_prompt=burst_prompt),
                split(), seed, (None, None))
    rows = {}
    for name, mk in (("disagg_unified", unified),
                     ("disagg_split", split)):
        rows[name] = drive_fleet(workload, mk(), seed, slo)
        r = rows[name]
        print(f"[bench_serve] {name:15s}: {r['tokens_per_s']:8.1f} tok/s  "
              f"decode tpot p99 {r['decode_tpot_p99_s'] * 1e3:7.2f}ms  "
              f"slo {r['slo_attainment']:.2f}  goodput "
              f"{r['goodput_tokens_per_s']:8.1f} tok/s  "
              f"steps {r['engine_steps']:5d}", flush=True)
    uni, spl = rows["disagg_unified"], rows["disagg_split"]
    assert spl["output_crc32"] == uni["output_crc32"], \
        "disaggregation changed greedy output"
    assert spl["decode_tpot_p99_s"] < uni["decode_tpot_p99_s"], \
        "disaggregated fleet did not improve decode TPOT p99"
    if fast:
        # fast mode keeps loose deadlines (CPU jitter): the floor is
        # goodput parity + the TPOT win above
        assert spl["goodput_tokens"] >= uni["goodput_tokens"], \
            "disaggregated fleet lost SLO goodput"
    else:
        assert spl["goodput_tokens"] > uni["goodput_tokens"], \
            "disaggregated fleet did not improve SLO goodput under " \
            "the calibrated TPOT deadline"
    rows["disagg_workload"] = {
        "n_steady": n_steady, "steady_rate_rps": steady_rate,
        "burst_every_s": burst_every, "burst_size": burst_size,
        "burst_prompt": list(burst_prompt), "poisson": True,
        "open_loop": True, "replicas": n_replicas,
        "unified_engine": uni_kw, "prefill_engine": pre_kw,
        "decode_engine": dec_kw,
        "slo": {"ttft_deadline_s": slo[0], "tpot_deadline_s": slo[1]}}
    rows["disagg_tpot_p99_ratio"] = round(
        uni["decode_tpot_p99_s"] / max(spl["decode_tpot_p99_s"], 1e-9), 3)
    rows["disagg_goodput_ratio"] = round(
        spl["goodput_tokens"] / max(uni["goodput_tokens"], 1), 3)
    return rows


def make_swing_workload(seed: int, n_base: int, base_rate: float,
                        vocab: int, swing_start_s: float,
                        swing_dur_s: float, swing_mult: float = 10.0,
                        prompt=(6, 12), new=(10, 16)):
    """Seeded open-loop schedule with a traffic SWING: a base Poisson
    stream overlaid with a ``swing_mult``x-rate window (the ROADMAP
    item-2(c) "10x traffic swing") of identically-shaped requests.
    Every request carries a ``kind`` tag (steady|swing); the schedule
    is fixed by the seed BEFORE either fleet runs, so the fixed-max
    oracle and the autoscaled fleet face identical load."""
    rng = np.random.default_rng(seed)
    reqs = []

    def stream(rate, t_start, t_end, kind):
        t = t_start
        while True:
            t += float(rng.exponential(1.0 / rate))
            if t >= t_end:
                break
            plen = int(rng.integers(prompt[0], prompt[1] + 1))
            mnew = int(rng.integers(new[0], new[1] + 1))
            reqs.append({"arrival_s": t, "kind": kind,
                         "prompt": rng.integers(1, vocab,
                                                (plen,)).tolist(),
                         "max_new": mnew})

    stream(base_rate, 0.0, n_base / base_rate, "steady")
    stream(base_rate * swing_mult, swing_start_s,
           swing_start_s + swing_dur_s, "swing")
    reqs.sort(key=lambda r: r["arrival_s"])
    return reqs


def drive_elastic(workload, router, scaler, slo):
    """Open-loop drive of one router with an optional ``FleetAutoscaler``
    ticking between ``step_all`` passes (``scaler=None`` = the fixed
    fleet oracle). Differences from ``drive_fleet``, both forced by
    elasticity:

      * a RETIRED replica's original handles terminate with
        ``RequestFailed`` by design — each logical request resolves to
        its FINAL handle (the hand-off records are chronological, last
        replacement wins), and THAT must finish clean: zero parked or
        lost, asserted per request;
      * the artifact's cost metric is REPLICA-PASSES (live replicas
        stepped, summed over passes) — engine-step sums can't price an
        idle-but-provisioned fleet, which is exactly what autoscaling
        exists to avoid — and the crc/attainment are computed offline
        over final handles keyed by tag, so both fleets are scored by
        one placement-independent rule."""
    ttft_d, tpot_d = slo
    pending = sorted(workload, key=lambda r: r["arrival_s"])
    handles = []
    replica_passes = 0
    peak_alive = sum(router._alive)
    t0 = time.monotonic()
    i = 0
    while i < len(pending) or router.has_work():
        now = time.monotonic() - t0
        while i < len(pending) and pending[i]["arrival_s"] <= now:
            r = pending[i]
            steady = r.get("kind") != "swing"
            handles.append((r, router.submit(
                r["prompt"], max_new_tokens=r["max_new"],
                ttft_deadline=ttft_d if steady else None,
                tpot_deadline=tpot_d if steady else None, tag=i)))
            i += 1
        if router.has_work():
            router.step_all()
            replica_passes += sum(router._alive)
        elif i < len(pending):
            time.sleep(min(pending[i]["arrival_s"] - now, 0.005))
        if scaler is not None:
            scaler.control()
            peak_alive = max(peak_alive, sum(router._alive))
    wall = time.monotonic() - t0
    final = {}
    for idx, (spec, req) in enumerate(handles):
        final[idx] = (spec, req)
    for rec in router.handoffs:
        for h in rec["handles"]:
            final[h.tag["tag"]] = (final[h.tag["tag"]][0], h)
    tokens, crc = 0, 0
    lats, tpots, met, tracked = [], [], 0, 0
    for key in sorted(final):
        spec, req = final[key]
        assert req.done and req.error is None, \
            f"request {key} parked/lost across the elastic fleet"
        tokens += len(req.output)
        crc = zlib.crc32(np.asarray(req.output, np.int32).tobytes(), crc)
        lats.append((req.finished_at - t0) - spec["arrival_s"])
        if spec.get("kind") != "swing" and len(req.output) > 1 \
                and req.first_token_at is not None:
            ttft = (req.first_token_at - t0) - spec["arrival_s"]
            tpot = (req.finished_at - req.first_token_at) \
                / (len(req.output) - 1)
            tpots.append(tpot)
            # offline SLO attainment over FINAL handles: the engine
            # roll-up can't follow a request across a retire, and a
            # tombstone-reused slot drops its predecessor's counts —
            # the offline rule scores both fleets identically
            tracked += 1
            if (ttft_d is None or ttft <= ttft_d) and \
                    (tpot_d is None or tpot <= tpot_d):
                met += 1
    row = {
        "replicas_start": len([a for a in router._alive if a])
        if scaler is None else None,
        "requests": len(handles),
        "output_tokens": int(tokens),
        "wall_s": round(wall, 4),
        "tokens_per_s": round(tokens / wall, 2),
        "p99_latency_s": round(float(np.percentile(np.asarray(lats),
                                                   99)), 4),
        "steady_requests": tracked,
        "decode_tpot_p50_s": round(_order_stat(tpots, 0.50), 5),
        "decode_tpot_p99_s": round(_order_stat(tpots, 0.99), 5),
        "replica_passes": int(replica_passes),
        "peak_alive": int(peak_alive),
        "slo_attainment": round(met / tracked, 6) if tracked else 1.0,
        "output_crc32": crc,
    }
    if scaler is not None:
        row["autoscaler"] = scaler.telemetry()
        row["scale_events"] = [
            {"tick": e.tick, "rule": e.rule, "action": e.action,
             "outcome": e.outcome, "replica": e.replica}
            for e in scaler.events]
    return row


def run_elastic_pair(seed: int, fast: bool):
    """The elastic rows (ROADMAP item 2 rung c): ONE seeded 10x-swing
    schedule driven through (a) the fixed-max ORACLE — a fleet frozen
    at the autoscaler's max envelope, always-on capacity — and (b) the
    AUTOSCALED fleet: starts at the min envelope, and the
    ``FleetAutoscaler`` spawns replicas into the swing
    (``add_replica``) and retires them through ``decommission`` as it
    subsides, every retire replaying its drain manifest onto
    survivors. The claim priced by the artifact: elasticity holds the
    oracle's SLO attainment within tolerance while paying for FEWER
    replica-passes, with >= 1 spawn and >= 1 retire mid-run, zero
    requests parked or lost, and greedy output crc-equal to the
    oracle — scaling moves work, never changes tokens."""
    from paddle_tpu.serving import (AutoscalerConfig, EngineConfig,
                                    FleetAutoscaler, FleetObsConfig,
                                    ObsConfig, ReplicaRouter,
                                    ServingEngine)
    model = _build_router_model(fast)
    vocab = model.config.vocab_size
    if fast:
        n_base, base_rate = 16, 20.0
        swing_start, swing_dur = 0.25, 0.12
        min_r, max_r = 1, 3
        slo = (8.0, 2.0)               # generous CPU-fast deadlines
        kw = {"max_seqs": 4, "token_budget": 24, "block_size": 8,
              "num_blocks": 48}
        scfg = dict(cooldown=6, drain_deadline_s=0.05)
        tol = 0.15
    else:
        n_base, base_rate = 120, 40.0
        swing_start, swing_dur = 0.8, 0.5
        min_r, max_r = 2, 6
        slo = (5.0, 0.05)
        kw = {"max_seqs": 8, "token_budget": 48, "block_size": 8,
              "num_blocks": 160}
        scfg = dict(cooldown=12, drain_deadline_s=0.1)
        tol = 0.05
    workload = make_swing_workload(seed + 17, n_base, base_rate, vocab,
                                   swing_start, swing_dur)
    obs = lambda: ObsConfig(flight_steps=32, flight_requests=16)  # noqa: E731

    def mk(role=None):
        return ServingEngine(model, EngineConfig(obs=obs(), **kw))

    def mk_router(n):
        return ReplicaRouter([mk() for _ in range(n)], policy="affinity",
                             seed=seed,
                             fleet_obs=FleetObsConfig(window=256))

    ServingEngineWarmup(model, kw)
    # warm the open-loop path once (placement/replay programs compiled)
    drive_elastic(make_swing_workload(seed + 18, 4, 200.0, vocab,
                                      0.01, 0.01), mk_router(1), None,
                  (None, None))
    rows = {}
    rows["elastic_oracle"] = drive_elastic(workload, mk_router(max_r),
                                           None, slo)
    router = mk_router(min_r)
    scaler = FleetAutoscaler(router, engine_factory=mk,
                             config=AutoscalerConfig(
                                 min_replicas=min_r, max_replicas=max_r,
                                 **scfg))
    rows["elastic_autoscaled"] = drive_elastic(workload, router, scaler,
                                               slo)
    for name in ("elastic_oracle", "elastic_autoscaled"):
        r = rows[name]
        extra = ""
        if "autoscaler" in r:
            a = r["autoscaler"]
            extra = (f"  spawns {a['spawns']} retires {a['retires']} "
                     f"faults {a['faults']}")
        print(f"[bench_serve] {name:18s}: {r['tokens_per_s']:8.1f} "
              f"tok/s  slo {r['slo_attainment']:.2f}  replica-passes "
              f"{r['replica_passes']:6d}  peak {r['peak_alive']}"
              f"{extra}", flush=True)
    ora, ela = rows["elastic_oracle"], rows["elastic_autoscaled"]
    a = ela["autoscaler"]
    assert a["spawns"] >= 1 and a["retires"] >= 1, \
        f"the swing never exercised the autoscaler: {a}"
    assert ela["output_crc32"] == ora["output_crc32"], \
        "autoscaling changed greedy output"
    assert ela["replica_passes"] < ora["replica_passes"], \
        "the autoscaled fleet paid more replica-passes than always-max"
    assert ela["slo_attainment"] >= ora["slo_attainment"] - tol, \
        (f"autoscaled SLO attainment {ela['slo_attainment']} fell past "
         f"tolerance {tol} under the oracle's {ora['slo_attainment']}")
    rows["elastic_workload"] = {
        "n_base": n_base, "base_rate_rps": base_rate,
        "swing_start_s": swing_start, "swing_dur_s": swing_dur,
        "swing_mult": 10.0, "poisson": True, "open_loop": True,
        "engine": kw, "envelope": {"min": min_r, "max": max_r},
        "slo": {"ttft_deadline_s": slo[0], "tpot_deadline_s": slo[1]}}
    rows["elastic_replica_pass_ratio"] = round(
        ela["replica_passes"] / max(ora["replica_passes"], 1), 3)
    rows["elastic_slo_delta"] = round(
        ela["slo_attainment"] - ora["slo_attainment"], 6)
    return rows


def _build_router_model(fast: bool):
    """The router rows' own tiny model: same geometry as the fast bench
    model but with a LONGER position budget in full mode — the scale-out
    rows measure shared-prefix prefill economics, and a system-prompt-
    sized prefix needs the context room."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(11)
    cfg = LlamaConfig.tiny(vocab_size=128, hidden_size=32, layers=2,
                           heads=4, kv_heads=2,
                           seq=128 if fast else 256)
    cfg.use_flash_attention = False
    return LlamaForCausalLM(cfg)


def run_router_pair(seed: int, fast: bool):
    """The scale-out rows: one shared-prefix open-loop schedule driven
    through (a) a single engine, (b) N replicas under RANDOM routing,
    (c) N replicas under PREFIX-AFFINITY routing — identical per-engine
    config (a replica is one chip; scale-out adds chips, so aggregate
    pool/cache capacity is exactly what the fleet buys). The per-engine
    pool holds its affinity SHARE of the prefix working set but not all
    of it: under affinity routing every prefix stays resident on its
    home replica, while the single engine (and every replica under
    random routing) keeps evicting and re-prefilling — the honest
    mechanism behind the tokens/s scaling the artifact pins (compute
    here is one CPU core either way; on real silicon the per-chip
    parallelism multiplies on top)."""
    model = _build_router_model(fast)
    vocab = model.config.vocab_size
    if fast:
        n_replicas, n_requests, rate = 2, 48, 2000.0
        n_prefixes, prefix_len = 8, 96
        engine_kw = {"max_seqs": 4, "token_budget": 24, "block_size": 8,
                     "num_blocks": 64}
    else:
        n_replicas, n_requests, rate = 4, 1500, 400.0
        n_prefixes, prefix_len = 32, 216
        engine_kw = {"max_seqs": 8, "token_budget": 32, "block_size": 8,
                     "num_blocks": 240}
    workload = make_shared_prefix_workload(seed + 3, n_requests, rate,
                                           vocab, n_prefixes, prefix_len)
    # compile the one engine program (the pool shape is part of it)
    # OUTSIDE every timed row — the single-engine row must not be the
    # one that happens to pay the jit cold start
    ServingEngineWarmup(model, engine_kw)
    rows = {}
    for name, n, policy in (("router_single", 1, "least_loaded"),
                            ("router_random", n_replicas, "random"),
                            ("router_affinity", n_replicas, "affinity")):
        rows[name] = drive_router(model, workload, n, policy, engine_kw,
                                  seed)
        r = rows[name]
        print(f"[bench_serve] {name:15s}: {r['tokens_per_s']:8.1f} tok/s  "
              f"p99 {r['p99_latency_s']:7.3f}s  "
              f"steps {r['engine_steps']:5d}  "
              f"prefix hit {r['prefix_hit_token_rate'] * 100:5.1f}%",
              flush=True)
    aff, rnd, one = (rows["router_affinity"], rows["router_random"],
                     rows["router_single"])
    # every policy must deliver identical greedy tokens — routing moves
    # requests, it never changes what the model says
    assert aff["output_crc32"] == rnd["output_crc32"] \
        == one["output_crc32"], "routing changed greedy output"
    assert aff["prefix_hit_token_rate"] > rnd["prefix_hit_token_rate"], \
        "prefix-affinity routing did not beat random on cache hit rate"
    rows["router_workload"] = {
        "n_requests": n_requests, "rate_rps": rate, "poisson": True,
        "open_loop": True, "n_prefixes": n_prefixes,
        "prefix_len": prefix_len, "replicas": n_replicas,
        "engine": engine_kw}
    rows["router_vs_single"] = round(
        aff["tokens_per_s"] / max(one["tokens_per_s"], 1e-9), 3)
    rows["affinity_vs_random"] = round(
        aff["tokens_per_s"] / max(rnd["tokens_per_s"], 1e-9), 3)
    return rows


def drive_lossy(workload, engines, seed: int, slo, transport_cfg,
                membership_cfg, plan):
    """Open-loop drive of a disaggregated fleet over the fault-domain
    transport, optionally under a seeded lossy-link chaos plan. Unlike
    ``drive_fleet`` this is failure-tolerant: the row REPORTS terminal
    failures instead of asserting them away, because the no-dedup/
    no-lease baseline row exists to show what the reliability
    machinery averts."""
    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import ReplicaRouter
    router = ReplicaRouter(engines, policy="affinity", seed=seed,
                           transport=transport_cfg,
                           membership=membership_cfg)
    ttft_d, tpot_d = slo
    pending = sorted(workload, key=lambda r: r["arrival_s"])
    handles = []
    if plan is not None:
        chaos.install_plan(plan)
    t0 = time.monotonic()
    try:
        i = 0
        while i < len(pending) or router.has_work():
            now = time.monotonic() - t0
            while i < len(pending) and pending[i]["arrival_s"] <= now:
                r = pending[i]
                handles.append((r, router.submit(
                    r["prompt"], max_new_tokens=r["max_new"],
                    ttft_deadline=ttft_d, tpot_deadline=tpot_d,
                    tag=i)))
                i += 1
            if router.has_work():
                router.step_all()
            elif i < len(pending):
                time.sleep(min(pending[i]["arrival_s"] - now, 0.005))
    finally:
        if plan is not None:
            chaos.clear_plan()
    wall = time.monotonic() - t0
    tokens, crc, failed, parked = 0, 0, 0, 0
    for spec, req in handles:
        if not req.done:
            parked += 1
        elif req.error is not None:
            failed += 1
        else:
            tokens += len(req.output)
            crc = zlib.crc32(np.asarray(req.output, np.int32).tobytes(),
                             crc)
    tel = router.telemetry()
    slo_agg = tel["fleet"].get("slo", {})
    tp = tel["router"]["transport"]
    return {
        "replicas": len(engines),
        "requests": len(handles),
        "parked": parked,
        "failed": failed,
        "output_tokens": int(tokens),
        "wall_s": round(wall, 4),
        "tokens_per_s": round(tokens / wall, 2),
        "slo_attainment": slo_agg.get("attainment"),
        "goodput_tokens": slo_agg.get("goodput_tokens", 0),
        "kv_handoffs": dict(router.kv_handoffs),
        "transport": {"counters": tp["counters"],
                      "retries_by_site": tp["retries_by_site"],
                      "giveups_by_site": tp["giveups_by_site"]},
        "lease_transitions":
            tel["router"]["membership"]["transition_counts"]
            if tel["router"]["membership"] else None,
        "output_crc32": crc,
    }


def run_lossy_pair(seed: int, fast: bool):
    """The fault-domain rows: ONE seeded open-loop schedule on a 1
    prefill + 2 decode fleet whose cross-replica channels ride the
    chaos-injectable transport, driven three ways — (a) fault-free
    (the oracle crc), (b) a 5% drop + 5% dup + 5% delay plan against
    the FULL reliability stack (dedup window, ack-tracked retransmits,
    lease membership), and (c) the same plan against a no-dedup/
    no-lease baseline (``max_attempts=1, dedup_window=0``,
    membership disarmed). The floor: the resilient row absorbs the
    loss with zero parked/failed requests, crc equal to the fault-free
    oracle, and SLO attainment >= 0.95; the baseline row converges
    only because the engine's duplicate-import guard and the give-up
    recompute ladder avert the double-decode/wedge — its extra aborts
    and recomputes are the measured cost of running lossy links
    without the transport's reliability machinery."""
    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import (EngineConfig, ObsConfig,
                                    ServingEngine, TransportConfig)
    model = _build_router_model(fast)
    vocab = model.config.vocab_size
    if fast:
        n_requests, rate = 18, 60.0
        pre_kw = {"max_seqs": 2, "token_budget": 16, "block_size": 8,
                  "num_blocks": 64}
        dec_kw = {"max_seqs": 4, "token_budget": 8, "block_size": 8,
                  "num_blocks": 64}
        slo = (8.0, 2.0)               # generous CPU-fast deadlines
    else:
        n_requests, rate = 96, 60.0
        pre_kw = {"max_seqs": 4, "token_budget": 32, "block_size": 8,
                  "num_blocks": 128}
        dec_kw = {"max_seqs": 8, "token_budget": 8, "block_size": 8,
                  "num_blocks": 128}
        slo = (4.0, 0.5)
    workload = make_workload(seed + 11, n_requests, rate, vocab)

    def mk_fleet():
        obs = lambda: ObsConfig(flight_steps=32,  # noqa: E731
                                flight_requests=16)
        pre = ServingEngine(model, EngineConfig(role="prefill",
                                                obs=obs(), **pre_kw))
        dec = [ServingEngine(model, EngineConfig(role="decode",
                                                 obs=obs(), **dec_kw))
               for _ in range(2)]
        return [pre] + dec

    def mk_plan():
        return (chaos.FaultPlan(seed=seed)
                .add("transport.send", "error", "drop", prob=0.05)
                .add("transport.send", "error", "dup", prob=0.05)
                .add("transport.send", "delay", "1", prob=0.05))

    ServingEngineWarmup(model, pre_kw)
    ServingEngineWarmup(model, dec_kw)
    drive_lossy(make_workload(seed + 12, 4, 200.0, vocab), mk_fleet(),
                seed, (None, None), True, True, None)      # handoff warm

    rows = {}
    specs = (
        ("lossy_faultfree", TransportConfig(), True, None),
        ("lossy_resilient", TransportConfig(), True, mk_plan()),
        ("lossy_naive", TransportConfig(max_attempts=1, dedup_window=0),
         None, mk_plan()),
    )
    for name, cfg, member, plan in specs:
        rows[name] = drive_lossy(workload, mk_fleet(), seed, slo, cfg,
                                 member, plan)
        r = rows[name]
        c = r["transport"]["counters"]
        print(f"[bench_serve] {name:15s}: {r['tokens_per_s']:8.1f} tok/s"
              f"  slo {r['slo_attainment']:.2f}  parked {r['parked']}  "
              f"failed {r['failed']}  pages {r['kv_handoffs']['pages']}"
              f"  recompute {r['kv_handoffs']['recompute']}  dropped "
              f"{c['dropped']}  deduped {c['deduped']}  retransmits "
              f"{c['retransmits']}  giveups {c['giveups']}", flush=True)

    oracle, res, naive = (rows["lossy_faultfree"],
                          rows["lossy_resilient"], rows["lossy_naive"])
    assert oracle["parked"] == 0 and oracle["failed"] == 0
    assert oracle["transport"]["counters"]["retransmits"] == 0, \
        "fault-free transport retransmitted — the clean path regressed"
    rc = res["transport"]["counters"]
    assert rc["dropped"] + rc["duplicate"] + rc["delayed"] > 0, \
        "the lossy plan never fired — the bench has no teeth"
    assert res["parked"] == 0 and res["failed"] == 0, \
        "resilient row parked/failed requests on lossy links"
    assert res["output_crc32"] == oracle["output_crc32"], \
        "lossy-resilient outputs diverged from the fault-free oracle"
    assert res["slo_attainment"] >= 0.95, \
        f"lossy SLO attainment {res['slo_attainment']} < 0.95"
    # the baseline converges CORRECTLY only because the engine guard
    # and the recompute ladder catch what the transport no longer does
    assert naive["parked"] == 0, "naive baseline wedged (parked)"
    assert naive["output_crc32"] == oracle["output_crc32"] or \
        naive["failed"] > 0, \
        "naive baseline corrupted outputs without reporting failures"
    rows["lossy_workload"] = {
        "n_requests": n_requests, "rate_rps": rate, "poisson": True,
        "open_loop": True, "replicas": 3,
        "prefill_engine": pre_kw, "decode_engine": dec_kw,
        "fault_plan": {"drop": 0.05, "dup": 0.05, "delay": 0.05},
        "naive_transport": {"max_attempts": 1, "dedup_window": 0,
                            "membership": False},
        "slo": {"ttft_deadline_s": slo[0], "tpot_deadline_s": slo[1]}}
    rows["lossy_slo_delta"] = round(
        res["slo_attainment"] - (naive["slo_attainment"] or 0.0), 3)
    rows["lossy_averted"] = {
        "naive_recomputes": naive["kv_handoffs"]["recompute"],
        "naive_giveups": naive["transport"]["counters"]["giveups"],
        "naive_duplicates_delivered":
            naive["transport"]["counters"]["duplicate"],
        "resilient_deduped": rc["deduped"],
        "resilient_retransmits": rc["retransmits"]}
    return rows


def drive_chaos(model, workload, engine_kw: dict, resilient: bool,
                fault_at, seed: int, slo, max_waiting: int):
    """One overload+fault run. ``resilient=False`` reproduces the PR 6
    failure mode: the injected ``serve.engine_step`` error escapes
    ``step()`` and the driver stops (requests park forever — counted,
    not waited for). ``resilient=True`` arms containment + SLO-aware
    shed: the fault is retried, overload is refused at ``submit()``,
    and the run drains completely. Both see the identical seeded
    schedule and fault plan."""
    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving import (AdmissionRejected, EngineConfig,
                                    ObsConfig, ResilienceConfig,
                                    ServingEngine)
    res_cfg = ResilienceConfig(max_step_retries=3, nan_guard=True,
                               max_waiting=max_waiting,
                               backpressure="shed") if resilient else False
    eng = ServingEngine(model, EngineConfig(
        policy="continuous", resilience=res_cfg,
        obs=ObsConfig(flight_steps=64, flight_requests=32), **engine_kw))
    ttft_d, tpot_d = slo
    plan = chaos.FaultPlan(seed=seed).add("serve.engine_step", "error",
                                          at=fault_at)
    chaos.install_plan(plan)
    pending = sorted(workload, key=lambda r: r["arrival_s"])
    handles, shed, failed = [], 0, 0
    wedged = False
    t0 = time.monotonic()
    i = 0
    try:
        while i < len(pending) or eng.has_work():
            now = time.monotonic() - t0
            while i < len(pending) and pending[i]["arrival_s"] <= now:
                r = pending[i]
                i += 1
                try:
                    handles.append((r, eng.submit(
                        r["prompt"], max_new_tokens=r["max_new"],
                        ttft_deadline=ttft_d, tpot_deadline=tpot_d)))
                except AdmissionRejected:
                    shed += 1
            if wedged:
                if i >= len(pending):
                    break       # nobody will ever serve the rest
                time.sleep(0.001)
                continue
            if eng.has_work():
                try:
                    eng.step()
                except Exception:
                    # the PR 6 wedge: the driver thread dies with its
                    # RUNNING requests parked — keep accepting arrivals
                    # (the queue is unbounded) but never step again
                    wedged = True
            elif i < len(pending):
                time.sleep(min(pending[i]["arrival_s"] - now, 0.005))
    finally:
        chaos.clear_plan()
    wall = time.monotonic() - t0
    finished = parked = tokens = 0
    for _, req in handles:
        if req.done and req.error is None:
            finished += 1
            tokens += len(req.output)
        elif req.done:
            failed += 1
        else:
            parked += 1
    tel = eng.telemetry()
    goodput = tel["slo"]["goodput_tokens"]
    row = {
        "resilient": resilient,
        "requests": len(handles) + shed,
        "accepted": len(handles),
        "finished": finished,
        "parked": parked,
        "failed": failed,
        "shed": shed,
        "wedged": wedged,
        "engine_step_faults": getattr(eng, "step_faults", 0),
        "output_tokens": int(tokens),
        "wall_s": round(wall, 4),
        "tokens_per_s": round(tokens / wall, 2),
        "slo_attainment": tel["slo"]["attainment"],
        "goodput_tokens": goodput,
        "goodput_tokens_per_s": round(goodput / wall, 2),
    }
    if resilient:
        row["resilience"] = tel["resilience"]
    return row


def run_chaos_pair(model, seed: int, fast: bool, engine_kw: dict):
    """The fault+overload schedule and both rows. Overload: arrivals at
    several times the engine's drain rate; fault: one seeded
    ``serve.engine_step`` error once the batch is saturated."""
    vocab = model.config.vocab_size
    if fast:
        n_requests, rate, max_waiting = 24, 400.0, 6
        slo = (2.0, 2.0)
    else:
        n_requests, rate, max_waiting = 64, 120.0, 12
        slo = (2.0, 0.5)
    workload = make_workload(seed + 2, n_requests, rate, vocab)
    fault_at = (6,)
    rows = {}
    for name, resilient in (("chaos_baseline", False),
                            ("chaos_resilient", True)):
        rows[name] = drive_chaos(model, workload, engine_kw, resilient,
                                 fault_at, seed, slo, max_waiting)
        r = rows[name]
        print(f"[bench_serve] {name:15s}: finished {r['finished']:3d}/"
              f"{r['requests']}  parked {r['parked']:3d}  "
              f"shed {r['shed']:3d}  goodput "
              f"{r['goodput_tokens_per_s']:.1f} tok/s  "
              f"wedged={r['wedged']}", flush=True)
    base, res = rows["chaos_baseline"], rows["chaos_resilient"]
    assert base["wedged"] and base["parked"] > 0, \
        "baseline did not wedge — the chaos schedule lost its teeth"
    assert not res["wedged"] and res["parked"] == 0, \
        f"resilient engine parked requests: {res}"
    assert res["goodput_tokens"] > base["goodput_tokens"], \
        "resilience did not protect goodput under fault+overload"
    rows["chaos_workload"] = {"n_requests": n_requests, "rate_rps": rate,
                              "poisson": True, "open_loop": True,
                              "fault": {"site": "serve.engine_step",
                                        "at": list(fault_at)},
                              "max_waiting": max_waiting,
                              "slo": {"ttft_deadline_s": slo[0],
                                      "tpot_deadline_s": slo[1]}}
    return rows


def run_bench(fast: bool = True, seed: int = 0, tag: str = "fast",
              n_requests: int = None, rate: float = None,
              out_path: str = None, spec: bool = False,
              num_draft_tokens: int = 4, slo=None, chaos: bool = False,
              router: bool = False, disagg: bool = False,
              elastic: bool = False, lossy: bool = False):
    model = _build_model(fast)
    vocab = model.config.vocab_size
    if fast:
        n_requests = n_requests or 24
        rate = rate or 200.0           # arrivals outrun a tiny CPU model
        engine_kw = {"max_seqs": 4, "token_budget": 24, "block_size": 8}
        slo = slo or (5.0, 2.0)        # generous CPU-fast-path deadlines
    else:
        n_requests = n_requests or 64
        rate = rate or 30.0
        engine_kw = {"max_seqs": 8, "token_budget": 64, "block_size": 16}
        slo = slo or (2.0, 0.5)
    workload = make_workload(seed, n_requests, rate, vocab)

    # warm the jit cache outside the timed runs (all rows share the one
    # compiled program: same decoder, same static shapes — a speculative
    # verify batch is the same packed [token_budget] shape)
    warm = ServingEngineWarmup(model, engine_kw)
    rows = {}
    for policy in ("static", "continuous"):
        rows[policy] = drive(model, workload, policy, engine_kw, slo=slo)
        print(f"[bench_serve] {policy:11s}: "
              f"{rows[policy]['tokens_per_s']:8.1f} tok/s  "
              f"p99 {rows[policy]['p99_latency_s']:.3f}s  "
              f"slo {rows[policy]['slo_attainment']:.2f}  "
              f"goodput {rows[policy]['goodput_tokens_per_s']:.1f} tok/s  "
              f"steps {rows[policy]['engine_steps']}", flush=True)

    from paddle_tpu.utils import chip
    device = chip.device_summary()
    result = {
        "bench": "serve",
        "schema_version": 2,
        "tag": tag,
        "seed": seed,
        # every number below was taken on THIS backend; on "cpu" the
        # rates compare scheduling policies and are not device metrics
        "device": device,
        "device_kind": device["kind"],
        "fast": bool(fast),
        "slo": {"ttft_deadline_s": slo[0], "tpot_deadline_s": slo[1]},
        "model": {"hidden": model.config.hidden_size,
                  "layers": model.config.num_hidden_layers,
                  "heads": model.config.num_attention_heads,
                  "kv_heads": model.config.num_key_value_heads,
                  "vocab": vocab},
        "workload": {"n_requests": n_requests, "rate_rps": rate,
                     "poisson": True, "open_loop": True},
        "engine": engine_kw,
        "static": rows["static"],
        "continuous": rows["continuous"],
        "vs_static": round(rows["continuous"]["tokens_per_s"]
                           / max(rows["static"]["tokens_per_s"], 1e-9), 3),
        "warmup_steps": warm,
    }

    if spec:
        # speculation pair: same continuous engine, one seeded
        # repetitive/code-like workload, with and without the n-gram
        # self-drafting drafter. Greedy verification keeps output
        # bit-identical, so identical output_crc32 is asserted here.
        spec_load = make_repetitive_workload(seed + 1, n_requests, rate,
                                             vocab)
        spec_kw = {"spec_method": "ngram",
                   "num_draft_tokens": int(num_draft_tokens)}
        for name, skw in (("nonspec", None), ("spec", spec_kw)):
            rows[name] = drive(model, spec_load, "continuous", engine_kw,
                               spec_kw=skw, slo=slo)
            extra = (f"  accept {rows[name]['accept_rate']:.2f}"
                     if skw else "")
            print(f"[bench_serve] {name:11s}: "
                  f"{rows[name]['tokens_per_s']:8.1f} tok/s  "
                  f"p99 {rows[name]['p99_latency_s']:.3f}s  "
                  f"steps {rows[name]['engine_steps']}{extra}", flush=True)
        assert rows["spec"]["output_crc32"] == \
            rows["nonspec"]["output_crc32"], \
            "speculative output diverged from non-speculative greedy"
        result["spec_workload"] = {"n_requests": n_requests,
                                   "rate_rps": rate, "poisson": True,
                                   "open_loop": True, "repetitive": True}
        result["nonspec"] = rows["nonspec"]
        result["spec"] = rows["spec"]
        result["vs_nonspec"] = round(
            rows["spec"]["tokens_per_s"]
            / max(rows["nonspec"]["tokens_per_s"], 1e-9), 3)
    if chaos:
        # resilience pair: identical fault+overload schedule, PR 6
        # baseline behavior (wedge) vs the armed resilience plane
        crows = run_chaos_pair(model, seed, fast, engine_kw)
        result["chaos_workload"] = crows["chaos_workload"]
        result["chaos_baseline"] = crows["chaos_baseline"]
        result["chaos_resilient"] = crows["chaos_resilient"]
        result["chaos_goodput_ratio"] = round(
            crows["chaos_resilient"]["goodput_tokens"]
            / max(crows["chaos_baseline"]["goodput_tokens"], 1), 3)
    if router:
        # scale-out rows: single engine vs N-replica router (random and
        # prefix-affinity). The router rows run on their own tiny model
        # even in full mode: the thousands-of-requests open-loop
        # schedule is what exercises the fleet, and the measured
        # quantity (aggregate prefix-cache capacity + placement policy)
        # is model-size-free.
        rrows = run_router_pair(seed, fast)
        for key in ("router_workload", "router_single", "router_random",
                    "router_affinity", "router_vs_single",
                    "affinity_vs_random"):
            result[key] = rrows[key]
    if disagg:
        # disaggregation rows: equal-size unified vs prefill/decode
        # split fleets on one bursty-prompt schedule — decode TPOT p99
        # and SLO goodput are the headline, crc equality the invariant
        drows = run_disagg_pair(seed, fast)
        for key in ("disagg_workload", "disagg_unified", "disagg_split",
                    "disagg_tpot_p99_ratio", "disagg_goodput_ratio"):
            result[key] = drows[key]
    if elastic:
        # elastic rows: one seeded 10x-swing schedule, fixed-max oracle
        # vs the autoscaled fleet — SLO held within tolerance at fewer
        # replica-passes, >= 1 spawn + retire, crc equality, zero parked
        erows = run_elastic_pair(seed, fast)
        for key in ("elastic_workload", "elastic_oracle",
                    "elastic_autoscaled", "elastic_replica_pass_ratio",
                    "elastic_slo_delta"):
            result[key] = erows[key]
    if lossy:
        # fault-domain rows: one lossy-link schedule, full reliability
        # stack vs the no-dedup/no-lease baseline — crc equal to the
        # fault-free oracle and SLO >= 0.95 the floor, the baseline's
        # extra aborts/recomputes the measured cost
        lrows = run_lossy_pair(seed, fast)
        for key in ("lossy_workload", "lossy_faultfree",
                    "lossy_resilient", "lossy_naive", "lossy_slo_delta",
                    "lossy_averted"):
            result[key] = lrows[key]
    if out_path is None:
        out_path = os.path.join(HERE, f"BENCH_SERVE_{tag}.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, out_path)          # atomic: a killed run can't truncate
    ratios = f"vs_static={result['vs_static']}"
    if spec:
        ratios += f" vs_nonspec={result['vs_nonspec']}"
    if router:
        ratios += (f" router_vs_single={result['router_vs_single']}"
                   f" affinity_vs_random={result['affinity_vs_random']}")
    if disagg:
        ratios += (f" disagg_tpot_p99_ratio="
                   f"{result['disagg_tpot_p99_ratio']}"
                   f" disagg_goodput_ratio="
                   f"{result['disagg_goodput_ratio']}")
    if elastic:
        ratios += (f" elastic_replica_pass_ratio="
                   f"{result['elastic_replica_pass_ratio']}"
                   f" elastic_slo_delta={result['elastic_slo_delta']}")
    if lossy:
        ratios += (f" lossy_slo="
                   f"{result['lossy_resilient']['slo_attainment']}"
                   f" lossy_slo_delta={result['lossy_slo_delta']}")
    print(f"[bench_serve] {ratios}  -> {out_path}", flush=True)
    return result


def ServingEngineWarmup(model, engine_kw):
    """Compile the engine step (and generate-path jits the oracle tests
    share) before any timer starts; returns steps used."""
    from paddle_tpu.serving import EngineConfig, ServingEngine
    eng = ServingEngine(model, EngineConfig(**engine_kw))
    eng.generate_batch([[1, 2, 3]], max_new_tokens=2)
    return eng.steps


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="tiny seeded tier-1 mode")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tag", default=None,
                    help="artifact tag (BENCH_SERVE_<tag>.json)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--spec", action="store_true",
                    help="add the speculative vs non-speculative pair on "
                         "a repetitive workload")
    ap.add_argument("--chaos", action="store_true",
                    help="add the resilience pair: seeded fault+overload "
                         "schedule, PR 6 baseline (wedges) vs the armed "
                         "resilience plane (contains, sheds, finishes)")
    ap.add_argument("--router", action="store_true",
                    help="add the scale-out rows: single engine vs an "
                         "N-replica ReplicaRouter under random and "
                         "prefix-affinity routing on a shared-prefix "
                         "open-loop workload")
    ap.add_argument("--disagg", action="store_true",
                    help="add the disaggregation rows: equal-size "
                         "unified vs prefill/decode split fleets "
                         "(KV-page handoff over the router) on a "
                         "bursty-prompt schedule")
    ap.add_argument("--elastic", action="store_true",
                    help="add the elastic rows: fixed-max oracle vs the "
                         "FleetAutoscaler-driven fleet on a seeded "
                         "10x-traffic-swing schedule (spawn into the "
                         "swing, lossless retire out of it)")
    ap.add_argument("--lossy", action="store_true",
                    help="add the fault-domain rows: a seeded 5%% "
                         "drop+dup+delay plan against the full "
                         "transport reliability stack vs the no-dedup/"
                         "no-lease baseline")
    ap.add_argument("--draft-tokens", type=int, default=4,
                    help="per-sequence draft budget k for --spec")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    tag = args.tag or ("fast" if args.fast else "run")
    from paddle_tpu.utils import chip
    chip.enable_compile_cache()
    res = run_bench(fast=args.fast, seed=args.seed, tag=tag,
                    n_requests=args.requests, rate=args.rate,
                    out_path=args.out, spec=args.spec,
                    num_draft_tokens=args.draft_tokens, chaos=args.chaos,
                    router=args.router, disagg=args.disagg,
                    elastic=args.elastic, lossy=args.lossy)
    ok = res["vs_static"] > 1.0 and res.get("vs_nonspec", 2.0) > 1.0 \
        and res.get("router_vs_single", 2.0) > 1.0 \
        and res.get("disagg_tpot_p99_ratio", 2.0) > 1.0 \
        and res.get("elastic_replica_pass_ratio", 0.5) < 1.0 \
        and (res.get("lossy_resilient") is None
             or res["lossy_resilient"]["slo_attainment"] >= 0.95)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
