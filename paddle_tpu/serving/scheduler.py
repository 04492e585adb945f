"""Continuous-batching scheduler: admit/evict at every decode step.

The static micro-batcher (``inference.BatchingServer``) stacks requests
into a batch and runs it to completion — every early-finishing sequence
idles its batch slot until the longest one drains, which is where decode
throughput goes to die. This scheduler instead rebuilds the batch EVERY
step under one token budget:

  * running decode sequences get one token-slot each, first (a decode
    step is never starved by prefill);
  * leftover budget feeds prefill CHUNKS of running-but-not-yet-prefilled
    and freshly admitted requests, strictly FIFO by arrival — so prefill
    and decode share one packed ragged batch (the shape ragged paged
    attention serves) and no request waits behind a later arrival
    (no-starvation invariant, test-pinned);
  * finished sequences are evicted at the step boundary, their pages
    released to the pool (prefix pages parked for reuse);
  * when the pool cannot grow a decode sequence, the MOST RECENTLY
    admitted running request is preempted (pages released, re-queued at
    the waiting front for recompute with its generated tokens appended
    to the prompt) — FIFO order again decides who survives pressure;
  * speculative DRAFT tokens (``serving.speculative``) come LAST: only
    budget left over after decode, prefill, and admission turns into
    per-sequence verify chunks, so speculation accelerates idle decode
    capacity and yields to real work under load.

``role="prefill"`` (disaggregated serving) re-purposes the same budget
machinery: the WHOLE budget feeds chunked prefill, a chunk never
includes the sequence's final pending token (feeding it would SAMPLE —
the decode pool's job), and a request whose prompt is fully cached
minus that token sweeps into ``prefill_done`` for the engine's KV-page
hand-off. ``role="decode"`` engines keep the full scheduler (the
recompute fallback prefills here); their admission honors pages a
KV-page import pre-attached instead of re-matching the prefix cache.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
import warnings
from typing import Callable, List, Optional, Sequence

from ..resilience import chaos
from .kv_pool import KVBlockPool, PoolExhausted

_req_ids = itertools.count()

# Request lifecycle states (HANDOFF: prefill complete on a prefill-role
# engine, KV pages awaiting export to a decode-pool replica)
WAITING, RUNNING, FINISHED = "waiting", "running", "finished"
HANDOFF = "handoff"

# The canonical lifecycle table: {from_state: (to_state, ...)} — the
# ONLY legal ``req.state`` transitions, with "new" as the pre-lifecycle
# pseudo-state a fresh Request is born from. Ground truth for the CCY201
# static rule (analysis/concur_rules.py reads this with ast.literal_eval
# — keep it a pure literal) and for the static==runtime pin in
# tests/test_concurcheck.py.
#   waiting -> running    admission (schedule)
#   waiting -> handoff    prefill-complete sweep straight off the queue
#   waiting -> finished   fail_request on a never-admitted request
#   running -> waiting    preemption / step-fault requeue (recompute)
#   running -> handoff    prefill-complete sweep
#   running -> finished   finish (eos / budget) or terminal failure
#   handoff -> waiting    decode-side import / recompute adoption
#   handoff -> finished   fail_request before the hand-off landed
REQUEST_TRANSITIONS = {
    "new": ("waiting",),
    "waiting": ("running", "handoff", "finished"),
    "running": ("waiting", "handoff", "finished"),
    "handoff": ("waiting", "finished"),
    "finished": (),
}


class Request:
    """One generation request inside the engine.

    ``seq`` is the token stream fed to the model: the prompt, then each
    sampled token as it is read back. ``pos`` counts how many of those a
    launched step has fed to the KV cache; the request is in its decode
    phase once ``pos == seq_len - 1`` (one pending token to feed). A
    token sampled by the step in flight and not read yet counts in
    ``seq_len`` and not in ``seq``: ``unread`` says where it is. After a
    preemption ``pos`` rolls back to the prefix-cached depth and the
    generated tokens ride along in ``seq`` for recompute."""

    def __init__(self, prompt: Sequence[int], max_new_tokens: int = 32,
                 eos_id: Optional[int] = None,
                 on_token: Optional[Callable[[int], None]] = None,
                 stream: bool = False,
                 ttft_deadline: Optional[float] = None,
                 tpot_deadline: Optional[float] = None,
                 tag=None):
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        for name, d in (("ttft_deadline", ttft_deadline),
                        ("tpot_deadline", tpot_deadline)):
            if d is not None and d <= 0:
                raise ValueError(f"{name} must be > 0 seconds, got {d}")
        self.rid = next(_req_ids)
        self.prompt: List[int] = [int(t) for t in prompt]
        self.seq: List[int] = list(self.prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.on_token = on_token
        # SLO deadlines (seconds; None = untracked): TTFT is submit ->
        # first token, TPOT is the mean per-output-token latency after
        # the first. serving/obs.py accounts violations and goodput.
        self.ttft_deadline = None if ttft_deadline is None \
            else float(ttft_deadline)
        self.tpot_deadline = None if tpot_deadline is None \
            else float(tpot_deadline)
        # opaque caller identity, carried through drain manifests and
        # restart replay (a router's affinity key, a drill's stable
        # request index) — never read by the engine itself
        self.tag = tag
        self.output: List[int] = []
        self.state = WAITING
        self.slot: Optional[int] = None
        self.pages: List[int] = []
        self.pos = 0                  # tokens already in the KV cache
        # (step, row) of the launched step whose sample is this sequence's
        # next token, until the engine reads it back (None otherwise): the
        # next plan feeds that row's token on the device
        self.unread = None
        self.n_prefix = 0             # of which reused from the prefix cache
        self.preemptions = 0
        self.step_retries = 0         # contained step-fault requeues
        self.error: Optional[BaseException] = None
        self.arrival = time.monotonic()
        # when a disaggregated hand-off landed this request on its
        # decode replica (None otherwise): the decode engine's service
        # -time evidence clocks from here, not from the original submit,
        # so prefill time never pollutes the decode pool's estimates
        self.handoff_at: Optional[float] = None
        # when the scheduler first gave this request an entry of a plan
        # (kept across preemption: the queue wait is counted once)
        self.first_planned_at: Optional[float] = None
        # the engine's launch its first token waits on first (set by
        # ``ServingEngine.submit``): ``serve.emit`` counts from it
        self.launch_mark: Optional[int] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.finish_reason: Optional[str] = None
        self.trace = None             # RequestTrace when the obs plane is on
        self._done = threading.Event()
        self._stream: Optional["queue.Queue"] = queue.Queue() if stream \
            else None

    # -- client-side API ------------------------------------------------------
    def result(self, timeout: Optional[float] = None) -> List[int]:
        """The full output token list; raises the request's terminal
        error (``serving.resilience.RequestFailed``) if the engine gave
        up on it — a failed request resolves, it never hangs."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.rid} not finished")
        if self.error is not None:
            raise self.error
        return list(self.output)

    def stream(self):
        """Yield tokens as they are generated (requires stream=True).
        A failed request's stream raises its terminal error after the
        last delivered token instead of blocking forever."""
        if self._stream is None:
            raise ValueError("request was not created with stream=True")
        while True:
            tok = self._stream.get()
            if tok is None:
                return
            if isinstance(tok, BaseException):
                raise tok
            yield tok

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def seq_len(self) -> int:
        """``len(seq)``, and the token sampled on the device and not read
        back yet where one is."""
        return len(self.seq) + (self.unread is not None)

    # -- engine-side helpers --------------------------------------------------
    def emit(self, tok: int) -> None:
        self.output.append(int(tok))
        self.seq.append(int(tok))
        if self.on_token is not None:
            self.on_token(int(tok))
        if self._stream is not None:
            self._stream.put(int(tok))

    def finish(self) -> None:
        self.state = FINISHED
        self.finished_at = time.monotonic()
        if self._stream is not None:
            self._stream.put(None)
        self._done.set()

    def fail(self, exc: BaseException) -> None:
        """Resolve this request with a terminal error: ``result()``
        raises it, ``stream()`` raises it after the delivered tokens.
        Idempotent against a racing finish — the first terminal state
        wins."""
        if self._done.is_set():
            return
        self.error = exc
        self.finish_reason = "error"
        self.state = FINISHED
        self.finished_at = time.monotonic()
        if self._stream is not None:
            self._stream.put(exc)
        self._done.set()


class StepEntry:
    """One request's contribution to a packed step: feed
    ``seq[start:start+n]`` at positions ``start..start+n-1``, then any
    ``draft`` tokens (speculative proposals, NOT part of ``seq``) at
    positions ``start+n..start+n+len(draft)-1`` — the verify chunk."""

    __slots__ = ("req", "start", "n", "draft")

    def __init__(self, req: Request, start: int, n: int,
                 draft: Sequence[int] = ()):
        self.req = req
        self.start = start
        self.n = n
        self.draft = tuple(draft)

    @property
    def samples(self) -> bool:
        """Does this entry's last token produce a next-token sample? True
        exactly when it feeds the sequence's current last token."""
        return self.start + self.n == self.req.seq_len


class StepPlan:
    __slots__ = ("entries", "admitted", "preempted", "drafted", "explain",
                 "decode_tokens", "prefill_tokens", "first_scheduled",
                 "first_wait_s")

    def __init__(self, entries, admitted, preempted, drafted=0,
                 explain=None, decode_tokens=0, prefill_tokens=0,
                 first_scheduled=0, first_wait_s=0.0):
        self.entries: List[StepEntry] = entries
        self.admitted: int = admitted
        self.preempted: int = preempted
        self.drafted: int = drafted
        # structured step-plan record (serving/obs.py flight recorder):
        # budget split, who was admitted/preempted and WHY, exhaustion
        # events, spec outcome. None when the obs plane is disarmed.
        self.explain: Optional[dict] = explain
        # the budget split, counted as the entries are made (the engine's
        # ``serve.run`` span carries them; ``explain`` reads the same
        # count): one token per sequence in its decode phase, and the
        # chunks of sequences still inside their prompt
        self.decode_tokens: int = decode_tokens
        self.prefill_tokens: int = prefill_tokens
        # requests given their first entry ever by this plan, and the sum
        # over them of (now - arrival): the queue wait as the engine saw it
        self.first_scheduled: int = first_scheduled
        self.first_wait_s: float = first_wait_s

    @property
    def total_tokens(self) -> int:
        return sum(e.n + len(e.draft) for e in self.entries)


class InFlight(Exception):
    """A plan would preempt a sequence whose next token is still on the
    device: the engine reads that step back first and plans again."""


class Scheduler:
    """Builds one StepPlan per engine step. Not thread-safe by itself —
    the engine serializes submit/step under its lock."""

    def __init__(self, pool: KVBlockPool, max_seqs: int, token_budget: int,
                 max_pages_per_seq: int, drafter=None,
                 num_draft_tokens: int = 0, obs=None,
                 role: Optional[str] = None):
        if role not in (None, "prefill", "decode"):
            raise ValueError(
                f"unknown engine role {role!r} (want prefill|decode|None)")
        if token_budget < max_seqs:
            raise ValueError(
                f"token_budget {token_budget} < max_seqs {max_seqs}: a "
                "full decode batch would not fit one step")
        if num_draft_tokens < 0:
            raise ValueError(
                f"num_draft_tokens must be >= 0, got {num_draft_tokens}")
        self.pool = pool
        self.max_seqs = int(max_seqs)
        self.token_budget = int(token_budget)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.drafter = drafter
        self.num_draft_tokens = int(num_draft_tokens)
        self._drafter_warned = False
        # serving/obs.py observer (None = disarmed: every hook below is
        # one `is None` check) and the current step's explain record
        self.obs = obs
        self._explain: Optional[dict] = None
        # disaggregated-serving role: "prefill" devotes the whole token
        # budget to chunked prefill and never schedules a sampling
        # token — requests whose prompt is fully cached (one pending
        # token) sweep into ``prefill_done`` for KV-page hand-off;
        # "decode" is a routing/accounting label (a decode engine still
        # prefills for the recompute fallback); None = unified.
        self.role = role
        self.prefill_done: List[Request] = []
        self.waiting: List[Request] = []
        self.running: List[Request] = []   # admission order
        self._free_slots = list(range(self.max_seqs - 1, -1, -1))
        # drain mode (engine.drain): admission stops, running requests
        # decode to completion — waiting requests go to the manifest
        self.draining = False

    # -- queue side -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        max_len = len(req.prompt) + req.max_new_tokens
        cap = self.max_pages_per_seq * self.pool.block_size
        if max_len - 1 > cap:
            raise ValueError(
                f"request needs up to {max_len - 1} cached tokens but a "
                f"sequence caps at {cap} "
                f"({self.max_pages_per_seq} pages x "
                f"{self.pool.block_size})")
        self.waiting.append(req)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running or self.prefill_done)

    def pop_prefill_done(self) -> List[Request]:
        """Drain the prefill-complete list (requests still holding their
        KV pages — the engine exports those pages, hands the request to
        the decode pool, and only then releases). Called by the engine
        every step, so nothing lingers here past the step that swept it."""
        done, self.prefill_done = self.prefill_done, []
        return done

    def _prefill_complete(self, req: Request) -> None:
        """Move one request out of scheduling and into the hand-off
        list: prompt fully cached (one pending token), pages KEPT for
        export, slot returned (slots only matter for page-table rows)."""
        req.state = HANDOFF
        if req.slot is not None:
            self._free_slots.append(req.slot)
            req.slot = None
        self.prefill_done.append(req)

    def queue_depth(self) -> int:
        return len(self.waiting)

    # -- page bookkeeping -----------------------------------------------------
    def _grow_pages(self, req: Request, upto_pos: int,
                    phase: str = "decode") -> bool:
        """Ensure pages cover positions [0, upto_pos]; False on exhaustion
        (caller decides: shrink chunk, defer, or preempt)."""
        need = upto_pos // self.pool.block_size + 1 - len(req.pages)
        if need <= 0:
            return True
        try:
            req.pages.extend(self.pool.allocate(need))
        except PoolExhausted:
            self._note_exhaustion(req, phase, "exhausted", need)
            return False
        except chaos.FaultInjected:
            # an injected serve.kv_alloc fault IS the pool-exhaustion
            # drill: same deferral/preemption path, deterministically
            self._note_exhaustion(req, phase, "chaos", need)
            return False
        return True

    def _note_exhaustion(self, req: Request, phase: str, kind: str,
                         need: int) -> None:
        """Record a failed page grow in the step-plan record and raise
        the pool-exhaustion anomaly (flight-recorder dump trigger).
        Draft-phase pressure is routine opportunistic yielding, not an
        anomaly — it is recorded but never triggers a dump."""
        ex = self._explain
        if ex is not None and len(ex["exhaustion"]) < 8:
            ex["exhaustion"].append({
                "site": "serve.kv_alloc", "rid": req.rid, "phase": phase,
                "kind": kind, "need_pages": need,
                "free": self.pool.free_blocks(),
                "cached": self.pool.cached_blocks()})
        if self.obs is not None and phase != "draft":
            self.obs.note_anomaly("pool_exhausted", {
                "site": "serve.kv_alloc", "rid": req.rid, "phase": phase,
                "kind": kind, "need_pages": need})

    def _release(self, req: Request, cache_prefix: bool) -> None:
        req.unread = None             # a row of a step in flight is dropped
        if cache_prefix and req.pos >= len(req.prompt):
            # the prompt's full pages are valid reusable prefix content
            self.pool.register_prefix(req.prompt, req.pages)
        if req.pages:
            self.pool.release(req.pages)
        req.pages = []
        if req.slot is not None:
            self._free_slots.append(req.slot)
            req.slot = None

    def evict_finished(self, req: Request) -> None:
        """Remove a finished request at the step boundary, caching its
        prompt pages for prefix reuse."""
        self.running.remove(req)
        self._release(req, cache_prefix=True)
        if self.obs is not None:
            self.obs.on_finish(req, req.finish_reason or "finished")
        req.finish()

    def _preempt_youngest(self, to_grow: Optional[Request] = None
                          ) -> Optional[Request]:
        """Pool pressure relief: kick the most recently admitted running
        request back to the waiting front for recompute."""
        if not self.running:
            return None
        if self.running[-1].unread is not None:
            raise InFlight(self.running[-1].rid)
        victim = self.running.pop()
        self._release(victim, cache_prefix=False)
        victim.state = WAITING
        victim.pos = 0
        victim.n_prefix = 0
        victim.preemptions += 1
        self.waiting.insert(0, victim)
        if self._explain is not None:
            self._explain["preempted"].append({
                "rid": victim.rid, "reason": "pool_pressure",
                "to_grow": to_grow.rid if to_grow is not None else None,
                "generated": len(victim.output)})
        if self.obs is not None:
            self.obs.on_preempt(
                victim, to_grow.rid if to_grow is not None else None)
        return victim

    # -- step-fault containment (serving/resilience.py) -----------------------
    def requeue_all_running(self, reason: str = "step_fault"
                            ) -> List[Request]:
        """Kick EVERY running request back to the waiting front for
        prefix recompute — the step-fault containment reset: after a
        faulted device step no in-flight KV write can be trusted, so
        pages are released (content unregistered) and each request
        recomputes from its surviving ``seq`` (prompt + generated
        tokens, the PR 6 preemption mechanics). Requests rejoin the
        waiting queue in submission order, AHEAD of never-admitted
        arrivals; each carries one more ``step_retries`` tick for the
        engine's retry-budget check. Returns the requeued requests."""
        victims = sorted(self.running + self.prefill_done,
                         key=lambda r: r.rid)
        self.running.clear()
        self.prefill_done.clear()
        for req in reversed(victims):
            self._release(req, cache_prefix=False)
            req.state = WAITING
            req.pos = 0
            req.n_prefix = 0
            req.step_retries += 1
            self.waiting.insert(0, req)
            if self.obs is not None:
                self.obs.on_requeue(req, reason)
        return victims

    def fail_request(self, req: Request, exc: BaseException,
                     reason: str = "error") -> None:
        """Terminally fail one request (retry budget exhausted, engine
        abort): evict it from wherever it lives, release its pages
        WITHOUT caching (its KV content is not trusted), record exactly
        one terminal lifecycle event, and resolve its ``result()``/
        ``stream()`` with the error instead of leaving it parked."""
        if req in self.running:
            self.running.remove(req)
            self._release(req, cache_prefix=False)
        elif req in self.prefill_done:
            # swept but never exported (death/abort before the hand-off
            # landed): its pages are still held — release them
            self.prefill_done.remove(req)
            self._release(req, cache_prefix=False)
        elif req in self.waiting:
            self.waiting.remove(req)
        req.fail(exc)                 # resolve first: clients unblock now
        if self.obs is not None:
            self.obs.on_fail(req, reason)

    # -- the per-step planner -------------------------------------------------
    def schedule(self) -> StepPlan:
        entries: List[StepEntry] = []
        decode_entries: List[StepEntry] = []
        budget = self.token_budget
        admitted = preempted = drafted = 0
        decode_tokens = prefill_tokens = first_scheduled = 0
        first_wait_s = 0.0
        now = time.monotonic()
        obs = self.obs
        armed = obs is not None and obs.armed
        explain = None
        if armed:
            # the two token counts are filled in from the plan's at the end
            explain = {"budget_total": budget, "decode_tokens": 0,
                       "prefill_tokens": 0, "drafted_tokens": 0,
                       "admitted": [], "preempted": [], "exhaustion": [],
                       "chaos": [], "admission": None, "spec": None}
        self._explain = explain

        # 0) prefill role: a request whose prompt is fully cached (one
        #    pending token — feeding it would SAMPLE, which is the decode
        #    pool's job) is prefill-complete: sweep it into the hand-off
        #    list with its pages intact. The engine exports the pages and
        #    hands the request across the pool boundary this same step.
        if self.role == "prefill":
            for req in [r for r in self.running
                        if r.pos >= r.seq_len - 1]:
                self.running.remove(req)
                self._prefill_complete(req)

        # 1) one decode token per running sequence in its decode phase —
        #    grown pages first; exhaustion preempts the youngest (possibly
        #    the grower itself) and retries once. A sequence whose last
        #    token is in flight is known finished by count: not planned.
        for req in list(self.running):
            if req.pos != req.seq_len - 1 or budget <= 0:
                continue
            if req.unread is not None and \
                    len(req.output) + 1 >= req.max_new_tokens:
                continue
            while not self._grow_pages(req, req.pos):
                victim = self._preempt_youngest(to_grow=req)
                preempted += 1
                if victim is None or victim is req:
                    break
            if req.state is not RUNNING or req not in self.running:
                continue                      # preempted itself
            if len(req.pages) * self.pool.block_size <= req.pos:
                continue                      # still no page: sit out
            e = StepEntry(req, req.pos, 1)
            entries.append(e)
            decode_entries.append(e)
            budget -= 1
            decode_tokens += 1

        # 2) prefill chunks for running requests still inside their prompt
        #    (chunked prefill: admitted earlier, prompt longer than the
        #    budget share they got)
        for req in self.running:
            if budget <= 0:
                break
            if req.pos >= req.seq_len - 1:
                continue                      # decode-phase: handled above
            chunk = min(self._prefill_cap(req), budget)
            chunk = self._fit_chunk(req, chunk)
            if chunk <= 0:
                continue
            entries.append(StepEntry(req, req.pos, chunk))
            budget -= chunk
            prefill_tokens += chunk

        # 3) admission, strictly FIFO
        stopped_by = None
        while self.waiting:
            if self.draining:
                stopped_by = "drain"
                break
            if not self._free_slots:
                stopped_by = "no_slot"
                break
            if budget <= 0:
                stopped_by = "budget"
                break
            req = self.waiting[0]
            try:
                chaos.site("serve.admit")
            except chaos.FaultInjected:
                stopped_by = "chaos"          # drill: defer this step
                if explain is not None:
                    explain["chaos"].append("serve.admit")
                if obs is not None:
                    obs.note_anomaly("chaos_fault",
                                     {"site": "serve.admit"})
                break
            if req.pages:
                # a KV-page hand-off import pre-attached this request's
                # cache (pages + pos, including the partial boundary
                # page a fresh match_prefix could never return): honor
                # it instead of re-matching, which would clobber the
                # imported position
                n_cached = req.pos
            else:
                pages, n_cached = self.pool.match_prefix(
                    req.seq, max_tokens=len(req.seq) - 1)
                req.pages = pages
                req.pos = req.n_prefix = n_cached
            if self.role == "prefill" and req.pos >= len(req.seq) - 1:
                # the prefix cache already covers everything but the
                # sampling token: prefill-complete straight from the
                # queue — no slot, no chunk, pages ride to the hand-off
                self.waiting.pop(0)
                admitted += 1
                if explain is not None:
                    explain["admitted"].append(
                        {"rid": req.rid, "chunk": 0,
                         "prefix_tokens": n_cached,
                         "requeued": req.preemptions})
                if armed:
                    obs.on_admit(req, 0, n_cached)
                self._prefill_complete(req)
                continue
            chunk = min(self._prefill_cap(req), budget)
            chunk = self._fit_chunk(req, chunk, phase="admit")
            if chunk <= 0:
                # pool pressure: roll the prefix hit back and stop
                # admitting (FIFO: nobody behind may jump the queue)
                if req.pages:
                    self.pool.release(req.pages)
                req.pages = []
                req.pos = req.n_prefix = 0
                stopped_by = "pool"
                break
            self.waiting.pop(0)
            req.slot = self._free_slots.pop()
            req.state = RUNNING
            self.running.append(req)
            entries.append(StepEntry(req, req.pos, chunk))
            budget -= chunk
            admitted += 1
            prefill_tokens += chunk
            if req.first_planned_at is None:
                req.first_planned_at = now
                first_scheduled += 1
                first_wait_s += now - req.arrival
            if explain is not None:
                explain["admitted"].append({"rid": req.rid, "chunk": chunk,
                                            "prefix_tokens": n_cached,
                                            "requeued": req.preemptions})
            if armed:
                obs.on_admit(req, chunk, n_cached)
        if explain is not None:
            explain["admission"] = {"stopped_by": stopped_by,
                                    "waiting_after": len(self.waiting)}

        # 4) speculation LAST: drafted tokens take only the budget left
        #    after every decode step, prefill chunk, and admission got
        #    theirs — under load speculation yields its slots to real
        #    work instead of starving it, degrading toward plain decode.
        #    Each draft token occupies the budget like a prefill token
        #    (it is one more row of the same packed verify batch). All
        #    eligible sequences are drafted in ONE propose_batch call so
        #    a device-backed drafter runs one program per step, not one
        #    per sequence.
        if (self.drafter is not None and self.num_draft_tokens > 0
                and budget > 0):
            cands = []
            avail = budget
            for e in decode_entries:
                if avail <= 0:
                    break
                # drafting past the request's remaining output is waste
                # (a verify step emits at most len(draft)+1 tokens), and
                # drafting past the leftover budget is waste a device-
                # backed drafter would PAY for — cap each candidate's k
                # so the batched propose never computes discarded drafts
                room = e.req.max_new_tokens - len(e.req.output) - 1
                d_max = min(self.num_draft_tokens, room, avail)
                if d_max > 0:
                    cands.append((e, d_max))
                    avail -= d_max
            # a drafter can cost throughput, never correctness — and
            # never the engine: a propose failure (draft-model capacity,
            # user drafter bug) degrades this step to plain decode
            # instead of escaping schedule() and wedging the driver
            # thread with RUNNING requests parked forever
            t_draft = time.monotonic() if explain is not None else 0.0
            draft_error = None
            try:
                proposals = self.drafter.propose_batch(
                    [e.req for e, _ in cands], [d for _, d in cands]) \
                    if cands else []
            except Exception as exc:
                draft_error = repr(exc)
                if not self._drafter_warned:
                    warnings.warn(
                        f"drafter propose_batch failed ({exc!r}); "
                        "skipping speculation — decode continues "
                        "unspeculated")
                    self._drafter_warned = True
                proposals = []
            proposed_total = sum(len(p) for p in proposals)
            for (e, d_max), prop in zip(cands, proposals):
                if budget <= 0:
                    break
                drafts = list(prop)[:min(d_max, budget)]
                # pages must also cover the drafted positions; shrink the
                # proposal under pool pressure rather than preempting —
                # speculation is opportunistic
                while drafts and not self._grow_pages(
                        e.req, e.start + e.n - 1 + len(drafts),
                        phase="draft"):
                    drafts.pop()
                if not drafts:
                    continue
                e.draft = tuple(int(t) for t in drafts)
                budget -= len(drafts)
                drafted += len(drafts)
            if explain is not None:
                explain["drafted_tokens"] = drafted
                explain["spec"] = {
                    "candidates": len(cands),
                    "proposed": proposed_total,
                    "scheduled": drafted,
                    "propose_seconds": round(
                        time.monotonic() - t_draft, 6),
                    "error": draft_error}

        if explain is not None:
            explain["budget_left"] = budget
            explain["decode_tokens"] = decode_tokens
            explain["prefill_tokens"] = prefill_tokens
        self._explain = None
        return StepPlan(entries, admitted, preempted, drafted,
                        explain=explain, decode_tokens=decode_tokens,
                        prefill_tokens=prefill_tokens,
                        first_scheduled=first_scheduled,
                        first_wait_s=first_wait_s)

    def _prefill_cap(self, req: Request) -> int:
        """How many tokens of ``req.seq`` prefill may still feed: the
        full remainder on a unified/decode engine (feeding the final
        token yields the logits the sample comes from), but NEVER the
        final token on a prefill-role engine — that feed would sample,
        and sampling is the decode pool's half of the split."""
        cap = req.seq_len - req.pos
        if self.role == "prefill":
            cap -= 1
        return cap

    def _fit_chunk(self, req: Request, chunk: int,
                   phase: str = "prefill") -> int:
        """Shrink a prefill chunk to the pages actually obtainable.
        allocate() is all-or-nothing, so on failure retry with the chunk
        the currently AVAILABLE pages could cover — partial progress
        beats stalling the FIFO head on idle free pages."""
        bs = self.pool.block_size
        while chunk > 0 and not self._grow_pages(req,
                                                 req.pos + chunk - 1,
                                                 phase=phase):
            cap = (len(req.pages) + self.pool.available_blocks()) * bs \
                - req.pos
            chunk = min(chunk - 1, max(cap, 0))
        return chunk


__all__ = ["Request", "Scheduler", "StepPlan", "StepEntry", "InFlight",
           "WAITING", "RUNNING", "FINISHED", "HANDOFF",
           "REQUEST_TRANSITIONS"]
