"""Disaggregated serving: prefill/decode engine pools + KV-page handoff.

The acceptance oracle is the same one every serving PR pins: greedy
output across the prefill→decode pool boundary must be BIT-IDENTICAL to
the single-engine path — the imported K/V is byte-for-byte what the
decode engine would have computed itself. This file pins:

  * ``KVBlockPool.export_pages``/``import_pages`` page bit-identity and
    prefix-registration transfer (the hash-chain keys ride with the
    pages, so the decode pool's cache is warm for the next arrival);
  * engine-vs-``generate()`` parity across the pool boundary — chunked
    prefill, prefix reuse, mp=2 sharded pools, cache-cold AND through
    the AOT warm-start path;
  * the role-aware scheduler (a prefill engine never samples; the
    decode pool's token-thin program carries all decode);
  * the hand-off failure ladder: import exhaustion ⇒ prompt recompute
    (degraded, bit-identical), no survivor ⇒ exactly one terminal
    lifecycle event (never a park);
  * the per-role service-time evidence, disagg metrics, mem_report's
    ``role=`` pricing, and the bench/drill fast floors.
"""
import functools
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import metrics as _metrics
from paddle_tpu.serving import (EngineConfig, KVBlockPool, ReplicaRouter,
                                RequestFailed, ServingEngine)
from paddle_tpu.serving.obs import TERMINAL_EVENT, ObsConfig
from paddle_tpu.serving.scheduler import HANDOFF

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
sys.path.insert(0, TOOLS)

pytestmark = pytest.mark.disagg


@functools.lru_cache(maxsize=None)
def _model(kv_heads=2, heads=4, seed=3, vocab=61):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab_size=vocab, hidden_size=32, layers=2,
                           heads=heads, kv_heads=kv_heads, seq=128)
    cfg.use_flash_attention = False
    return LlamaForCausalLM(cfg)


def _prompts(n, vocab=61, seed=0, lens=(7, 4, 11, 20, 9, 17, 3, 26)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (lens[i % len(lens)],)).tolist()
            for i in range(n)]


_oracle_memo = {}


def _oracle(model, prompts, max_new=8):
    key = (id(model), tuple(tuple(p) for p in prompts), max_new)
    if key not in _oracle_memo:
        out = []
        for p in prompts:
            toks, _ = model.generate(
                paddle.to_tensor(np.asarray([p], np.int32)),
                max_new_tokens=max_new)
            out.append(toks.numpy()[0].tolist())
        _oracle_memo[key] = out
    return [list(o) for o in _oracle_memo[key]]


def _pre(model, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("token_budget", 24)
    kw.setdefault("block_size", 8)
    return ServingEngine(model, EngineConfig(role="prefill", **kw))


def _dec(model, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("token_budget", 8)
    kw.setdefault("block_size", 8)
    return ServingEngine(model, EngineConfig(role="decode", **kw))


def _fleet(model, n_pre=1, n_dec=1, pre_kw=None, dec_kw=None, seed=0):
    engines = [_pre(model, **(pre_kw or {})) for _ in range(n_pre)] \
        + [_dec(model, **(dec_kw or {})) for _ in range(n_dec)]
    return ReplicaRouter(engines, policy="affinity", seed=seed)


# -- export / import ----------------------------------------------------------

class TestExportImport:
    def test_export_import_page_bit_identity(self):
        """The exported page arrays land in the importing engine's pools
        byte-for-byte, including the partial boundary page — and the
        prefix registration rides along (the decode pool can serve the
        prompt's full pages as cache hits afterwards)."""
        model = _model()
        prompt = _prompts(1, lens=(20,))[0]      # 19 cached -> 3 pages
        pre = _pre(model)
        pre.submit(prompt, max_new_tokens=4)
        pre.run_until_idle(max_steps=50)
        (req, record), = pre.pop_handoffs()
        assert record["n_tokens"] == len(prompt) - 1 == 19
        assert record["num_pages"] == 3
        assert len(record["keys"]) == 2          # full pages only
        assert record["tokens"] == prompt[:16]
        dec = _dec(model)
        dec.import_handoff(req, record)
        assert req.pages and req.pos == 19
        for i, page in enumerate(req.pages):
            np.testing.assert_array_equal(
                np.asarray(dec._kp[:, page]), np.asarray(record["k"][i]))
            np.testing.assert_array_equal(
                np.asarray(dec._vp[:, page]), np.asarray(record["v"][i]))
        # prefix-registration transfer: a same-prefix lookup in the
        # DECODE pool hits the imported full pages
        pages, n = dec.pool.match_prefix(prompt)
        assert n == 16 and pages == req.pages[:2]
        dec.pool.release(pages)

    def test_export_pages_validates_coverage(self):
        pool = KVBlockPool(8, 8)
        pages = pool.allocate(2)
        with pytest.raises(ValueError, match="exactly"):
            pool.export_pages(pages, list(range(30)), 30)  # needs 4
        with pytest.raises(ValueError, match="negative"):
            pool.export_pages(pages, [], -1)

    def test_import_pages_block_size_mismatch(self):
        a, b = KVBlockPool(8, 8), KVBlockPool(8, 16)
        pages = a.allocate(1)
        rec = a.export_pages(pages, list(range(8)), 6)
        with pytest.raises(ValueError, match="block_size"):
            b.import_pages(rec)

    def test_import_pages_exhaustion_is_atomic(self):
        src = KVBlockPool(8, 8)
        pages = src.allocate(4)
        rec = src.export_pages(pages, list(range(32)), 32)
        tiny = KVBlockPool(2, 8)
        from paddle_tpu.serving import PoolExhausted
        with pytest.raises(PoolExhausted):
            tiny.import_pages(rec)
        assert tiny.free_blocks() == 2 and tiny.used_blocks() == 0


# -- parity across the pool boundary ------------------------------------------

class TestDisaggParity:
    @pytest.mark.parametrize("kv_heads", [2, 4])
    def test_parity_vs_generate(self, kv_heads):
        """Greedy output across the prefill→decode hand-off equals the
        one-shot generate() tokens exactly — GQA and MHA."""
        model = _model(kv_heads=kv_heads)
        prompts = _prompts(6)
        want = _oracle(model, prompts)
        router = _fleet(model)
        handles = [router.submit(p, max_new_tokens=8, tag=i)
                   for i, p in enumerate(prompts)]
        router.run_until_idle(max_steps=500)
        assert [h.result(0) for h in handles] == want
        assert router.kv_handoffs["pages"] == len(prompts)
        assert router.kv_handoffs["recompute"] == 0

    def test_parity_chunked_prefill_and_prefix_reuse(self):
        """Long prompts chunk through a small prefill budget; a repeated
        prompt takes the prefix-cache path on the PREFILL replica (only
        the tail re-prefills) and the handed-off pages still decode
        bit-identically."""
        model = _model()
        rng = np.random.default_rng(4)
        long_p = rng.integers(1, 61, (40,)).tolist()
        prompts = [long_p, long_p, rng.integers(1, 61, (9,)).tolist()]
        want = _oracle(model, prompts)
        router = _fleet(model, pre_kw={"token_budget": 16})
        got = []
        for p in prompts:                       # sequential: force reuse
            h = router.submit(p, max_new_tokens=8)
            router.run_until_idle(max_steps=300)
            got.append(h.result(0))
        assert got == want
        pre = router.replicas[0]
        assert pre.pool.stats["prefix_hits"] >= 1

    def test_parity_mp2_sharded_pools(self):
        """The pool boundary under tensor parallelism: BOTH engines run
        mp=2 (per-KV-head sharded pools), pages device_put across as
        sharded arrays — tokens still match generate() exactly."""
        model = _model(kv_heads=2)
        prompts = _prompts(4)
        want = _oracle(model, prompts)
        router = _fleet(model, pre_kw={"mesh": 2}, dec_kw={"mesh": 2})
        handles = [router.submit(p, max_new_tokens=8, tag=i)
                   for i, p in enumerate(prompts)]
        router.run_until_idle(max_steps=500)
        assert [h.result(0) for h in handles] == want
        assert router.kv_handoffs["pages"] == len(prompts)

    def test_parity_cache_cold_and_warm(self, tmp_path):
        """The AOT warm-start path across the boundary: cold fleet
        exports both role programs (different token budgets = different
        artifacts), a second identical fleet warm-starts from the cache,
        and both deliver the oracle tokens."""
        cache = str(tmp_path / "aot")
        model = _model()
        prompts = _prompts(4)
        want = _oracle(model, prompts)

        def fleet():
            return _fleet(model, pre_kw={"aot_cache": cache},
                          dec_kw={"aot_cache": cache})

        cold = fleet()
        assert [e.aot_warm_result for e in cold.replicas] \
            == ["miss", "miss"]
        handles = [cold.submit(p, max_new_tokens=8) for p in prompts]
        cold.run_until_idle(max_steps=500)
        assert [h.result(0) for h in handles] == want
        warm = fleet()
        assert [e.aot_warm_result for e in warm.replicas] \
            == ["hit", "hit"]
        handles = [warm.submit(p, max_new_tokens=8) for p in prompts]
        warm.run_until_idle(max_steps=500)
        assert [h.result(0) for h in handles] == want

    def test_one_token_prompt_edge(self):
        """A 1-token prompt is prefill-complete at admission with ZERO
        cached tokens (nothing to export but the hand-off itself)."""
        model = _model()
        router = _fleet(model)
        h = router.submit([5], max_new_tokens=4)
        router.run_until_idle(max_steps=100)
        assert h.result(0) == _oracle(model, [[5]], 4)[0]


# -- role-aware scheduler / engine --------------------------------------------

class TestRoles:
    def test_prefill_engine_never_samples(self):
        """A prefill-role engine emits NO tokens: every request sweeps
        to the hand-off outbox with its prompt fully cached minus the
        sampling token, pages intact."""
        model = _model()
        pre = _pre(model)
        prompts = _prompts(3)
        reqs = [pre.submit(p, max_new_tokens=8) for p in prompts]
        pre.run_until_idle(max_steps=100)
        assert pre.tokens_generated == 0
        out = pre.pop_handoffs()
        assert [r.rid for r, _ in out] == [r.rid for r in reqs]
        for req, record in out:
            assert req.state == HANDOFF
            assert req.output == []
            assert record["n_tokens"] == len(req.prompt) - 1
        assert pre.kv_handoffs_out == 3
        # the handed-off requests left the engine: no work remains
        assert not pre.has_work()

    def test_role_validation(self):
        model = _model()
        with pytest.raises(ValueError, match="role"):
            ServingEngine(model, EngineConfig(role="both"))
        with pytest.raises(ValueError, match="prefill-role"):
            ServingEngine(model, EngineConfig(role="prefill",
                                              spec_method="ngram"))

    def test_router_pool_validation(self):
        model = _model()
        with pytest.raises(ValueError, match="decode replica"):
            ReplicaRouter([_pre(model), _pre(model)])
        with pytest.raises(ValueError, match="mixed fleet"):
            ReplicaRouter([_pre(model), _dec(model),
                           ServingEngine(model, EngineConfig(
                               block_size=8))])

    def test_submits_route_to_prefill_pool(self):
        model = _model()
        router = _fleet(model, n_pre=2, n_dec=2)
        handles = [router.submit(p, max_new_tokens=4)
                   for p in _prompts(4)]
        for h in handles:
            owner = [i for i, e in enumerate(router.replicas)
                     if h in e.sched.waiting + e.sched.running]
            assert owner and owner[0] in router.prefill_pool
        router.run_until_idle(max_steps=400)

    def test_per_role_service_estimates(self):
        """The satellite: ``_predicted_wait`` learns per-role service
        times. The prefill engine's estimate comes from arrival→handoff
        (it finishes nothing), the decode engine's from handoff→finish
        — so neither role prices the other's work."""
        model = _model()
        router = _fleet(model)
        pre, dec = router.replicas
        assert pre._service_estimate() is None
        handles = [router.submit(p, max_new_tokens=8)
                   for p in _prompts(4)]
        router.run_until_idle(max_steps=400)
        assert all(h.done for h in handles)
        assert pre._service_estimate() is not None
        assert dec._service_estimate() is not None
        # decode clocks from the hand-off, so its estimate is at most
        # the full submit->finish span of the slowest request
        spans = [h.finished_at - h.arrival for h in handles]
        assert dec._service_estimate() <= max(spans) + 1e-6
        assert pre._predicted_wait(4) is not None


# -- failure ladder -----------------------------------------------------------

class TestHandoffFailures:
    def test_import_exhaustion_falls_back_to_recompute(self):
        """A decode pool transiently too full to import degrades to
        prompt recompute — outputs unchanged, outcome counted."""
        model = _model()
        rng = np.random.default_rng(2)
        a = rng.integers(1, 61, (38,)).tolist()
        b = rng.integers(1, 61, (38,)).tolist()
        want = _oracle(model, [a, b], 6)
        router = _fleet(model, pre_kw={"token_budget": 48,
                                       "max_seqs": 2},
                        dec_kw={"max_seqs": 2, "num_blocks": 7})
        ha = router.submit(a, max_new_tokens=6)
        hb = router.submit(b, max_new_tokens=6)
        router.run_until_idle(max_steps=600)
        assert [ha.result(0), hb.result(0)] == want
        assert router.kv_handoffs["recompute"] >= 1

    def test_no_survivor_is_terminal_with_one_finish_event(self):
        """The handoff-failure path: every decode replica is dead and
        the prefill replica cannot decode — the request resolves with a
        terminal RequestFailed carrying EXACTLY ONE terminal lifecycle
        event (never a park, never a double-finish)."""
        model = _model()
        pre = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8, role="prefill",
            obs=ObsConfig(flight_steps=16, flight_requests=8)))
        dec = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=8, block_size=8, role="decode",
            obs=ObsConfig(flight_steps=16, flight_requests=8)))
        router = ReplicaRouter([pre, dec], seed=0)
        h = router.submit(_prompts(1)[0], max_new_tokens=4, tag="t")
        router.fail_replica(1, reason="death")     # decode pool gone
        router.run_until_idle(max_steps=100)
        assert h.done and isinstance(h.error, RequestFailed)
        with pytest.raises(RequestFailed):
            h.result(0)
        assert router.kv_handoffs["failed"] == 1
        assert h.trace is not None
        assert len(h.trace.terminal_events()) == 1
        assert h.trace.terminal_events()[0]["reason"] == "handoff_failed"

    def test_recompute_path_has_one_finish_event_and_handoff_trace(self):
        """The degraded path still completes a single clean lifecycle:
        submit → prefill → kv_handoff → handoff_admit(recompute) →
        ... → exactly one finish."""
        model = _model()
        pre = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=48, block_size=8, role="prefill",
            obs=ObsConfig(flight_steps=16, flight_requests=8)))
        dec = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=8, block_size=8, num_blocks=7,
            role="decode",
            obs=ObsConfig(flight_steps=16, flight_requests=8)))
        router = ReplicaRouter([pre, dec], seed=0)
        rng = np.random.default_rng(5)
        a = rng.integers(1, 61, (38,)).tolist()
        b = rng.integers(1, 61, (38,)).tolist()
        ha = router.submit(a, max_new_tokens=6)
        hb = router.submit(b, max_new_tokens=6)
        router.run_until_idle(max_steps=600)
        assert ha.done and hb.done
        assert router.kv_handoffs["recompute"] >= 1
        recomputed = [h for h in (ha, hb) if any(
            e["kind"] == "handoff_admit"
            and e.get("outcome") == "recompute"
            for e in h.trace.events)]
        assert recomputed, "no request took the recompute path"
        for h in (ha, hb):
            kinds = [e["kind"] for e in h.trace.events]
            assert kinds.count(TERMINAL_EVENT) == 1
            assert "kv_handoff" in kinds
            # the kv_handoff event sits between prefill and first_token
            assert kinds.index("kv_handoff") < kinds.index("first_token")

    def test_handoff_event_between_prefill_and_first_token(self):
        """The ISSUE's lifecycle contract on the CLEAN path: kv_handoff
        lands after the prefill chunks, before first_token, and the
        terminal event is unique."""
        model = _model()
        pre = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8, role="prefill",
            obs=ObsConfig(flight_steps=16, flight_requests=8)))
        dec = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=8, block_size=8, role="decode",
            obs=ObsConfig(flight_steps=16, flight_requests=8)))
        router = ReplicaRouter([pre, dec], seed=0)
        h = router.submit(_prompts(1, lens=(20,))[0], max_new_tokens=4)
        router.run_until_idle(max_steps=200)
        assert h.result(0)
        kinds = [e["kind"] for e in h.trace.events]
        assert "prefill" in kinds and "kv_handoff" in kinds
        assert kinds.index("prefill") < kinds.index("kv_handoff") \
            < kinds.index("first_token")
        assert kinds.count(TERMINAL_EVENT) == 1
        # pool-level accounting crossed the boundary with the request
        assert pre.obs.counters["handoff_out"] == 1
        assert dec.obs.counters["handoff_in"] == 1
        assert dec.obs.counters["finished"] == 1
        assert not pre.obs._live and not dec.obs._live


class TestReviewHardening:
    """Pins for the review-caught failure modes."""

    def test_scatter_failure_never_parks_garbage_prefix_pages(self):
        """import_pages registers prefix keys before the device scatter;
        a scatter failure must UNREGISTER them — otherwise released
        never-written pages park prefix-matchable and a later
        same-prefix request silently reads garbage K/V."""
        model = _model()
        prompt = _prompts(1, lens=(20,))[0]
        pre = _pre(model)
        pre.submit(prompt, max_new_tokens=4)
        pre.run_until_idle(max_steps=50)
        (req, record), = pre.pop_handoffs()
        dec = _dec(model)

        def boom(arr):
            raise RuntimeError("scatter failed")
        dec._place_page = boom
        with pytest.raises(RuntimeError, match="scatter"):
            dec.import_handoff(req, record)
        # nothing registered, nothing held, nothing cached
        assert dec.pool.used_blocks() == 0
        assert dec.pool.cached_blocks() == 0
        pages, n = dec.pool.match_prefix(prompt)
        assert pages == [] and n == 0

    def test_all_decode_dead_is_terminal_not_pingpong(self):
        """With only PREFILL survivors a hand-off must fail terminally:
        a prefill target would sweep the request straight back into its
        own hand-off list — an export/import ping-pong that never emits
        a token."""
        model = _model()
        router = _fleet(model, n_pre=2, n_dec=1)
        dec_idx = router.decode_pool[0]
        router.fail_replica(dec_idx, reason="death")
        h = router.submit(_prompts(1)[0], max_new_tokens=4, tag="t")
        n = router.run_until_idle(max_steps=200)
        assert n < 200, "fleet never went idle (hand-off ping-pong)"
        assert h.done and isinstance(h.error, RequestFailed)
        assert router.kv_handoffs["failed"] == 1

    def test_heterogeneous_cap_mismatch_resolves_cleanly(self):
        """A decode replica whose per-sequence cap cannot hold the
        request: the import's ValueError is a fallback signal (never a
        prefill-replica 'death'), and the impossible adoption resolves
        terminally instead of parking."""
        model = _model()
        pre = _pre(model)
        dec = _dec(model, max_model_len=16)   # caps far below the pre
        router = ReplicaRouter([pre, dec], seed=0)
        h = router.submit(_prompts(1, lens=(26,))[0], max_new_tokens=8,
                          tag="big")
        router.run_until_idle(max_steps=200)
        assert router._alive == [True, True], \
            "cap mismatch killed a healthy replica"
        assert h.done and isinstance(h.error, RequestFailed)
        assert dec.pool.used_blocks() == 0, "failed import leaked pages"

    def test_deferred_handoffs_drain_without_step_all(self):
        """Per-replica-thread driving never calls step_all: deferred
        hand-offs must still retry (the decode replicas' post-step
        hook), or they would park forever."""
        model = _model()
        router = _fleet(model, pre_kw={"token_budget": 48, "max_seqs": 8},
                        dec_kw={"max_seqs": 2, "token_budget": 8})
        handles = [router.submit(p, max_new_tokens=6, tag=i)
                   for i, p in enumerate(_prompts(8))]
        # drive each engine DIRECTLY — router.step_all never runs
        for _ in range(600):
            stepped = False
            for eng in router.replicas:
                if eng.has_work():
                    eng.step()
                    stepped = True
            if not stepped and not router._pending_handoffs:
                break
        assert router.kv_handoffs["deferred"] >= 1, \
            "the tiny decode queue never deferred — test lost its teeth"
        assert not router._pending_handoffs
        want = _oracle(model, [h.prompt for h in handles], 6)
        assert [h.result(0) for h in handles] == want


# -- telemetry / metrics / tools ----------------------------------------------

class TestObservabilityAndTools:
    def test_telemetry_pools_and_serve_top_render(self):
        import serve_top
        model = _model()
        router = _fleet(model, n_pre=1, n_dec=2)
        for i, p in enumerate(_prompts(6)):
            router.submit(p, max_new_tokens=4, tag=i)
        router.run_until_idle(max_steps=400)
        tel = router.telemetry()
        pools = tel["router"]["pools"]
        assert pools["prefill"]["replicas"] == [0]
        assert pools["decode"]["replicas"] == [1, 2]
        assert tel["router"]["kv_handoffs"]["pages"] == 6
        pre_tel = tel["replicas"][0]
        assert pre_tel["role"] == "prefill"
        assert pre_tel["handoff"]["out"] == 6
        frame = serve_top.render(tel)
        assert "pools" in frame and "prefill 1/1" in frame
        assert "handoff" in frame and "Pr0" in frame and "Dr1" in frame

    def test_disagg_metrics_recorded(self):
        model = _model()
        _metrics.enable_metrics()
        try:
            _metrics.reset_registry()
            router = _fleet(model)
            for p in _prompts(3):
                router.submit(p, max_new_tokens=2)
            router.run_until_idle(max_steps=300)
            snap = _metrics.get_registry().snapshot()
            assert snap.get("serve_kv_handoff_pages_total", 0) >= 1
            hand = {k: v for k, v in snap.items()
                    if k.startswith("serve_disagg_handoffs_total")}
            assert sum(hand.get("serve_disagg_handoffs_total", {})
                       .values()) == 3
            assert any(k.startswith("serve_role_queue_depth")
                       for k in snap)
        finally:
            _metrics.disable_metrics()
            _metrics.reset_registry()

    def test_mem_report_role_term(self):
        """plan(role=) prices the pools separately: the staging term
        appears only with a role, role=None output is unchanged (the
        committed fixture stays byte-identical), and train mode
        rejects it."""
        import mem_report
        cfg = mem_report.PRESETS["tiny-llama-serve"]
        base = mem_report.plan(cfg, mode="serve", block_size=8)
        assert "role" not in base
        assert "kv_staging" not in base["components"]
        pre = mem_report.plan(cfg, mode="serve", block_size=8,
                              role="prefill")
        dec = mem_report.plan(cfg, mode="serve", block_size=8,
                              role="decode", max_seqs=16)
        assert pre["role"] == "prefill" and dec["role"] == "decode"
        for p in (pre, dec):
            assert p["components"]["kv_staging"] > 0
        # the staging tax is one max-depth request's pages
        assert pre["components"]["kv_staging"] == \
            pre["components"]["kv_cache"] // 8   # max_seqs=8 default
        # decode residency: more resident seqs = more kv_cache
        assert dec["components"]["kv_cache"] > pre["components"]["kv_cache"]
        with pytest.raises(ValueError, match="serve-mode"):
            mem_report.plan(cfg, mode="train", role="prefill")
        assert mem_report.self_check() == []

    def test_aot_warm_role_configs_listed(self):
        import aot_warm
        assert "tiny-llama-serve-prefill" in aot_warm.CONFIGS
        assert "tiny-llama-serve-decode" in aot_warm.CONFIGS


# -- drill fast mode (tier-1) -------------------------------------------------

class TestDrill:
    def test_chaos_drill_disagg_stable_per_seed(self):
        """tools/chaos_drill.py --disagg: the prefill-death drill runs
        green and its stable subset is bit-identical per seed."""
        import importlib
        chaos_drill = importlib.import_module("chaos_drill")
        r1 = chaos_drill.run_disagg_drill(seed=321, verbose=False)
        r2 = chaos_drill.run_disagg_drill(seed=321, verbose=False)
        assert r1["ok"] and r2["ok"]
        assert r1["stable"] == r2["stable"]
        assert r1["stable"]["replay_crc"] == r1["stable"]["oracle_crc"]
        assert r1["stable"]["pre_death_page_handoffs"] >= 1
