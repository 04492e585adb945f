"""Continuous-batching serving engine (paddle_tpu.serving).

Oracle strategy, mirroring test_generation.py: the engine's packed
ragged-paged decode must reproduce the one-shot ``generate()`` tokens
exactly, and the ragged paged attention must match the dense
``generation._attend`` / ``_attend_gqa`` paths on CPU. Scheduler
invariants (FIFO no-starvation, eviction frees every page, prefix-reuse
refcounts) and the chaos drill sites are pinned host-side.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation as G
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import (EngineConfig, KVBlockPool, ObsConfig,
                                PoolExhausted, ServingEngine,
                                ragged_paged_attention)

pytestmark = pytest.mark.serve


import functools


@functools.lru_cache(maxsize=None)
def _model(kv_heads=2, seed=3, vocab=61):
    """One shared read-only model per (geometry, seed): every engine in
    this file only READS weights (pools are the donated state), so the
    ~30 tests that used to rebuild identical models now share three —
    the deterministic paddle.seed(seed) build makes the cached instance
    bit-identical to a fresh one."""
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab_size=vocab, hidden_size=32, layers=2,
                           heads=4, kv_heads=kv_heads, seq=64)
    cfg.use_flash_attention = False
    return LlamaForCausalLM(cfg)


def _prompts(n, lens=(7, 4, 11, 5, 9, 3, 8, 6), vocab=61, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (lens[i % len(lens)],)).tolist()
            for i in range(n)]


_oracle_memo = {}


def _oracle(model, prompts, max_new):
    """Memoized one-shot generate() reference: several tests ask for the
    oracle of the identical (model, prompts, max_new) triple — compute
    each once (keyed by model identity: _model() is cached too)."""
    key = (id(model), tuple(tuple(p) for p in prompts), max_new)
    if key in _oracle_memo:
        return [list(o) for o in _oracle_memo[key]]
    out = []
    for p in prompts:
        toks, _ = model.generate(
            paddle.to_tensor(np.asarray([p], np.int32)),
            max_new_tokens=max_new)
        out.append(toks.numpy()[0].tolist())
    _oracle_memo[key] = [list(o) for o in out]
    return out


# -- ragged paged attention vs the dense decode paths -------------------------

def _build_pool(rng, lens, kvh, bs, d, extra_pages=2):
    """Per-seq dense caches packed into a paged pool + tables."""
    mp = max((ln - 1) // bs + 1 for ln in lens) + 1
    total = sum((ln - 1) // bs + 1 for ln in lens) + extra_pages
    kp = np.zeros((total, kvh, bs, d), np.float32)
    vp = np.zeros((total, kvh, bs, d), np.float32)
    tables = np.full((len(lens), mp), -1, np.int32)
    dense_k, dense_v = [], []
    nxt = 0
    for s, ln in enumerate(lens):
        dk = rng.standard_normal((ln, kvh, d)).astype(np.float32)
        dv = rng.standard_normal((ln, kvh, d)).astype(np.float32)
        dense_k.append(dk)
        dense_v.append(dv)
        for c in range((ln - 1) // bs + 1):
            pg = nxt
            nxt += 1
            tables[s, c] = pg
            chunk_k = dk[c * bs:(c + 1) * bs]
            kp[pg, :, :len(chunk_k)] = chunk_k.transpose(1, 0, 2)
            chunk_v = dv[c * bs:(c + 1) * bs]
            vp[pg, :, :len(chunk_v)] = chunk_v.transpose(1, 0, 2)
    return kp, vp, tables, dense_k, dense_v


@pytest.mark.parametrize("rep", [1, 2])
def test_ragged_attention_matches_dense(rep):
    rng = np.random.default_rng(0)
    kvh, d, bs = 2, 8, 4
    h = kvh * rep
    lens = [5, 9, 3]
    kp, vp, tables, dense_k, dense_v = _build_pool(rng, lens, kvh, bs, d)
    # one decode query per sequence at its last position
    q = rng.standard_normal((len(lens), h, d)).astype(np.float32)
    slot = np.arange(len(lens), dtype=np.int32)
    pos = np.asarray([ln - 1 for ln in lens], np.int32)
    got = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(slot), jnp.asarray(pos),
        jnp.ones(len(lens), bool), rep=rep)
    for s, ln in enumerate(lens):
        # dense oracle: [1, 1, H, D] query over the [1, ln, kvh, D] cache
        qd = jnp.asarray(q[s][None, None])
        kd = jnp.asarray(dense_k[s][None])
        vd = jnp.asarray(dense_v[s][None])
        mask = jnp.ones((1, 1, 1, ln), bool)
        if rep == 1:
            want = G._attend(qd, kd, vd, mask)
        else:
            want = G._attend_gqa(qd, kd, vd, mask, rep)
        np.testing.assert_allclose(np.asarray(got[s]),
                                   np.asarray(want[0, 0]),
                                   atol=2e-5, rtol=2e-5)


def test_ragged_attention_mixed_phase_chunk():
    """A prefill chunk (several tokens of one seq) packed with decode
    tokens of others matches the dense causal computation."""
    rng = np.random.default_rng(1)
    kvh = h = 2
    d, bs = 8, 4
    lens = [6, 10]
    kp, vp, tables, dense_k, dense_v = _build_pool(rng, lens, kvh, bs, d)
    # seq 0: chunk of 3 queries at positions 3..5; seq 1: decode at 9
    q = rng.standard_normal((4, h, d)).astype(np.float32)
    slot = np.asarray([0, 0, 0, 1], np.int32)
    pos = np.asarray([3, 4, 5, 9], np.int32)
    got = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(slot), jnp.asarray(pos),
        jnp.ones(4, bool), rep=1)
    qd = jnp.asarray(q[:3][None])                    # [1, 3, H, D]
    kd = jnp.asarray(dense_k[0][None])
    vd = jnp.asarray(dense_v[0][None])
    t_idx = jnp.arange(lens[0])[None, None, None, :]
    q_idx = jnp.asarray(pos[:3])[None, None, :, None]
    want = G._attend(qd, kd, vd, t_idx <= q_idx)
    np.testing.assert_allclose(np.asarray(got[:3]), np.asarray(want[0]),
                               atol=2e-5, rtol=2e-5)


def _mixed_step(rng, rep, dtype, t, kvh=2, d=8, bs=4):
    """One packed step as ``_pack_plan`` lays it out: slot 0 a decode row,
    slot 1 a prefill chunk that starts mid-page, slot 2 a verify chunk (the
    fed token and two drafts), slot 3 idle, slot 4 a chunk over a table with
    a -1 page inside that shares its first page with slot 0; the rest of the
    budget is padding rows."""
    p, s, mp = 20, 5, 6
    kp = jnp.asarray(rng.standard_normal((p, kvh, bs, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((p, kvh, bs, d)), dtype)
    tables = np.full((s, mp), -1, np.int32)
    tables[0, :3] = [2, 5, 7]
    tables[1, :4] = [1, 9, 11, 12]
    tables[2, :5] = [0, 3, 4, 6, 8]
    tables[4, :4] = [2, -1, 13, 14]
    plan = [(0, 9, 1), (1, 6, 7), (2, 17, 3), (4, 9, 6)]   # slot, pos, rows
    slot = np.zeros(t, np.int32)
    pos = np.zeros(t, np.int32)
    valid = np.zeros(t, bool)
    i = 0
    for sl, first, n in plan:
        slot[i:i + n] = sl
        pos[i:i + n] = np.arange(first, first + n)
        valid[i:i + n] = True
        i += n
    q = jnp.asarray(rng.standard_normal((t, kvh * rep, d)), dtype)
    return q, kp, vp, (jnp.asarray(tables), jnp.asarray(slot),
                       jnp.asarray(pos), jnp.asarray(valid))


def _decode_step(rng, rep, dtype, t, kvh=2, d=8, bs=4):
    """Decode only: every row its own slot at a random depth, one idle
    slot between them, one padding row at the end."""
    s, mp = t, 5
    p = s * mp
    kp = jnp.asarray(rng.standard_normal((p, kvh, bs, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((p, kvh, bs, d)), dtype)
    tables = rng.permutation(p).reshape(s, mp).astype(np.int32)
    slot = np.asarray([i for i in range(s) if i != 2] + [0], np.int32)
    pos = rng.integers(0, mp * bs, t).astype(np.int32)
    valid = np.ones(t, bool)
    valid[-1] = False
    q = jnp.asarray(rng.standard_normal((t, kvh * rep, d)), dtype)
    return q, kp, vp, (jnp.asarray(tables), jnp.asarray(slot),
                       jnp.asarray(pos), jnp.asarray(valid))


def _planned(plan, slots, mp, t, holes=()):
    """A step builder for ``plan``, (slot, first position, rows) per
    scheduled sequence in packing order: each slot gets the pages its
    context needs from a shuffled pool, ``holes`` are (slot, table column)
    entries set back to -1. Pages of 4: a block of the kernel's walk is 64
    pages, so a context past 256 has a second block."""
    def step(rng, rep, dtype, t_, kvh=2, d=8, bs=4):
        assert t_ == t
        need = {sl: -(-(first + n) // bs) for sl, first, n in plan}
        free = list(rng.permutation(sum(need.values()) + 3))
        pools = [jnp.asarray(rng.standard_normal((len(free), kvh, bs, d)),
                             dtype) for _ in range(2)]
        tables = np.full((slots, mp), -1, np.int32)
        slot, pos = np.zeros(t, np.int32), np.zeros(t, np.int32)
        valid = np.zeros(t, bool)
        i = 0
        for sl, first, n in plan:
            tables[sl, :need[sl]] = [free.pop() for _ in range(need[sl])]
            slot[i:i + n] = sl
            pos[i:i + n] = np.arange(first, first + n)
            valid[i:i + n] = True
            i += n
        for sl, col in holes:
            tables[sl, col] = -1
        q = jnp.asarray(rng.standard_normal((t, kvh * rep, d)), dtype)
        return q, *pools, tuple(jnp.asarray(a) for a in
                                (tables, slot, pos, valid))
    return step


# what the copies' chain across tiles and slots makes delicate
_CHAIN_STEPS = {
    # live slots with empty ones before, between and after them
    "chain-gaps": (_planned([(1, 9, 1), (4, 6, 7), (5, 300, 1)], 8, 80, 12),
                   1, 12),
    "chain-one-live": (_planned([(3, 20, 5)], 5, 8, 8), 2, 8),
    "chain-none-live": (_planned([], 4, 8, 8), 1, 8),
    # three blocks, then two, then one, then a chunk of two tiles: a slot's
    # first block lands in either half of the buffer
    "chain-odd-even": (_planned([(0, 599, 1), (1, 299, 1), (2, 40, 1),
                                 (3, 250, 20)], 4, 160, 24), 1, 24),
    # a -1 entry inside the context, in a block that the slot before it
    # fetched: in its first block and in its second
    "chain-hole": (_planned([(0, 30, 1), (2, 280, 1), (3, 10, 3)], 4, 80, 8,
                            holes=[(2, 1), (2, 66), (3, 0)]), 2, 8),
    # the budget's last tile is clamped back inside q, after a fetch ahead
    "chain-clamped": (_planned([(0, 5, 3), (1, 8, 14)], 2, 8, 17), 1, 17),
    # contexts of exactly one page, one block, and one slot past a block
    "chain-edges": (_planned([(0, 3, 1), (1, 15, 1), (2, 255, 1),
                              (3, 256, 1), (4, 240, 17)], 5, 80, 24), 1, 24),
}


@pytest.mark.parametrize("step,rep,dtype,t,tol", [
    (_mixed_step, 1, jnp.float32, 24, 2e-5),      # MHA
    (_mixed_step, 4, jnp.float32, 24, 2e-5),      # GQA, Mistral's ratio
    (_mixed_step, 2, jnp.float32, 17, 2e-5),      # the last tile clamps
    (_mixed_step, 1, jnp.bfloat16, 40, 2e-2),
    (_mixed_step, 4, jnp.bfloat16, 40, 2e-2),
    (_decode_step, 1, jnp.float32, 10, 2e-5),
    (_decode_step, 2, jnp.float32, 10, 2e-5),
    *((step, rep, jnp.float32, t, 2e-5)
      for step, rep, t in _CHAIN_STEPS.values()),
], ids=["mixed-mha", "mixed-gqa4", "mixed-gqa2-clamped", "mixed-mha-bf16",
        "mixed-gqa4-bf16", "decode-mha", "decode-gqa2", *_CHAIN_STEPS])
def test_paged_kernel_matches_reference(monkeypatch, step, rep, dtype, t,
                                        tol):
    from paddle_tpu.kernels import ragged_pallas as rp
    monkeypatch.setattr(rp, "_INTERPRET", True)
    q, kp, vp, (tables, slot, pos, valid) = step(
        np.random.default_rng(2), rep, dtype, t)
    want = ragged_paged_attention(q, kp, vp, tables, slot, pos, valid,
                                  rep=rep)
    meta = rp.seq_meta(slot, pos, valid, tables.shape[0])
    got = rp.paged_attention(q, kp, vp, tables, *meta, rep=rep)
    assert got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    # padding rows and idle slots read zero
    assert not np.asarray(got, np.float32)[~np.asarray(valid)].any()


def test_seq_meta_is_the_plan():
    from paddle_tpu.kernels import ragged_pallas as rp
    _, _, _, (tables, slot, pos, valid) = _mixed_step(
        np.random.default_rng(0), 1, jnp.float32, 24)
    starts, counts, ctx, live_from = rp.seq_meta(slot, pos, valid,
                                                 tables.shape[0])
    assert counts.tolist() == [1, 7, 3, 0, 6]
    assert [s for s, n in zip(starts.tolist(), counts.tolist()) if n] \
        == [0, 1, 8, 11]
    assert ctx.tolist() == [10, 13, 20, 0, 15]
    # the chain the kernel's copies follow: the first live slot from each
    # slot on, 5 (no slot) past the last
    assert live_from.tolist() == [0, 1, 2, 4, 4, 5]


def test_attention_path_is_chosen_by_backend_mesh_and_geometry(monkeypatch):
    from paddle_tpu import kernels
    from paddle_tpu.kernels import ragged_pallas as rp
    from paddle_tpu.serving import ragged
    pool = ((256, 32, 16, 128), jnp.bfloat16)
    assert not hasattr(rp, "enabled")            # no flag, no gate
    assert ragged.attention_path(None, *pool) == "reference"      # a CPU
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    assert ragged.attention_path(None, *pool) == "paged_kernel"
    assert ragged.attention_path(object(), *pool) == "reference"  # a mesh
    # pages Mosaic cannot tile: half a bfloat16 sublane tile, a 64-wide head
    assert ragged.attention_path(
        None, (256, 32, 8, 128), jnp.bfloat16) == "reference"
    assert ragged.attention_path(
        None, (256, 32, 8, 128), jnp.float32) == "paged_kernel"
    assert ragged.attention_path(
        None, (256, 32, 16, 64), jnp.bfloat16) == "reference"


@pytest.mark.parametrize("kv_heads", [4, 2])     # MHA and GQA
def test_engine_on_the_paged_kernel_matches_generate(monkeypatch, kv_heads):
    """The whole engine on the kernel (interpreted): chunked prefill,
    decode and eviction give generate()'s tokens, as the reference path
    does in test_engine_matches_generate."""
    from paddle_tpu.kernels import ragged_pallas as rp
    monkeypatch.setattr(rp, "_INTERPRET", True)
    model = _model(kv_heads=kv_heads)
    prompts = _prompts(3)
    want = _oracle(model, prompts, max_new=4)
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=8,
                                            block_size=8))
    assert eng.telemetry()["attention"] == "paged_kernel"
    assert eng.generate_batch(prompts, max_new_tokens=4) == want


# -- engine vs generate() parity ----------------------------------------------

@pytest.mark.parametrize("kv_heads", [4, 2])     # MHA and GQA
def test_engine_matches_generate(kv_heads):
    model = _model(kv_heads=kv_heads)
    prompts = _prompts(5)
    want = _oracle(model, prompts, max_new=6)
    eng = ServingEngine(model, EngineConfig(max_seqs=3, token_budget=16,
                                            block_size=8))
    got = eng.generate_batch(prompts, max_new_tokens=6)
    assert got == want
    assert eng.pool.used_blocks() == 0           # eviction freed everything


def test_engine_matches_generate_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(5)
    cfg = GPTConfig.tiny(vocab_size=53, hidden_size=32, layers=2, heads=4,
                         seq=64)
    model = GPTForCausalLM(cfg)
    prompts = _prompts(3, vocab=53, seed=4)
    want = _oracle(model, prompts, max_new=5)
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=12,
                                            block_size=4))
    got = eng.generate_batch(prompts, max_new_tokens=5)
    assert got == want


def test_engine_matches_generate_gpt_moe():
    """step_ragged through the no-drop MoE blocks (scan over expert
    banks on [T, 1, d] packed tokens)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(13)
    cfg = GPTConfig.tiny(vocab_size=53, hidden_size=32, layers=2, heads=4,
                         seq=64, num_experts=4, moe_every=1, moe_top_k=2,
                         moe_gate="naive")
    model = GPTForCausalLM(cfg)
    prompts = _prompts(2, vocab=53, seed=9)
    want = _oracle(model, prompts, max_new=4)
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=12,
                                            block_size=4))
    assert eng.generate_batch(prompts, max_new_tokens=4) == want


def test_engine_eos_and_streaming():
    model = _model()
    prompts = _prompts(2)
    ref = _oracle(model, prompts, max_new=8)
    eos = ref[0][2]                  # force an early stop on row 0
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=16,
                                            block_size=8))
    seen = []
    r0 = eng.submit(prompts[0], max_new_tokens=8, eos_id=eos,
                    on_token=seen.append, stream=True)
    r1 = eng.submit(prompts[1], max_new_tokens=8, eos_id=eos)
    streamed = []
    t = threading.Thread(target=lambda: streamed.extend(r0.stream()))
    t.start()
    eng.run_until_idle()
    t.join(timeout=30)
    assert r0.output == ref[0][:3]           # stopped AT the eos token
    assert streamed == r0.output == seen
    assert r1.done and len(r1.output) <= 8


def test_engine_chunked_prefill_matches():
    """token_budget smaller than a prompt forces multi-step prefill
    chunks; output must not change."""
    model = _model()
    prompts = [_prompts(1, lens=(23,))[0]]
    want = _oracle(model, prompts, max_new=4)
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=6,
                                            block_size=4))
    got = eng.generate_batch(prompts, max_new_tokens=4)
    assert got == want


# -- scheduler invariants ------------------------------------------------------

def test_fifo_no_starvation():
    """With equal-length work and a 2-slot batch, FIFO admission means
    finish order == submission order (nobody is starved past a later
    arrival)."""
    model = _model()
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=16,
                                            block_size=8))
    prompts = _prompts(6, lens=(5,))
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run_until_idle()
    finished = [r.finished_at for r in reqs]
    assert all(r.done for r in reqs)
    assert finished == sorted(finished)


def test_eviction_frees_all_blocks_no_prefix_cache():
    model = _model()
    eng = ServingEngine(model, EngineConfig(max_seqs=4, token_budget=32,
                                            block_size=4,
                                            enable_prefix_cache=False))
    eng.generate_batch(_prompts(6), max_new_tokens=5)
    assert eng.pool.used_blocks() == 0
    assert eng.pool.cached_blocks() == 0
    assert eng.pool.free_blocks() == eng.pool.num_blocks


def test_prefix_reuse_refcounts_and_parity():
    model = _model()
    eng = ServingEngine(model, EngineConfig(max_seqs=3, token_budget=16,
                                            block_size=4))
    rng = np.random.default_rng(7)
    shared = rng.integers(1, 61, (9,)).tolist()   # 2 full pages + 1
    want = _oracle(model, [shared], max_new=6)[0]
    # populate the prefix cache
    assert eng.generate_batch([shared], max_new_tokens=6) == [want]
    assert eng.pool.cached_blocks() == 2
    base_hits = eng.pool.stats["prefix_hits"]
    # two concurrent requests with the same prompt share the cached pages
    r1 = eng.submit(shared, max_new_tokens=6)
    r2 = eng.submit(shared, max_new_tokens=6)
    eng.step()                                    # both admitted
    shared_pages = r1.pages[:2]
    assert r1.n_prefix == 8 and r2.n_prefix == 8
    assert r2.pages[:2] == shared_pages           # same physical pages
    assert all(eng.pool._ref[p] == 2 for p in shared_pages)
    eng.run_until_idle()
    assert r1.result(0) == want and r2.result(0) == want
    assert eng.pool.stats["prefix_hits"] == base_hits + 2
    assert eng.pool.used_blocks() == 0            # refcounts fully drained
    assert all(eng.pool._ref[p] == 0 for p in shared_pages)


def test_pool_pressure_preempts_and_completes():
    """A pool too small for all sequences' full growth must preempt (not
    wedge or corrupt): everything still finishes with oracle tokens."""
    model = _model()
    prompts = _prompts(3, lens=(9, 11, 10))
    want = _oracle(model, prompts, max_new=8)
    eng = ServingEngine(model, EngineConfig(max_seqs=3, token_budget=16,
                                            block_size=4, num_blocks=9,
                                            enable_prefix_cache=False))
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_idle(max_steps=500)
    assert [r.result(0) for r in reqs] == want
    assert eng.pool.used_blocks() == 0


def test_prefill_makes_partial_progress_on_page_shortage():
    """allocate() is all-or-nothing; a prompt needing more pages than are
    free must still prefill the chunk the free pages CAN cover instead of
    stalling the FIFO head (review regression)."""
    from paddle_tpu.serving.scheduler import Request, Scheduler
    pool = KVBlockPool(2, 16, enable_prefix_cache=False)
    sched = Scheduler(pool, max_seqs=2, token_budget=64,
                      max_pages_per_seq=4)
    sched.submit(Request(list(range(1, 41)), max_new_tokens=2))
    plan = sched.schedule()
    assert plan.admitted == 1
    assert plan.entries and plan.entries[0].n == 32   # 2 pages x 16


def test_submit_accepts_exact_pool_fit():
    """total == an exact page multiple must not be rejected by an
    off-by-one page count (review regression)."""
    model = _model()
    eng = ServingEngine(model, EngineConfig(max_seqs=1, token_budget=8,
                                            block_size=8, num_blocks=4,
                                            max_model_len=32))
    req = eng.submit(list(range(1, 29)), max_new_tokens=4)   # total 32
    eng.run_until_idle()
    assert len(req.result(0)) == 4


def test_pool_exhaustion_raises_on_impossible_request():
    pool = KVBlockPool(2, 4)
    pool.allocate(2)
    with pytest.raises(PoolExhausted):
        pool.allocate(1)


def test_submit_rejects_oversized_request():
    model = _model()
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=8,
                                            block_size=4))
    with pytest.raises(ValueError, match="max_model_len"):
        eng.submit(list(range(1, 60)), max_new_tokens=30)


# -- chaos drill sites ---------------------------------------------------------

def test_chaos_admit_defers_then_serves():
    model = _model()
    prompts = _prompts(2)
    want = _oracle(model, prompts, max_new=4)
    plan = chaos.FaultPlan(seed=0).add("serve.admit", "error", at=(1,))
    chaos.install_plan(plan)
    try:
        eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=16,
                                                block_size=8))
        got = eng.generate_batch(prompts, max_new_tokens=4)
    finally:
        chaos.clear_plan()
    assert got == want
    assert ("serve.admit", "error", 1) in plan.fired


def test_chaos_kv_alloc_exercises_exhaustion_path():
    model = _model()
    prompts = _prompts(2)
    want = _oracle(model, prompts, max_new=4)
    plan = chaos.FaultPlan(seed=0).add("serve.kv_alloc", "error", at=(1, 2))
    chaos.install_plan(plan)
    try:
        eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=16,
                                                block_size=8))
        got = eng.generate_batch(prompts, max_new_tokens=4)
    finally:
        chaos.clear_plan()
    assert got == want                 # deferred, retried, completed
    assert [f for f in plan.fired if f[0] == "serve.kv_alloc"]


# -- config routing / front door ----------------------------------------------

def test_config_knobs_route_to_engine():
    import warnings

    from paddle_tpu.inference import Config, create_llm_predictor
    model = _model()
    conf = Config()
    conf.set_max_batch_size(3)
    conf.set_kv_cache_block_size(8)
    conf.set_kv_cache_capacity(24)
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # routed knobs must NOT warn
        pred = create_llm_predictor(model, conf, max_new_tokens=4)
    eng = pred.engine
    assert eng.config.max_seqs == 3
    assert eng.pool.block_size == 8
    assert eng.pool.num_blocks == 24
    assert pred.clone().engine is eng    # pool/scheduler shared via clone


def test_tensorrt_max_batch_size_routed():
    from paddle_tpu.inference import Config
    conf = Config()
    with pytest.warns(UserWarning, match="routed to the serving engine"):
        conf.enable_tensorrt_engine(1 << 20, 5)
    assert conf.serving_options()["max_seqs"] == 5


def test_batching_server_delegates_to_engine():
    from paddle_tpu.inference import (BatchingServer, Config,
                                      create_llm_predictor)
    model = _model()
    prompts = _prompts(4)
    want = _oracle(model, prompts, max_new=5)
    conf = Config()
    conf.set_max_batch_size(4)
    pred = create_llm_predictor(model, conf, max_new_tokens=5)
    server = BatchingServer(pred)
    try:
        assert server.max_batch_size == 4
        futs = [server.submit([np.asarray(p, np.int32)]) for p in prompts]
        got = [f.result(timeout=120)[0].tolist() for f in futs]
    finally:
        server.close()
    assert got == want
    assert server.requests_served == 4


# -- speculative decoding ------------------------------------------------------

def test_verify_greedy_unit():
    from paddle_tpu.serving import verify_greedy
    # full accept: every draft equals its target; bonus token rides along
    assert verify_greedy([7, 8, 9], [7, 8, 9, 4]) == (3, [7, 8, 9, 4])
    # partial: first mismatch cuts; emitted = accepted drafts + the
    # model's own token AT the mismatch position
    assert verify_greedy([7, 8, 9], [7, 5, 9, 4]) == (1, [7, 5])
    # full rejection still emits the ordinary next token
    assert verify_greedy([7, 8], [1, 2, 3]) == (0, [1])
    assert verify_greedy([], [6]) == (0, [6])
    with pytest.raises(ValueError, match="len\\(drafts\\)\\+1"):
        verify_greedy([7], [1])


def test_ngram_drafter_prompt_lookup():
    from paddle_tpu.serving import NgramDrafter

    class Req:
        def __init__(self, seq):
            self.seq = seq

    d = NgramDrafter(max_match=3, min_match=1)
    # suffix [2, 3] recurs at offset 1; its continuation [4, 5] is drafted
    assert d.propose(Req([9, 2, 3, 4, 5, 2, 3]), 2) == [4, 5]
    # the continuation may overlap the tail, but never runs past the
    # end of recorded history (proposals are real observed tokens only)
    assert d.propose(Req([1, 2, 1, 2, 1, 2]), 4) == [1, 2]
    # most recent occurrence wins
    assert d.propose(Req([5, 7, 1, 5, 8, 2, 5]), 1) == [8]
    # nothing recurs -> no proposal (speculation skipped, never wrong)
    assert d.propose(Req([1, 2, 3, 4, 5]), 3) == []
    assert d.propose(Req([1, 2]), 0) == []
    with pytest.raises(ValueError, match="min_match"):
        NgramDrafter(max_match=2, min_match=3)
    # the per-step scan is bounded: a recurrence older than `lookback`
    # is invisible (host cost stays O(lookback) as sequences grow)
    d8 = NgramDrafter(max_match=3, min_match=2, lookback=8)
    far = [4, 5, 6] + [9] * 10 + [4, 5]          # match 10 tokens back
    assert d8.propose(Req(far), 2) == []
    assert NgramDrafter(max_match=3, min_match=2).propose(Req(far), 1) \
        == [6]
    # default propose_batch maps propose over the batch
    seqs = [[9, 2, 3, 4, 2, 3], [1, 2, 3]]
    assert d.propose_batch([Req(s) for s in seqs], [2, 2]) == [[4, 2], []]


def test_draft_greedy_matches_generate():
    """Within its context window the draft path IS plain greedy
    generate() — same decoder, left-padded fixed width."""
    model = _model()
    prompt = _prompts(1, lens=(9,))[0]
    want = _oracle(model, [prompt], max_new=4)[0]
    got = G.draft_greedy(model, prompt, 4, width=16)
    assert got == want


@pytest.mark.parametrize("kv_heads", [4, 2])     # MHA and GQA
def test_spec_matches_generate_llama(kv_heads):
    model = _model(kv_heads=kv_heads)
    prompts = _prompts(4)
    want = _oracle(model, prompts, max_new=8)
    eng = ServingEngine(model, EngineConfig(
        max_seqs=3, token_budget=24, block_size=8,
        spec_method="ngram", num_draft_tokens=4))
    got = eng.generate_batch(prompts, max_new_tokens=8)
    assert got == want                 # bit-identical to one-shot greedy
    assert eng.pool.used_blocks() == 0  # rollbacks drained every refcount


def test_spec_matches_generate_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(5)
    cfg = GPTConfig.tiny(vocab_size=53, hidden_size=32, layers=2, heads=4,
                         seq=64)
    model = GPTForCausalLM(cfg)
    prompts = _prompts(3, vocab=53, seed=4)
    want = _oracle(model, prompts, max_new=5)
    eng = ServingEngine(model, EngineConfig(
        max_seqs=2, token_budget=12, block_size=4,
        spec_method="ngram", num_draft_tokens=3))
    assert eng.generate_batch(prompts, max_new_tokens=5) == want


def test_spec_draft_model_matches_generate():
    """The draft-model drafter (here: self-speculation through a
    SLIDING 16-token window, so drafts can diverge from the full-context
    target) still yields bit-identical output."""
    model = _model()
    prompts = _prompts(2, lens=(7, 5))
    want = _oracle(model, prompts, max_new=6)
    eng = ServingEngine(model, EngineConfig(
        max_seqs=2, token_budget=16, block_size=8,
        spec_method="draft_model", num_draft_tokens=2, draft_model=model,
        spec_options={"context_width": 16}))
    got = eng.generate_batch(prompts, max_new_tokens=6)
    assert got == want
    assert eng.spec_proposed > 0       # the drafter did participate


def test_spec_eos_cut_parity():
    """eos landing inside an accepted verify prefix must cut the
    emission exactly where plain decoding would stop."""
    model = _model()
    prompts = _prompts(2)
    ref = _oracle(model, prompts, max_new=8)
    eos = ref[0][2]
    eng0 = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=24,
                                             block_size=8))
    want = [eng0.submit(p, max_new_tokens=8, eos_id=eos) for p in prompts]
    eng0.run_until_idle()
    eng1 = ServingEngine(model, EngineConfig(
        max_seqs=2, token_budget=24, block_size=8,
        spec_method="ngram", num_draft_tokens=4))
    got = [eng1.submit(p, max_new_tokens=8, eos_id=eos) for p in prompts]
    eng1.run_until_idle()
    assert [r.result(0) for r in got] == [r.result(0) for r in want]
    assert got[0].result(0) == ref[0][:3]        # stopped AT the eos token


def test_spec_accept_rate_floor_on_repetitive_text():
    """Repetitive/code-like prompts are the n-gram drafter's home turf:
    the accept rate must clear a floor and buy real steps (seeded, no
    wall clock — fully deterministic)."""
    model = _model(seed=3)
    rng = np.random.default_rng(7)
    pattern = rng.integers(1, 61, (5,)).tolist()
    prompts = [(pattern * 4)[:18], (pattern * 4)[:15]]
    kw = dict(max_seqs=2, token_budget=32, block_size=8)
    eng0 = ServingEngine(model, EngineConfig(**kw))
    want = eng0.generate_batch(prompts, max_new_tokens=24)
    eng1 = ServingEngine(model, EngineConfig(
        spec_method="ngram", num_draft_tokens=4, **kw))
    assert eng1.generate_batch(prompts, max_new_tokens=24) == want
    stats = eng1.spec_stats()
    assert stats["accept_rate"] >= 0.3, stats
    assert eng1.steps < eng0.steps     # speculation saved device calls


def test_used_blocks_is_the_count_of_held_pages():
    """Free, parked in the prefix cache or held: ``used_blocks`` subtracts
    and never walks the refcounts; through every way a page changes hands."""
    pool = KVBlockPool(6, 4)

    def held():
        assert pool.used_blocks() == sum(r > 0 for r in pool._ref)
        return pool.used_blocks()

    toks = list(range(9))
    pages = pool.allocate(3)
    assert held() == 3
    pool.register_prefix(toks, pages)
    pool.incref(pages[:1])
    pool.release(pages)                     # two park in the cache, one held
    assert held() == 1 and pool.cached_blocks() == 1
    pool.release(pages[:1])
    assert held() == 0 and pool.cached_blocks() == 2
    hit, n = pool.match_prefix(toks)        # both come back out of the cache
    assert n == 8 and held() == 2
    more = pool.allocate(4)                 # the unregistered page and three
    assert held() == 6 and pool.free_blocks() == 0
    pool.release(hit + more)
    assert held() == 0
    pool.allocate(5)                        # evicts a parked page
    assert held() == 5 and pool.stats["evicted"] == 1
    assert pool.drop_cache() == 1 and held() == 5


# -- KV rollback (truncate) ----------------------------------------------------

def test_truncate_releases_tail_and_drains_to_zero():
    pool = KVBlockPool(8, 4, enable_prefix_cache=False)
    pages = pool.allocate(4)                      # covers 16 positions
    kept, released, cow = pool.truncate(pages, 9)  # keep ceil(9/4) = 3
    assert kept == pages[:3] and released == 1 and cow is None
    assert pool._ref[pages[3]] == 0
    # exact page boundary: no partial page, no COW even at full coverage
    kept2, released2, cow2 = pool.truncate(kept, 8)
    assert kept2 == pages[:2] and released2 == 1 and cow2 is None
    pool.release(kept2)
    assert pool.used_blocks() == 0
    assert pool.free_blocks() == pool.num_blocks
    with pytest.raises(ValueError, match="negative"):
        pool.truncate([], -1)
    with pytest.raises(ValueError, match="holds only"):
        pool.truncate(pages[:1], 9)


def test_truncate_cow_on_refcount_shared_boundary():
    """Rollback must never mutate a page another sequence holds: a
    shared partially-kept boundary page is exchanged for a private
    copy, the original untouched for its other holder."""
    pool = KVBlockPool(8, 4, enable_prefix_cache=False)
    pages = pool.allocate(2)
    pool.incref([pages[1]])                       # second holder
    kept, released, cow = pool.truncate(list(pages), 6)   # partial page 1
    assert released == 0 and cow is not None
    old, new = cow
    assert old == pages[1] and kept == [pages[0], new] and new != old
    assert pool._ref[old] == 1                    # other holder keeps it
    assert pool._ref[new] == 1                    # caller owns the copy
    pool.release([old])
    pool.release(kept)
    assert pool.used_blocks() == 0


def test_truncate_cow_on_prefix_registered_boundary():
    """A boundary page registered in the prefix cache could be acquired
    by a later request at any moment — rollback goes copy-on-write and
    the registered original parks with its content intact."""
    pool = KVBlockPool(8, 4)
    toks = list(range(100, 108))                  # 2 full pages
    pages = pool.allocate(2)
    pool.register_prefix(toks, pages)
    kept, released, cow = pool.truncate(list(pages), 6)
    assert cow is not None and cow[0] == pages[1]
    assert kept[-1] == cow[1] and kept[-1] not in pool._key_of
    # the original parked in the cache and is still prefix-matchable
    assert pool._ref[pages[1]] == 0 and pages[1] in pool._key_of
    hit_pages, n = pool.match_prefix(toks + [1])
    assert hit_pages == pages and n == 8
    pool.release(hit_pages)
    pool.release(kept)
    assert pool.used_blocks() == 0


# -- scheduler: drafts yield budget under load ---------------------------------

def _running_decode_req(sched, pool, seq, slot):
    from paddle_tpu.serving.scheduler import RUNNING, Request
    req = Request(seq[:1], max_new_tokens=32)
    req.seq = list(seq)
    req.pos = len(seq) - 1
    req.state = RUNNING
    req.slot = slot
    req.pages = pool.allocate((req.pos - 1) // pool.block_size + 1)
    sched.running.append(req)
    sched._free_slots.remove(slot)
    return req


def test_truncate_cow_exhaustion_is_atomic():
    """When no page is obtainable for the copy-on-write, truncate must
    raise BEFORE mutating anything — the caller's page list stays fully
    owned (review regression: a mid-truncate failure used to leave the
    released tail behind)."""
    pool = KVBlockPool(2, 4, enable_prefix_cache=False)
    pages = pool.allocate(2)
    pool.incref([pages[1]])                       # shared boundary
    pool.incref([pages[0]])                       # tail share: release
    with pytest.raises(PoolExhausted, match="copy-on-write"):
        pool.truncate([pages[1], pages[0]], 3)    # of [0] frees nothing
    assert pool._ref[pages[0]] == 2               # nothing changed
    assert pool._ref[pages[1]] == 2
    # with the tail's last reference releasable the same call succeeds
    pool.release([pages[0]])
    kept, released, cow = pool.truncate([pages[1], pages[0]], 3)
    assert released == 1 and cow is not None and cow[0] == pages[1]


def test_truncate_cow_immune_to_kv_alloc_chaos():
    """The rollback's COW page grab bypasses the serve.kv_alloc probe:
    an armed pool-exhaustion drill must not be able to break truncate's
    atomicity mid-rollback (review regression)."""
    plan = chaos.FaultPlan(seed=0).add("serve.kv_alloc", "error", prob=1.0)
    chaos.install_plan(plan)
    try:
        pool = KVBlockPool(4, 4, enable_prefix_cache=False)
        with pytest.raises(chaos.FaultInjected):
            pool.allocate(2)                      # front door still drills
        chaos.clear_plan()
        pages = pool.allocate(2)
        chaos.install_plan(plan)
        pool.incref([pages[1]])
        kept, released, cow = pool.truncate(list(pages), 6)
    finally:
        chaos.clear_plan()
    assert released == 0 and cow is not None and cow[0] == pages[1]


def test_draft_model_propose_batch_slices_per_budget():
    """One batched draft forward serves mixed per-sequence budgets."""
    from paddle_tpu.serving import DraftModelDrafter

    class Req:
        def __init__(self, seq):
            self.seq = seq

    model = _model()
    prompts = _prompts(3, lens=(9, 6, 4))
    # k=0 sequences are excluded from the device batch entirely; the
    # others share one draft forward and slice to their own budget
    rows = G.draft_greedy_batch(model, prompts[:2], 3, width=16)
    d = DraftModelDrafter(model, context_width=16)
    got = d.propose_batch([Req(p) for p in prompts], [3, 1, 0])
    assert got == [rows[0], rows[1][:1], []]


def test_engine_pins_draft_model_batch_shape():
    """The engine pads every propose to (max_seqs, width, k): padding
    rows and the draft length are pinned at construction so the batched
    draft program compiles ONCE, however the live batch fluctuates —
    and the padded program proposes the same drafts as the bare one."""
    from paddle_tpu.serving import DraftModelDrafter

    class Req:
        def __init__(self, seq):
            self.seq = seq

    model = _model()
    eng = ServingEngine(model, EngineConfig(
        max_seqs=4, token_budget=16, block_size=8,
        spec_method="draft_model", num_draft_tokens=3, draft_model=model,
        spec_options={"context_width": 16}))
    assert eng.drafter.batch_pad == 4
    assert eng.drafter.draft_k == 3
    # explicit spec_options win over the engine's pinning
    eng2 = ServingEngine(model, EngineConfig(
        max_seqs=4, token_budget=16, block_size=8,
        spec_method="draft_model", num_draft_tokens=3, draft_model=model,
        spec_options={"context_width": 16, "batch_pad": 2, "draft_k": 1}))
    assert eng2.drafter.batch_pad == 2
    assert eng2.drafter.draft_k == 1
    # padded-batch proposals == bare per-sequence proposals
    prompts = _prompts(2, lens=(9, 6))
    bare = DraftModelDrafter(model, context_width=16)
    reqs = [Req(p) for p in prompts]
    assert eng.drafter.propose_batch(reqs, [2, 3]) == \
        bare.propose_batch(reqs, [2, 3])


def test_drafter_failure_degrades_not_wedges():
    """A drafter is opportunistic all the way down: propose_batch
    raising must degrade the step to plain decode (one warning, parity
    kept), never escape schedule() and wedge the engine's driver with
    RUNNING requests parked forever. An impossible draft-model config
    is rejected eagerly at engine construction instead."""
    import warnings as W
    from paddle_tpu.serving.speculative import Drafter

    class Exploding(Drafter):
        def propose(self, req, k):
            raise RuntimeError("boom")

    model = _model()
    prompts = _prompts(2, lens=(7, 5))
    want = _oracle(model, prompts, max_new=6)
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=16,
                                            block_size=8))
    eng.drafter = eng.sched.drafter = Exploding()
    eng.sched.num_draft_tokens = 2
    with W.catch_warnings(record=True) as rec:
        W.simplefilter("always")
        got = eng.generate_batch(prompts, max_new_tokens=6)
    assert got == want                      # parity, engine alive
    assert eng.spec_proposed == 0
    warned = [w for w in rec if "drafter" in str(w.message)]
    assert len(warned) == 1                 # warn once, not per step
    # draft model too small for k: caught at construction, not step time
    with pytest.raises(ValueError, match="draft model caps"):
        ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8,
            spec_method="draft_model", num_draft_tokens=64,
            draft_model=model))
    # missing draft model: clean ValueError, not an AttributeError
    with pytest.raises(ValueError, match="needs a draft_model"):
        ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8,
            spec_method="draft_model", num_draft_tokens=2))


def test_spec_drafts_take_only_leftover_budget():
    from paddle_tpu.serving import NgramDrafter
    from paddle_tpu.serving.scheduler import Request, Scheduler
    pool = KVBlockPool(64, 4)
    rep = [3, 4, 5, 3, 4, 5, 3, 4, 5]           # ngram-draftable history
    # budget == max_seqs: decode eats everything, drafts get nothing
    sched = Scheduler(pool, max_seqs=2, token_budget=2,
                      max_pages_per_seq=16, drafter=NgramDrafter(),
                      num_draft_tokens=4)
    for slot in (0, 1):
        _running_decode_req(sched, pool, rep, slot)
    plan = sched.schedule()
    assert plan.drafted == 0
    assert all(e.draft == () for e in plan.entries)
    # slack budget: the same batch drafts up to k per decode entry
    sched2 = Scheduler(pool, max_seqs=2, token_budget=16,
                       max_pages_per_seq=16, drafter=NgramDrafter(),
                       num_draft_tokens=4)
    for slot in (0, 1):
        _running_decode_req(sched2, pool, rep, slot)
    plan2 = sched2.schedule()
    # the lookup hit's continuation runs off the end of the 9-token
    # history after 3 tokens — drafters may propose fewer than k
    assert plan2.drafted == 6
    assert all(len(e.draft) == 3 for e in plan2.entries)
    # a waiting prefill outranks drafts for the leftover budget
    sched3 = Scheduler(pool, max_seqs=3, token_budget=9,
                       max_pages_per_seq=16, drafter=NgramDrafter(),
                       num_draft_tokens=4)
    for slot in (0, 1):
        _running_decode_req(sched3, pool, rep, slot)
    sched3.submit(Request(list(range(1, 8)), max_new_tokens=4))
    plan3 = sched3.schedule()
    assert plan3.admitted == 1
    prefill = [e for e in plan3.entries if e.n > 1]
    assert prefill and prefill[0].n == 7         # whole leftover to prefill
    assert plan3.drafted == 0


def test_chaos_spec_verify_full_rejection_drill():
    """Seeded full-rejection drill: when EVERY draft is rejected the
    engine still makes one-token-per-step progress (no livelock), output
    stays bit-identical, and FIFO finish order is preserved."""
    model = _model()
    prompts = _prompts(4, lens=(5,))
    want = _oracle(model, prompts, max_new=6)
    plan = chaos.FaultPlan(seed=0).add("serve.spec_verify", "error",
                                       prob=1.0)
    chaos.install_plan(plan)
    try:
        eng = ServingEngine(model, EngineConfig(
            max_seqs=2, token_budget=16, block_size=8,
            spec_method="ngram", num_draft_tokens=4))
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        steps = eng.run_until_idle(max_steps=300)
    finally:
        chaos.clear_plan()
    assert steps < 300                            # no livelock
    assert [r.result(0) for r in reqs] == want    # parity preserved
    assert eng.spec_accepted == 0                 # drill rejected all
    assert [f for f in plan.fired if f[0] == "serve.spec_verify"]
    finished = [r.finished_at for r in reqs]
    assert finished == sorted(finished)           # FIFO order held


def test_spec_config_routes_to_engine():
    import warnings

    from paddle_tpu.inference import Config, create_llm_predictor
    from paddle_tpu.serving import NgramDrafter
    model = _model()
    conf = Config()
    conf.set_speculative_config("ngram", num_draft_tokens=3, max_match=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # routed knobs must NOT warn
        pred = create_llm_predictor(model, conf, max_new_tokens=4)
    eng = pred.engine
    assert eng.config.spec_method == "ngram"
    assert isinstance(eng.drafter, NgramDrafter)
    assert eng.drafter.max_match == 2
    assert eng.sched.num_draft_tokens == 3
    with pytest.raises(ValueError, match="draft_model"):
        Config().set_speculative_config("draft_model")
    with pytest.raises(ValueError, match="unknown speculative"):
        Config().set_speculative_config("medusa")


# -- observability plane (serving/obs.py) --------------------------------------

def test_engine_parity_and_lifecycle_completeness_with_tracing_armed():
    """Arming the observability plane must not change a single token —
    and every submitted request's trace must end in exactly ONE terminal
    finish event, with submit/admit/first_token in causal order."""
    model = _model()
    prompts = _prompts(5)
    want = _oracle(model, prompts, max_new=6)
    eng = ServingEngine(model, EngineConfig(
        max_seqs=3, token_budget=16, block_size=8,
        obs=ObsConfig(flight_steps=64, flight_requests=32)))
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    assert [r.result(0) for r in reqs] == want   # bit-identical, armed
    for r in reqs:
        assert r.trace is not None
        terms = r.trace.terminal_events()
        assert len(terms) == 1, (r.rid, r.trace.events)
        assert terms[0] is r.trace.events[-1]    # finish is the LAST event
        kinds = [e["kind"] for e in r.trace.events]
        assert kinds[0] == "submit"
        assert kinds.index("admit") < kinds.index("first_token")
        assert terms[0]["reason"] == "max_new_tokens"
        assert terms[0]["output_tokens"] == 6
    tel = eng.telemetry()
    assert tel["requests"]["submitted"] == tel["requests"]["finished"] == 5
    assert tel["requests"]["live"] == 0


def test_lifecycle_terminal_events_on_eviction_and_preemption_paths():
    """The terminal-event invariant must survive the rough paths: pool
    pressure preempting requests (preempt events recorded, request
    re-admitted, still exactly one finish) and eos eviction."""
    model = _model()
    prompts = _prompts(3, lens=(9, 11, 10))
    want = _oracle(model, prompts, max_new=8)
    eng = ServingEngine(model, EngineConfig(
        max_seqs=3, token_budget=16, block_size=4, num_blocks=9,
        enable_prefix_cache=False, obs=True))
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_idle(max_steps=500)
    assert [r.result(0) for r in reqs] == want
    preempted = [r for r in reqs if r.preemptions]
    assert preempted, "drill config no longer exercises preemption"
    for r in reqs:
        assert len(r.trace.terminal_events()) == 1, r.trace.events
    for r in preempted:
        kinds = [e["kind"] for e in r.trace.events]
        assert "preempt" in kinds
        # re-admitted after the preemption: a later admit event exists
        assert max(i for i, k in enumerate(kinds) if k == "admit") \
            > kinds.index("preempt")
    tel = eng.telemetry()
    assert tel["requests"]["preempted"] == \
        sum(r.preemptions for r in reqs)
    # eos path: terminal reason says so
    ref = _oracle(model, [prompts[0]], max_new=8)[0]
    eng2 = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=16,
                                             block_size=8, obs=True))
    r = eng2.submit(prompts[0], max_new_tokens=8, eos_id=ref[2])
    eng2.run_until_idle()
    assert r.trace.terminal_events()[0]["reason"] == "eos"


def test_flight_ring_and_trace_bounded_under_long_run():
    """The flight recorder is a RING: a long run keeps only the last N
    step records and M lifecycles, and a single request's trace caps its
    event list (terminal event always lands; drops are counted)."""
    model = _model()
    eng = ServingEngine(model, EngineConfig(
        max_seqs=2, token_budget=16, block_size=8,
        obs=ObsConfig(flight_steps=6, flight_requests=3,
                      max_events_per_request=4)))
    reqs = [eng.submit(p, max_new_tokens=6) for p in _prompts(8, lens=(5,))]
    eng.run_until_idle()
    obs = eng.obs
    assert len(obs._steps) == 6                  # ring clamped, not grown
    assert len(obs._done) == 3
    assert eng.steps > 6                         # the run outgrew the ring
    steps = list(obs._steps)
    assert [s["step"] for s in steps] == \
        list(range(eng.steps - 5, eng.steps + 1))  # the LAST six, in order
    for r in reqs:
        # cap + the terminal event (which always lands past the cap)
        assert len(r.trace.events) <= 5
        assert len(r.trace.terminal_events()) == 1   # capped, never lost
        assert r.trace.dropped > 0
    rec = eng.dump_flight_record()
    assert len(rec["steps"]) == 6 and len(rec["requests"]) == 3


def test_step_plan_records_explain_budget_and_admission():
    """Every buffered step record must carry the scheduler's structured
    plan: budget split that adds up, admission verdicts, pool state."""
    model = _model()
    eng = ServingEngine(model, EngineConfig(
        max_seqs=2, token_budget=12, block_size=4, obs=True,
        spec_method="ngram", num_draft_tokens=3))
    rep = [3, 4, 5] * 6
    eng.submit(rep[:10], max_new_tokens=8)
    eng.submit(rep[:8], max_new_tokens=8)
    eng.run_until_idle()
    for rec in eng.obs._steps:
        plan = rec["plan"]
        used = plan["decode_tokens"] + plan["prefill_tokens"] + \
            plan["drafted_tokens"]
        assert used + plan["budget_left"] == plan["budget_total"]
        assert used == sum(e["n"] + e["draft"] for e in rec["entries"])
        assert plan["admission"] is not None
        assert {"used", "cached", "free", "utilization"} <= \
            set(rec["pool"])
    admitted = [a for rec in eng.obs._steps
                for a in rec["plan"]["admitted"]]
    assert {a["rid"] for a in admitted} == {r["rid"] for r in
                                            eng.obs._done}
    drafted = sum(rec["plan"]["drafted_tokens"] for rec in eng.obs._steps)
    assert drafted == eng.spec_proposed > 0
    specs = [rec["plan"]["spec"] for rec in eng.obs._steps
             if rec["plan"]["spec"]]
    assert all("propose_seconds" in s and s["error"] is None
               for s in specs)


def test_flight_dump_determinism_under_seeded_chaos_drill(tmp_path):
    """tools/chaos_drill.py --flight: the armed-but-quiet run produces
    no dump, the seeded exhaustion exactly one whose last step names it
    — and the dump's stable subset is identical across two runs of the
    same seed (replayable postmortems)."""
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    chaos_drill = importlib.import_module("chaos_drill")
    a = chaos_drill.run_flight_drill(seed=77, verbose=False)
    b = chaos_drill.run_flight_drill(seed=77, verbose=False)
    assert a["ok"] and a["stable"] == b["stable"]
    assert a["stable"]["reason"] == "pool_exhausted"
    assert a["stable"]["exhaustion"][0]["site"] == "serve.kv_alloc"


def test_flight_dump_never_raises_and_latches():
    """The dump path is chaos-drilled (serve.flight_dump): a faulted or
    unwritable dump degrades to a warning, never an exception into the
    engine driver; anomaly-triggered dumps latch per reason (one
    postmortem per anomaly class, not a dump storm)."""
    model = _model()
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=16,
                                            block_size=8, obs=True))
    eng.generate_batch(_prompts(2), max_new_tokens=4)
    plan = chaos.FaultPlan(seed=0).add("serve.flight_dump", "error",
                                       prob=1.0)
    chaos.install_plan(plan)
    try:
        assert eng.dump_flight_record() is None    # faulted: None, no raise
    finally:
        chaos.clear_plan()
    assert eng.obs.dump_failures == 1
    # unwritable path: same contract, no chaos needed
    assert eng.obs.dump(path="/nonexistent-dir/flight.json") is None
    assert eng.obs.dump_failures == 2
    # latching: repeated anomalies of one reason dump once
    eng.obs.note_anomaly("stall", {"fake": True})
    eng.obs.record_step({"step": 1, "dt_s": 0.0})
    eng.obs.note_anomaly("stall", {"fake": True})
    eng.obs.record_step({"step": 2, "dt_s": 0.0})
    stalls = [d for d in eng.obs.dumps if d["reason"] == "stall"]
    assert len(stalls) == 1
    eng.obs.reset_triggers()
    eng.obs.note_anomaly("stall", {"fake": True})
    eng.obs.record_step({"step": 3, "dt_s": 0.0})
    assert len([d for d in eng.obs.dumps
                if d["reason"] == "stall"]) == 2


def test_empty_plan_anomaly_still_dumps():
    """A wedged engine — exhaustion with NOTHING schedulable, so every
    plan comes back empty — must still land the explaining step record
    and flush the dump (review regression: the early return on empty
    plans skipped record_step, deferring the postmortem of exactly the
    stuck-engine case the recorder exists for)."""
    model = _model()
    plan = chaos.FaultPlan(seed=0).add("serve.kv_alloc", "error", prob=1.0)
    chaos.install_plan(plan)
    try:
        eng = ServingEngine(model, EngineConfig(max_seqs=2,
                                                token_budget=16,
                                                block_size=8, obs=True))
        eng.submit(_prompts(1)[0], max_new_tokens=4)
        eng.run_until_idle(max_steps=5)      # spins on empty plans
    finally:
        chaos.clear_plan()
    assert eng.steps == 0                     # nothing ever ran
    dumps = [d for d in eng.obs.dumps if d["reason"] == "pool_exhausted"]
    assert len(dumps) == 1
    last = list(eng.obs._steps)[-1]
    assert last.get("empty") is True
    assert last["plan"]["exhaustion"][0]["site"] == "serve.kv_alloc"
    # the request is still waiting, fault cleared => it must drain clean
    assert eng.run_until_idle(max_steps=50) < 50
    assert eng.telemetry()["requests"]["finished"] == 1


def test_stall_watchdog_triggers_dump():
    """A step exceeding the stall threshold is an anomaly: with a 0s
    threshold the very first step must dump with reason 'stall'."""
    model = _model()
    eng = ServingEngine(model, EngineConfig(
        max_seqs=2, token_budget=16, block_size=8,
        obs=ObsConfig(stall_threshold_s=0.0)))
    eng.generate_batch(_prompts(1), max_new_tokens=3)
    assert eng.obs.dumps and eng.obs.dumps[0]["reason"] == "stall"
    assert len([d for d in eng.obs.dumps
                if d["reason"] == "stall"]) == 1   # latched


def test_slo_goodput_telemetry_and_violation_dump():
    """Deadline accounting: generous deadlines => full attainment and
    goodput == throughput; an impossible TTFT deadline => one violation
    per request, zero goodput, and ONE slo_blow flight dump. The
    registry gauges/counters land when metrics are enabled."""
    from paddle_tpu.profiler import metrics as _metrics
    model = _model()
    prompts = _prompts(3)
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=16,
                                            block_size=8, obs=True))
    reqs = [eng.submit(p, max_new_tokens=5, ttft_deadline=60.0,
                       tpot_deadline=60.0) for p in prompts]
    eng.run_until_idle()
    tel = eng.telemetry()
    assert tel["slo"]["tracked"] == 3 and tel["slo"]["met"] == 3
    assert tel["slo"]["attainment"] == 1.0
    assert tel["slo"]["goodput_tokens"] == tel["slo"]["total_tokens"] \
        == sum(len(r.output) for r in reqs)
    assert tel["latency"]["ttft"]["count"] == 3
    assert 0 < tel["latency"]["ttft"]["p50"] <= \
        tel["latency"]["ttft"]["p95"] <= tel["latency"]["ttft"]["p99"]

    _metrics.reset_registry()
    _metrics.enable_metrics()
    try:
        eng2 = ServingEngine(model, EngineConfig(max_seqs=2,
                                                 token_budget=16,
                                                 block_size=8, obs=True))
        rs = [eng2.submit(p, max_new_tokens=5, ttft_deadline=1e-9)
              for p in prompts]
        eng2.run_until_idle()
        tel2 = eng2.telemetry()
        assert tel2["slo"]["violations"]["ttft"] == 3
        assert tel2["slo"]["met"] == 0 and tel2["slo"]["attainment"] == 0.0
        assert tel2["slo"]["goodput_tokens"] == 0
        assert tel2["slo"]["total_tokens"] == sum(len(r.output)
                                                  for r in rs)
        blows = [d for d in eng2.obs.dumps if d["reason"] == "slo_blow"]
        assert len(blows) == 1                     # latched: one postmortem
        snap = _metrics.get_registry().snapshot()
        assert snap["serve_slo_violations_total"]["kind=ttft"] == 3
        assert snap["serve_slo_attainment"] == 0.0
        assert snap["serve_flight_dumps_total"]["trigger=slo_blow"] == 1
        assert "q=p99" in snap["serve_ttft_quantile_seconds"]
        assert "serve_goodput_tokens_total" not in snap or \
            snap["serve_goodput_tokens_total"] == 0
    finally:
        _metrics.disable_metrics()
        _metrics.reset_registry()


def test_quantile_sketch_bounds_and_memory():
    """The Histogram quantile sketch: bounded memory, estimates within
    the published relative error of the exact order statistics."""
    from paddle_tpu.profiler import metrics as _metrics
    h = _metrics.Histogram("t", track_quantiles=True)
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-3.0, sigma=1.5, size=5000)
    for v in vals:
        h.observe(float(v))
    assert len(h._qcounts) == _metrics._Q_BUCKETS  # fixed, not per-value
    srt = np.sort(vals)
    rel = _metrics.QUANTILE_RELATIVE_ERROR
    for q in (0.5, 0.95, 0.99):
        exact = float(srt[int(np.ceil(q * len(srt))) - 1])
        got = h.quantile(q)
        assert exact * (1 - 1e-9) <= got <= exact * rel * (1 + 1e-9), \
            (q, exact, got)
    assert h.quantile(1.0) >= float(srt[-1]) * (1 - 1e-9)
    # empty + error contracts
    h2 = _metrics.Histogram("t2", track_quantiles=True)
    assert h2.quantile(0.5) == 0.0
    with pytest.raises(ValueError, match="track_quantiles"):
        _metrics.Histogram("t3").quantile(0.5)
    with pytest.raises(ValueError, match="0 < q"):
        h.quantile(0.0)
    # snapshot carries the sketch quantiles
    assert set(h.snapshot()["quantiles"]) == {0.5, 0.95, 0.99}


def test_obs_disabled_path_overhead_microbench():
    """The disarm contract: with the plane off the engine holds obs=None
    (requests get no trace, zero ring growth) and the disabled record_*
    helpers cost a single boolean check (generous 20us/call bound
    absorbs CI noise), same budget the PR 1 plane pins."""
    import time as _time

    from paddle_tpu.profiler import instrument, metrics as _metrics
    model = _model()
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=16,
                                            block_size=8))
    assert eng.obs is None
    req = eng.submit(_prompts(1)[0], max_new_tokens=3)
    eng.run_until_idle()
    assert req.trace is None                      # no per-request work
    assert not _metrics.metrics_enabled()
    n = 20_000
    t0 = _time.perf_counter()
    for _ in range(n):
        instrument.record_serve_slo_violation("ttft")
    per_v = (_time.perf_counter() - t0) / n
    t0 = _time.perf_counter()
    for _ in range(n):
        instrument.record_serve_quantiles("ttft", 0.1, 0.2, 0.3)
    per_q = (_time.perf_counter() - t0) / n
    t0 = _time.perf_counter()
    for _ in range(n):
        instrument.record_serve_flight_dump("manual")
    per_d = (_time.perf_counter() - t0) / n
    for per in (per_v, per_q, per_d):
        assert per < 20e-6, f"disabled obs record path {per:.2e}s/call"


def test_telemetry_stream_and_serve_top_render(tmp_path):
    """PADDLE_SERVE_TELEMETRY-style streaming: the observer rewrites the
    snapshot file on a step cadence and serve_top renders it."""
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    serve_top = importlib.import_module("serve_top")
    import json as _json
    tel_path = tmp_path / "telemetry.json"
    model = _model()
    eng = ServingEngine(model, EngineConfig(
        max_seqs=2, token_budget=16, block_size=8,
        obs=ObsConfig(telemetry_path=str(tel_path), telemetry_every=1)))
    eng.generate_batch(_prompts(3), max_new_tokens=4)
    with open(tel_path) as f:
        tel = _json.load(f)
    assert tel["requests"]["submitted"] == 3
    frame = serve_top.render(tel)
    assert "slo" in frame and "kv pool" in frame and "latency" in frame


def test_serve_top_demo_and_trace_export_smoke(tmp_path):
    """serve_top --demo runs end to end via subprocess, and the chrome
    trace export merges through tools/trace_merge.py (also subprocess)
    with the clock anchor aligning it like any training rank trace."""
    import json as _json
    import os
    import subprocess
    import sys
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "serve_top.py"),
         "--demo", "--iterations", "2", "--requests", "4", "--no-clear"],
        capture_output=True, timeout=300, env=env, cwd=repo)
    out = r.stdout.decode()
    assert r.returncode == 0, r.stderr.decode()
    assert "slo" in out and "drained 4 requests" in out

    # trace export -> trace_merge CLI
    model = _model()
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=16,
                                            block_size=8, obs=True))
    eng.generate_batch(_prompts(3), max_new_tokens=4)
    trace_path = tmp_path / "serve_trace.json"
    payload = eng.obs.export_chrome_trace(str(trace_path))
    names = [e["name"] for e in payload["traceEvents"]]
    assert "paddle_tpu.clock_anchor" in names
    for span in ("queue_wait", "prefill", "decode"):
        assert span in names
    tids = {e.get("tid") for e in payload["traceEvents"]
            if e["name"] == "decode"}
    assert len(tids) == 3                          # one track per request
    merged_path = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "trace_merge.py"),
         str(trace_path), "-o", str(merged_path)],
        capture_output=True, timeout=120, cwd=repo)
    assert r.returncode == 0, r.stderr.decode()
    assert b"no clock anchors" not in r.stderr     # anchor was found
    with open(merged_path) as f:
        merged = _json.load(f)
    spans = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert spans and all(e["ts"] >= 0 for e in spans)


# -- one admission policy ------------------------------------------------------

def test_engine_config_rejects_policy():
    """Admission is continuous batching and nothing else: there is no
    policy to pass."""
    with pytest.raises(TypeError):
        EngineConfig(policy="continuous")


def test_scheduler_rejects_policy():
    from paddle_tpu.serving.scheduler import Scheduler
    pool = KVBlockPool(2, 16)
    with pytest.raises(TypeError):
        Scheduler(pool, max_seqs=2, token_budget=64, max_pages_per_seq=2,
                  policy="static")
