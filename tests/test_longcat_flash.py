"""LongCat-Flash on the CPU at a small size, float32, seeded weights: the
model's ``forward``, ``generate()`` and the serving engine (prefill in chunks,
then decode through the latent pages) against the plain reference's full
forward; absorbed against unabsorbed attention; the paged kernel on latent
pages against the gather-based oracle; the share test (all the shares' held
experts, the zero experts once, add up to the uncut expert layer); routed
dispatch against the dense every-expert form under uneven routing; and the
faults the comparison has to see."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.generation import _decoder_for
from paddle_tpu.kernels import grouped_experts_pallas as ge
from paddle_tpu.kernels import ragged_pallas
from paddle_tpu.models import longcat_flash as lf
from paddle_tpu.serving import EngineConfig, ServingEngine, ragged

import engine_record

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench.reference import longcat_flash_block as ref   # noqa: E402
from bench.tools.longcat_faults import FAULTS, faulty    # noqa: E402

VOCAB, LAYERS = 256, 2


def _model(seed=0, **kw):
    """A tiny model holding experts 4..7 of 16, its router drawn wide enough
    that the chosen scores weigh in the logits."""
    cfg = dataclasses.replace(
        lf.LongcatFlashConfig.tiny(vocab_size=VOCAB, layers=LAYERS,
                                   experts_held=4, first_expert=4), **kw)
    paddle.seed(seed)
    model = lf.LongcatFlashForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "router.classifier" in name:
            p._data = jnp.asarray(rng.normal(0, 0.4, p.shape), jnp.float32)
        elif "e_score_correction_bias" in name:
            p._data = jnp.asarray(rng.normal(0, 0.02, p.shape), jnp.float32)
        elif name.endswith("layernorm.weight") or ".layernorm." in name \
                or "norm." in name:
            p._data = jnp.asarray(1 + rng.normal(0, 0.05, p.shape),
                                  jnp.float32)
    return model


def _ref_cfg(cfg, **kw):
    """The configuration as the reference reads it (the file's keys)."""
    out = {"num_attention_heads": cfg.num_attention_heads,
           "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
           "qk_nope_head_dim": cfg.qk_nope_head_dim,
           "qk_rope_head_dim": cfg.qk_rope_head_dim,
           "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
           "q_lora_rank": cfg.q_lora_rank,
           "mla_scale_q_lora": cfg.mla_scale_q_lora,
           "mla_scale_kv_lora": cfg.mla_scale_kv_lora,
           "moe_topk": cfg.moe_topk, "zero_expert_num": cfg.zero_expert_num,
           "routed_scaling_factor": cfg.routed_scaling_factor,
           "n_routed_experts": cfg.experts_held,
           "first_expert": cfg.first_expert}
    out.update(kw)
    return out


def _leaves(model):
    """(top, [layer leaves by their names inside the layer])."""
    state = {n: p._data for n, p in model.named_parameters()}
    top = {n: a for n, a in state.items() if ".layers." not in n}
    layers = []
    for i in range(model.config.num_layers):
        pre = f"model.layers.{i}."
        layers.append({n[len(pre):]: a for n, a in state.items()
                       if n.startswith(pre)})
    return top, layers


def _reference(model, ids, **kw):
    """The reference's logits [S, vocab] for one sequence of ids."""
    cfg = _ref_cfg(model.config, **kw)
    top, layers = _leaves(model)
    x = ref.embed(top, jnp.asarray(ids, jnp.int32), cfg)
    for lw in layers:
        x = ref.block(top, lw, x, cfg)
    return np.asarray(ref.head(top, x, cfg))


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n)


# -- (a) forward, generate() and the engine against the reference --------------
def test_forward_matches_the_reference():
    model = _model()
    ids = np.stack([_ids(1, 33), _ids(2, 33)])
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    for b in range(2):
        np.testing.assert_allclose(got[b], _reference(model, ids[b]),
                                   atol=3e-5)
    # the expert layer weighs in: the twelve... three chosen scores are a
    # sizeable share of one
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (64, 96)),
                    jnp.float32)
    w = dict(model.named_parameters())
    _, weight = lf.route(
        x, w["model.layers.0.mlp.router.classifier.weight"]._data,
        w["model.layers.0.mlp.router.e_score_correction_bias"]._data,
        model.config)
    assert 0.15 < float(weight.sum(-1).mean()) / 6 < 0.9


def test_generate_matches_the_reference_through_the_latent_cache():
    model = _model()
    dec = _decoder_for(model)
    assert type(dec).__name__ == "_LongcatDecoder"
    assert (dec.cache_entries, dec.n_layers, dec.n_kv, dec.v_dim) \
        == (2 * LAYERS, LAYERS, 1, 0)
    ids = np.stack([_ids(4, 14), _ids(5, 14)])
    new = 7
    toks, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=new)
    toks = np.asarray(toks._data)
    for b in range(2):
        full = np.concatenate([ids[b], toks[b]])
        want = _reference(model, full)
        assert (np.argmax(want[13:-1], -1) == toks[b]).all()
    # and the logits of the decode steps themselves
    from paddle_tpu import generation as G
    w = dec.weights(model)
    mask = jnp.ones(ids.shape, jnp.int32)
    kcs, vcs, key_mask, logits = G._prefill(dec, w, jnp.asarray(ids), mask,
                                            new)
    assert kcs.shape == (2 * LAYERS, 2, 14 + new, 1, dec.hd)
    assert vcs.shape == (2 * LAYERS, 2, 14 + new, 1, 0)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(logits[b]), _reference(model, ids[b])[-1], atol=3e-5)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    key_mask = key_mask.at[:, 14].set(True)
    step, kcs, vcs = dec.step(w, tok[:, None], jnp.full((2, 1), 14, jnp.int32),
                              kcs, vcs, 14, key_mask[:, None, None, :])
    for b in range(2):
        fed = np.concatenate([ids[b], [int(tok[b])]])
        np.testing.assert_allclose(np.asarray(step[b, 0]),
                                   _reference(model, fed)[-1], atol=3e-5)


def _engine(model, **kw):
    cfg = dict(max_seqs=4, token_budget=16, block_size=8, num_blocks=48,
               max_model_len=96)
    cfg.update(kw)
    return ServingEngine(model, EngineConfig(**cfg))


_record = engine_record.record


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_engine_logits_match_the_reference(kernel, monkeypatch):
    """Prefill in chunks sharing steps with decode, then decode through the
    latent pages; with the paged kernel (interpreted) as on the chip."""
    if kernel:
        monkeypatch.setattr(ragged_pallas, "_INTERPRET", True)
    model = _model()
    eng = _engine(model)
    dec = eng.dec
    assert eng._kp.shape == (2 * LAYERS, 48, 1, 8, dec.hd)
    assert eng._vp.shape == (2 * LAYERS, 48, 1, 8, 0)        # no V pool
    tel = eng.telemetry()
    assert tel["attention"] == ("paged_kernel" if kernel else "reference")
    row = model.config.kv_lora_rank + model.config.qk_rope_head_dim
    assert tel["model"] == {
        "weight_layers": LAYERS, "cache_entries": 2 * LAYERS,
        "cached_token_bytes": 2 * LAYERS * dec.hd * 4, "cache": "latent",
        "latent_row": row, "latent_row_padded": dec.hd, "experts_held": 4,
        "experts_published": 16, "zero_experts": 8, "experts_a_token": 3}
    assert tel["pool"]["page_bytes"] == 2 * LAYERS * 8 * dec.hd * 4
    steps = _record(eng)
    reqs = [eng.submit(_ids(s, n).tolist(), max_new_tokens=m)
            for s, n, m in ((5, 37, 9), (6, 5, 12), (7, 21, 7))]
    eng.run_until_idle(max_steps=200)
    assert all(r.done and r.error is None for r in reqs)
    want = {id(r): _reference(model, list(r.prompt) + list(r.output))
            for r in reqs}
    rows = 0
    for logits, counters, points in steps:
        assert counters.shape == (5,)
        pairs, held, zero, peak, touched = (int(c) for c in counters)
        assert 0 <= held <= pairs and 0 <= zero <= pairs
        assert peak <= held and touched <= 4 * LAYERS
        for req, pos, row_i in points:
            np.testing.assert_allclose(logits[row_i], want[id(req)][pos],
                                       atol=3e-5)
            rows += 1
    assert rows == 9 + 12 + 7
    # two thirds of the choices fall on routed experts, a quarter of those
    # here, a third on zero experts: roughly, at this size
    tot = np.sum([c for _, c, _ in steps], axis=0)
    assert 0.05 < tot[1] / tot[0] < 0.4 and 0.15 < tot[2] / tot[0] < 0.55


def test_the_steps_counters_ride_on_the_emit_span(tmp_path):
    import glob
    import gzip
    import json
    model = _model()
    eng = _engine(model)
    eng.generate_batch([_ids(15, 9).tolist()], max_new_tokens=2)   # compiled
    steps = _record(eng)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit(_ids(16, 23).tolist(), max_new_tokens=4)
        while eng.step():
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                     "*.trace.json.gz"))
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    emits = [e for e in events if e.get("name") == "serve.emit"
             and e.get("ph") == "X"]
    assert len(emits) == len(steps)
    sampled = [(e, s) for e, s in zip(sorted(emits, key=lambda e: e["ts"]),
                                      steps) if s[2]]
    assert sampled
    for e, (_, counters, _) in sampled:
        a = e["args"]
        assert [int(a[k]) for k in eng.dec.COUNTERS] == [int(c) for c in counters]
        assert float(a["moe_held_mean_tokens"]) == int(counters[1]) / 4


# -- (b) absorbed against unabsorbed attention ----------------------------------
def test_absorbed_attention_equals_unabsorbed():
    model = _model()
    dec = _decoder_for(model)
    cfg = model.config
    w = dec.weights(model)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(0, 1, (2, 19, cfg.hidden_size)), jnp.float32)
    cos, sin = w["__rope_cos"][:19], w["__rope_sin"][:19]
    want = lf.mla_unabsorbed(dec._block(w, 1, 0), x, cos, sin, cfg)
    q, row = dec._mla_rows(w, 1, 0, x, cos[None], sin[None])
    assert q.shape == (2, 19, 4, dec.hd) and row.shape == (2, 19, dec.hd)
    used = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert not np.asarray(row[..., used:]).any()          # the padding
    scores = jnp.einsum("bshd,btd->bhst", q, row) * dec.attn_scale
    causal = jnp.tril(jnp.ones((19, 19), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -1e30), -1)
    o_lat = jnp.einsum("bhst,btc->bshc", p, row[..., :cfg.kv_lora_rank])
    got = dec._mla_out(w, 1, 0, o_lat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- (c) the paged kernel on latent pages against the oracle --------------------
# (slot, first position, rows) per scheduled sequence in packing order; the
# table's width in pages of 8 (a block of the kernel's walk is 32 of them)
_LATENT_PLANS = {
    # slot 0: a chunk of 11 rows after 13 cached; slot 2: one decode row at
    # position 40; slot 3: a chunk of 3 from the start; slot 4: one row at 0
    "mixed": ([(0, 13, 11), (2, 40, 1), (3, 0, 3), (4, 0, 1)], 6),
    # a one-row slot before and after a chunk of 17 rows: tiles of 1, 16, 1
    # (the chunk's second) and 1 in turn, each fetched by the one before
    "one-chunk-one": ([(0, 70, 1), (1, 30, 17), (3, 9, 1)], 12),
    # contexts of exactly one page, 16 rows, one block and one row past it
    "edges": ([(0, 7, 1), (1, 15, 1), (2, 255, 1), (3, 256, 1),
               (4, 250, 17)], 40),
    # three blocks then two then one: a first block in either half
    "odd-even": ([(1, 599, 1), (2, 299, 1), (4, 5, 2)], 80),
    "one-live": ([(3, 20, 5)], 6),
    "none-live": ([], 6),
}


# -- (c) the paged kernel on latent pages against the oracle --------------------
@pytest.mark.parametrize("dtype,tol,plan", [
    (jnp.float32, 2e-5, "mixed"), (jnp.bfloat16, 2e-2, "mixed"),
    *((jnp.float32, 2e-5, name) for name in list(_LATENT_PLANS)[1:])])
def test_latent_paged_kernel_matches_the_gather_oracle(monkeypatch, dtype, tol,
                                                       plan):
    monkeypatch.setattr(ragged_pallas, "_INTERPRET", True)
    rng = np.random.default_rng(11)
    plan, mp = _LATENT_PLANS[plan]
    heads, d, latent, bs, slots = 4, 128, 64, 8, 5
    t = 24
    pages = 40 + sum(-(-(start + n) // bs) for _, start, n in plan)
    pool = jnp.asarray(rng.normal(0, 1, (pages, 1, bs, d)), dtype)
    tables = np.full((slots, mp), -1, np.int32)
    slot_ids = np.zeros(t, np.int32)
    positions = np.zeros(t, np.int32)
    valid = np.zeros(t, bool)
    r, free = 0, list(rng.permutation(pages))
    for slot, start, n in plan:
        for c in range(-(-(start + n) // bs)):
            tables[slot, c] = free.pop()
        slot_ids[r:r + n] = slot
        positions[r:r + n] = np.arange(start, start + n)
        valid[r:r + n] = True
        r += n
    q = jnp.asarray(rng.normal(0, 1, (t, heads, d)), dtype)
    args = (jnp.asarray(tables), jnp.asarray(slot_ids),
            jnp.asarray(positions), jnp.asarray(valid))
    scale = 24 ** -0.5
    want = ragged.ragged_paged_attention(q, pool, None, *args, rep=heads,
                                         scale=scale, latent=latent)
    attend = ragged.make_attend(*args, heads, scale=scale, latent=latent)
    got = attend(q, pool, None)
    assert got.shape == (t, heads, latent) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    assert not np.asarray(got[r:], np.float32).any()      # nobody's rows
    # and one entry of several, read at its page offset
    joined = jnp.concatenate([jnp.zeros_like(pool), pool])
    np.testing.assert_allclose(
        np.asarray(attend(q, joined, None, first_page=pages), np.float32),
        np.asarray(want, np.float32), atol=tol)


# -- (d) the share test -----------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Four chips of four experts each: their held-experts parts, with the
    zero experts' part counted once, are the reference's uncut layer."""
    whole = _model(first_expert=0, experts_held=16)
    _, layers = _leaves(whole)
    lw = layers[0]
    rng = np.random.default_rng(12)
    h = jnp.asarray(rng.normal(0, 1, (40, whole.config.hidden_size)),
                    jnp.float32)
    uncut = ref.moe(lw, h, _ref_cfg(whole.config))
    zero_only = ref.moe(lw, h, _ref_cfg(whole.config, n_routed_experts=0))
    assert float(jnp.abs(zero_only).max()) > 0.1
    total = jnp.zeros_like(h)
    for first in (0, 4, 8, 12):
        share = _model(first_expert=first, experts_held=4)
        dec = _decoder_for(share)
        w = {"model.layers.0.mlp." + n[len("mlp."):]:
             (a[first:first + 4] if n.startswith("mlp.experts.") else a)
             for n, a in lw.items() if n.startswith("mlp.")}
        m, counters = dec._moe(w, 0, h, jnp.ones(40, bool))
        np.testing.assert_allclose(           # the reference's own share
            np.asarray(m), np.asarray(ref.moe(
                {n[len("model.layers.0."):]: a for n, a in w.items()}, h,
                _ref_cfg(share.config))), atol=2e-5)
        total = total + m - zero_only
        assert int(counters[0]) == 40 * 3
    np.testing.assert_allclose(np.asarray(total + zero_only),
                               np.asarray(uncut), atol=5e-5)


# -- (e) routed dispatch against the dense form, uneven routing -----------------
@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_routed_dispatch_equals_the_dense_form_when_routing_is_uneven(
        kernel, monkeypatch):
    """Expert 5 takes half the pairs (every token's first choice of two),
    expert 6 none; the grouped product (interpreted, as on the chip, and in
    ``jnp``) gives what every held expert on every token gives."""
    if kernel:
        monkeypatch.setattr(ge, "_INTERPRET", True)
    model = _model(moe_topk=2, hidden_size=128, q_lora_rank=32)
    cfg = model.config
    dec = _decoder_for(model)
    w = dict(dec.weights(model))
    bias = np.zeros(cfg.router_width, np.float32)
    bias[5], bias[6] = 2.0, -2.0
    w["model.layers.0.mlp.router.e_score_correction_bias"] = jnp.asarray(bias)
    rng = np.random.default_rng(13)
    t = 72
    h = jnp.asarray(rng.normal(0, 1, (t, cfg.hidden_size)), jnp.float32)
    valid = jnp.asarray(np.arange(t) % 9 != 8)
    pre = "model.layers.0.mlp."
    m, counters = dec._moe(w, 0, h, valid)
    pairs, held, zero, peak, touched = (int(c) for c in counters)
    n_valid = int(valid.sum())
    assert pairs == 2 * n_valid and peak == n_valid and held >= peak
    assert touched < 4                                   # expert 6 untouched
    want = lf.moe_dense(h, w[pre + "router.classifier.weight"],
                        w[pre + "router.e_score_correction_bias"],
                        w[pre + "experts.gate_proj"], w[pre + "experts.up_proj"],
                        w[pre + "experts.down_proj"], cfg)
    np.testing.assert_allclose(np.asarray(m)[np.asarray(valid)],
                               np.asarray(want)[np.asarray(valid)], atol=3e-5)
    # a row that is nobody's is routed nowhere: only its zero experts' share
    chosen, weight = lf.route(h, w[pre + "router.classifier.weight"],
                              w[pre + "router.e_score_correction_bias"], cfg)
    sizes, tile_group, n_live, row_pair, pair_row = ge.group_plan(
        jnp.where((chosen >= 4) & (chosen < 8) & valid[:, None], chosen - 4,
                  4).reshape(-1), 4)
    assert int(sizes[1]) == n_valid and int(sizes[2]) == 0
    assert int(sizes.sum()) == held == int((np.asarray(row_pair) >= 0).sum())
    live = np.asarray(row_pair) >= 0
    assert (np.asarray(pair_row)[np.asarray(row_pair)[live]]
            == np.nonzero(live)[0]).all()                # the two maps agree
    assert int(n_live) == int(np.ceil(np.asarray(sizes) / ge.TM).sum())


def test_group_plan_holds_every_pair_whatever_the_routing():
    for keys in ([0] * 37, [3] * 5 + [4] * 32, list(range(5)) * 7 + [4, 4]):
        keys = jnp.asarray(keys, jnp.int32)
        sizes, tile_group, n_live, row_pair, pair_row = ge.group_plan(
            keys, 4, 8)
        assert tile_group.shape == (-(-len(keys) // 8) + 4,)
        got = np.asarray(row_pair)
        held = np.nonzero(np.asarray(keys) < 4)[0]
        assert sorted(got[got >= 0]) == list(held)      # each pair once
        for tile in range(int(n_live)):
            rows = got[tile * 8:(tile + 1) * 8]
            assert {int(keys[p]) for p in rows[rows >= 0]} \
                == {int(tile_group[tile])}
        assert (np.asarray(tile_group)[int(n_live):] == 4).all()
        assert (np.asarray(pair_row)[np.asarray(keys) >= 4] == -1).all()


# -- (f) faults that must fail -----------------------------------------------------
@pytest.mark.parametrize("fault", FAULTS)
def test_a_part_left_out_or_wrong_moves_the_logits(fault):
    """Each fault moves the logits by far more than the 3e-5 the program is
    held to, so the comparison that passes above fails on each."""
    model = _model()
    ids = _ids(21, 40)
    right = _reference(model, ids)
    with faulty(fault, {}) as changed:
        wrong = _reference(model, ids, **changed)
    assert np.abs(right - wrong).max() > 100 * 3e-5
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    assert np.abs(got - right).max() < 3e-5 < np.abs(got - wrong).max()


# -- both kernels, compiled for the chip that is described, not attached --------
@pytest.fixture(scope="module")
def one_chip():
    """One v5e chip as a sharding (``tests/test_chip_smoke.py`` has the
    same): the TPU's compiler is installed here and compiles for a chip it
    is told about."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernels_compile_at_the_batch_cells_shapes(one_chip, monkeypatch):
    """Mosaic accepts the latent attention kernel at 320 rows of 64 heads
    on rows of 640 over the cell's joined pools, and the grouped product at
    3,840 pairs over 16 experts of 6144 x 2048 (what interpret mode cannot
    show); ``tools/kernel_check.py`` runs both against their oracles on the
    chip."""
    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rows, heads, row, latent, slots, table = 320, 64, 640, 512, 256, 74
    per_slot = shape((slots,), jnp.int32)
    compiled = jax.jit(
        lambda q, kp, tables, *meta:
        ragged_pallas.paged_attention(q, kp, None, tables, *meta, rep=heads,
                                      scale=192 ** -0.5,
                                      latent=latent)).lower(
        shape((rows, heads, row)), shape((8 * 18944, 1, 16, row)),
        shape((slots, table), jnp.int32), per_slot, per_slot, per_slot,
        shape((slots + 1,), jnp.int32)).compile()
    assert "latent_paged_attention" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes \
        == rows * heads * latent * 2
    pairs, experts, hidden, width = rows * 12, 16, 6144, 2048
    tm = ge.TM
    tiles = -(-pairs // tm) + experts
    assert (tm, tiles) == (64, 76)
    bank = shape((experts, hidden, width))
    assert ge.tiles(shape((tiles * tm, hidden)), bank, tm)
    monkeypatch.setattr(paddle.kernels, "on_tpu", lambda: True)
    compiled = jax.jit(ge.grouped_experts).lower(
        shape((tiles * tm, hidden)), shape((tiles,), jnp.int32),
        shape((), jnp.int32), bank, bank,
        shape((experts, width, hidden))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_experts" in text
