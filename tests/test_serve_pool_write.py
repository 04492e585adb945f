"""The serving step writes its new K/V into the pools in place
(``generation.step_ragged``: the stacked pools joined into one run of pages,
threaded through the layers, a page written whole a row).

Oracles that do not pass through ``generation.py``: the model's own full
forward (``models/llama.py``, ``models/gpt.py``) gives every position's
logits and, through hooks on its projections, every layer's K and V; the
pools are then written row by row here, into per-layer arrays.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation as G
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     apply_rope)
from paddle_tpu.serving import EngineConfig, ServingEngine
from paddle_tpu.serving import engine as engine_mod

pytestmark = pytest.mark.serve

VOCAB, HIDDEN, HEADS = 61, 32, 4
DECODERS = ["llama", "gpt"]


def _model(kind, layers=3):
    paddle.seed(11)
    if kind == "llama":
        cfg = LlamaConfig.tiny(vocab_size=VOCAB, hidden_size=HIDDEN,
                               layers=layers, heads=HEADS, kv_heads=2, seq=64)
        cfg.use_flash_attention = False
        return LlamaForCausalLM(cfg)
    return GPTForCausalLM(GPTConfig.tiny(vocab_size=VOCAB, hidden_size=HIDDEN,
                                         layers=layers, heads=HEADS, seq=64))


def _truth(model, ids):
    """The model's own forward over one sequence: logits [S, V] and each
    layer's keys and values [L, S, kvh, hd] as the attention saw them."""
    kept = {}

    def keep(name):
        return lambda _layer, _inp, out: kept.__setitem__(name, out._data)

    s, hd = len(ids), HIDDEN // HEADS
    if isinstance(model, LlamaForCausalLM):
        blocks = [b.self_attn for b in model.model.layers]
        hooks = [p.register_forward_post_hook(keep((i, n)))
                 for i, b in enumerate(blocks)
                 for n, p in (("k", b.k_proj), ("v", b.v_proj))]
    else:
        blocks = [b.attn for b in model.transformer.h]
        hooks = [b.qkv_proj.register_forward_post_hook(keep((i, "qkv")))
                 for i, b in enumerate(blocks)]
    try:
        logits = model(paddle.to_tensor(np.asarray([ids], np.int32)))
    finally:
        for h in hooks:
            h.remove()
    ks, vs = [], []
    for i in range(len(blocks)):
        if (i, "qkv") in kept:
            qkv = kept[(i, "qkv")].reshape(1, s, 3, HEADS, hd)
            k, v = qkv[:, :, 1], qkv[:, :, 2]
        else:
            k = kept[(i, "k")].reshape(1, s, -1, hd)
            v = kept[(i, "v")].reshape(1, s, -1, hd)
            _, k = apply_rope(k, k, model.model.rope_cos._data[:s],
                              model.model.rope_sin._data[:s])
        ks.append(np.asarray(k[0]))
        vs.append(np.asarray(v[0]))
    return np.asarray(logits._data[0]), np.stack(ks), np.stack(vs)


# -- (a) one mixed step against a row-by-row write ------------------------------
BS, PAGES, TABLE, SLOTS, BUDGET = 8, 12, 4, 4, 24
#: page-table rows: sequence A on three scattered pages, B on one, C on two,
#: slot 3 holds no page at all
TABLES = np.array([[5, 2, 9, -1], [7, -1, -1, -1], [0, 11, -1, -1],
                   [-1, -1, -1, -1]], np.int32)


def _rows(*groups):
    """Packed step arguments from (slot, positions, tokens, valid) groups,
    padded to the budget with invalid rows that point at slot 0, position 0
    (where sequence A's first row lives: written, they would clobber it)."""
    slot, pos, tok, valid = [], [], [], []
    for s_, positions, tokens, ok in groups:
        slot += [s_] * len(positions)
        pos += list(positions)
        tok += list(tokens)
        valid += [ok] * len(positions)
    pad = BUDGET - len(slot)
    return (np.asarray(tok + [1] * pad, np.int32),
            np.asarray(slot + [0] * pad, np.int32),
            np.asarray(pos + [0] * pad, np.int32),
            np.asarray(valid + [False] * pad, bool))


@pytest.mark.parametrize("kind", DECODERS)
def test_a_mixed_step_writes_what_a_row_by_row_write_does(kind):
    model = _model(kind)
    dec = G._decoder_for(model)
    w = dec.weights(model)
    rng = np.random.default_rng(3)
    seqs = [rng.integers(1, VOCAB, n).tolist() for n in (21, 7, 10)]
    truth = [_truth(model, ids) for ids in seqs]
    a, b, c = seqs
    shape = (dec.cache_entries, PAGES, dec.n_kv, BS, dec.hd)
    assert shape[0] == 3
    # what the pools hold before: noise, so a slot nobody wrote is told apart
    want_k = rng.standard_normal(shape).astype(np.float32)
    want_v = rng.standard_normal(shape).astype(np.float32)
    first_k = want_k.copy()
    kp, vp = jnp.asarray(want_k), jnp.asarray(want_v)
    step = jax.jit(lambda *args: engine_mod._ragged_forward(dec, None, w,
                                                            *args))
    steps = [
        # every sequence's first chunk
        _rows((0, range(5), a[:5], True), (1, range(6), b[:6], True),
              (2, range(9), c[:9], True)),
        # A's next chunk crosses two page boundaries (positions 5..17 over
        # pages 5, 2 and 9); B and C decode; then rows that must write
        # nothing: an invalid one aimed at C's new slot, one whose slot has
        # no page, one past the table's last column
        _rows((0, range(5, 18), a[5:18], True), (1, [6], b[6:7], True),
              (2, [9], c[9:10], True), (2, [9], [3], False),
              (3, [3], [5], True), (1, [40], [7], True)),
    ]
    checked = 0
    for tokens, slot, pos, valid in steps:
        logits, exits, kp, vp = step(tokens, slot, pos, valid, TABLES, kp, vp)
        assert exits is None and kp.shape == vp.shape == shape
        for r in range(BUDGET):
            col = pos[r] // BS
            if not valid[r] or col >= TABLE or TABLES[slot[r], col] < 0:
                continue                           # a row that writes nothing
            ref_logits, ks, vs = truth[slot[r]]
            for layer in range(shape[0]):          # the plain write
                want_k[layer, TABLES[slot[r], col], :, pos[r] % BS] = \
                    ks[layer, pos[r]]
                want_v[layer, TABLES[slot[r], col], :, pos[r] % BS] = \
                    vs[layer, pos[r]]
            np.testing.assert_allclose(np.asarray(logits[r]),
                                       ref_logits[pos[r]], atol=3e-5)
            checked += 1
        np.testing.assert_allclose(np.asarray(kp), want_k, atol=1e-5)
        np.testing.assert_allclose(np.asarray(vp), want_v, atol=1e-5)
    assert checked == 20 + 15
    # 35 rows were written into 35 slots and no other slot moved by a bit
    moved = (np.asarray(kp) != first_k).any(axis=(0, 2, 4))      # [P, bs]
    assert moved.sum() == 35


# -- (b) nothing the size of a pool is copied, stacked or sliced ----------------
@pytest.mark.parametrize("kind", DECODERS)
def test_the_step_program_holds_no_pool_sized_copy(kind):
    """The lowered step program of a 4-layer decoder: one layer's pool is
    no value's type (nothing is cut out of the stacked pools), and the
    stacked and the joined pools are results of nothing but the two
    reshapes and the page scatters (nothing is stacked or concatenated)."""
    model = _model(kind, layers=4)
    dec = G._decoder_for(model)
    w = dec.weights(model)
    t, pages = 16, 12
    tail = f"{dec.n_kv}x{BS}x{dec.hd}xf32>"
    i32 = jnp.zeros((t,), jnp.int32)
    pool = jnp.zeros((4, pages, dec.n_kv, BS, dec.hd), jnp.float32)
    text = jax.jit(lambda *a: engine_mod._engine_step_impl(
        dec, None, engine_mod._argmax_rows, None, *a)).lower(
            w, i32, i32, i32, i32, i32, jnp.zeros((t,), bool),
            jnp.zeros((SLOTS, TABLE), jnp.int32), pool, pool).as_text()
    one_layer = re.compile(rf"tensor<(1x)?{pages}x{tail}")
    assert not one_layer.search(text), one_layer.search(text).group(0)
    whole = re.compile(rf"-> tensor<(4x{pages}|{4 * pages})x{tail}")
    # a scatter prints its result type where its update region closes
    made_by = [m.group(1) if (m := re.search(r"stablehlo\.(\w+)", line))
               else line.split()[0]
               for line in text.splitlines()
               if whole.search(line) and "func.func" not in line]
    # a reshape in and a reshape out a pool; K and V scattered once a layer
    assert sorted(made_by) == ["reshape"] * 4 + ["})"] * 8, made_by
    assert text.count('"stablehlo.scatter"') == 8
    assert "stablehlo.concatenate" not in "".join(
        line for line in text.splitlines() if tail in line)


# -- (c) pages that move whole still serve the same tokens ----------------------
def _engine(model, **kw):
    cfg = dict(max_seqs=4, token_budget=16, block_size=8, num_blocks=24,
               max_model_len=64, enable_prefix_cache=False)
    cfg.update(kw)
    return ServingEngine(model, EngineConfig(**cfg))


@pytest.mark.parametrize("kind", DECODERS)
def test_a_copied_and_a_handed_off_page_serve_the_same_tokens(kind):
    model = _model(kind)
    prompt = np.random.default_rng(5).integers(1, VOCAB, 13).tolist()
    (want,) = _engine(model).generate_batch([prompt], max_new_tokens=9)
    # hand-off: the prefill engine's pages, installed in a decode engine
    pre = _engine(model, role="prefill")
    pre.submit(prompt, max_new_tokens=9)
    pre.run_until_idle(max_steps=20)
    (req, record), = pre.pop_handoffs()
    eng = _engine(model, role="decode")
    eng.import_handoff(req, record)
    for _ in range(3):
        eng.step()
    assert not req.done and req.pos % 8
    # copy on write: another holder appears on the boundary page, the
    # sequence gets a private copy and the holder scribbles on its own
    old = req.pages[-1]
    eng.pool._ref[old] += 1
    kept, released, cow = eng.pool.truncate(req.pages, req.pos)
    assert released == 0 and cow == (old, kept[-1]) and cow[1] != old
    req.pages = kept
    eng._kp, eng._vp = engine_mod._copy_page(eng._kp, eng._vp, *cow)
    eng._kp = eng._kp.at[:, old].set(7.0)
    eng._vp = eng._vp.at[:, old].set(7.0)
    eng.pool.release([old])
    eng.run_until_idle(max_steps=50)
    assert req.done and req.error is None
    assert req.result(0) == want
