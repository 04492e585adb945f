"""KV-cached autoregressive decoding (paddle_tpu.generation) — the serving
decode capability (reference: masked_multihead_attention_kernel.cu fused
decode + PaddleNLP-style generate loops).

Oracle strategy: the cached decode must reproduce the training forward's
logits exactly (full recompute per step)."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def _model(tied=False, kv_heads=2, seed=3):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab_size=61, hidden_size=32, layers=2, heads=4,
                           kv_heads=kv_heads, seq=64)
    cfg.use_flash_attention = False
    cfg.tie_word_embeddings = tied
    return LlamaForCausalLM(cfg)


def _greedy_oracle(model, ids, steps):
    """Naive loop: full forward recompute each step, argmax. The sequence
    is right-padded to one width for every step of every test (causal
    attention never sees the padding); a growing sequence would recompile
    every eager op at every step."""
    ids = np.asarray(ids)
    b, n = ids.shape
    cur = np.zeros((b, max(16, n + steps)), ids.dtype)
    cur[:, :n] = ids
    out = []
    for t in range(steps):
        logits = model(paddle.to_tensor(cur)).numpy()
        tok = np.argmax(logits[:, n + t - 1], axis=-1).astype(np.int32)
        out.append(tok)
        cur[:, n + t] = tok
    return np.stack(out, axis=1)


@pytest.mark.parametrize("kv_heads", [4, 2])   # MHA and GQA
def test_greedy_generate_matches_full_recompute(kv_heads):
    model = _model(kv_heads=kv_heads)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 61, (2, 7)).astype(np.int32)
    want = _greedy_oracle(model, ids, steps=6)
    got, finished = model.generate(paddle.to_tensor(ids), max_new_tokens=6)
    np.testing.assert_array_equal(got.numpy(), want)
    assert not finished.numpy().any()


def test_left_padded_batch_matches_single_rows():
    model = _model()
    rng = np.random.default_rng(1)
    p1 = rng.integers(0, 61, (1, 4)).astype(np.int32)
    p2 = rng.integers(0, 61, (1, 7)).astype(np.int32)
    # batch them left-padded to length 7
    ids = np.zeros((2, 7), np.int32)
    mask = np.zeros((2, 7), np.int32)
    ids[0, 3:] = p1[0]
    mask[0, 3:] = 1
    ids[1] = p2[0]
    mask[1] = 1
    got, _ = model.generate(paddle.to_tensor(ids),
                            attention_mask=paddle.to_tensor(mask),
                            max_new_tokens=5)
    want1 = _greedy_oracle(model, p1, 5)
    want2 = _greedy_oracle(model, p2, 5)
    np.testing.assert_array_equal(got.numpy()[0], want1[0])
    np.testing.assert_array_equal(got.numpy()[1], want2[0])


def test_right_padding_rejected():
    model = _model()
    ids = np.ones((1, 5), np.int32)
    mask = np.array([[1, 1, 1, 0, 0]], np.int32)   # right padding
    with pytest.raises(ValueError, match="LEFT-padded"):
        model.generate(paddle.to_tensor(ids),
                       attention_mask=paddle.to_tensor(mask),
                       max_new_tokens=2)


def test_eos_rows_keep_emitting_eos():
    model = _model()
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 61, (2, 5)).astype(np.int32)
    free, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=8)
    first = free.numpy()[:, 0]
    eos = int(first[0])                    # force row 0's first pick as eos
    got, finished = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                                   eos_token_id=eos)
    g = got.numpy()
    assert (g[0] == eos).all()             # finished row: eos forever
    assert finished.numpy()[0]
    if first[1] != eos:
        assert g[1, 0] == first[1]         # other row unaffected at step 0


def test_sampling_reproducible_and_top_k_respected():
    model = _model()
    rng = np.random.default_rng(4)
    ids = rng.integers(0, 61, (2, 6)).astype(np.int32)
    a, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                          do_sample=True, temperature=0.8, top_k=3, seed=7)
    b, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                          do_sample=True, temperature=0.8, top_k=3, seed=7)
    np.testing.assert_array_equal(a.numpy(), b.numpy())
    c, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                          do_sample=True, temperature=0.8, top_k=3, seed=8)
    assert not np.array_equal(a.numpy(), c.numpy())
    # every sampled first token is within the top-3 of the prefill logits
    logits = model(paddle.to_tensor(ids)).numpy()[:, -1]
    top3 = np.argsort(logits, axis=-1)[:, -3:]
    for row in range(2):
        assert a.numpy()[row, 0] in top3[row]


def test_tied_embeddings_generate():
    model = _model(tied=True)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 61, (1, 6)).astype(np.int32)
    want = _greedy_oracle(model, ids, 4)
    got, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4)
    np.testing.assert_array_equal(got.numpy(), want)


def test_masked_multihead_attention_matches_dense():
    """The fused decode op (incubate parity surface): one step against a
    cache must equal dense attention over the concatenated sequence."""
    import paddle_tpu.incubate.nn.functional as IF

    rng = np.random.default_rng(6)
    b, h, m, d = 2, 4, 8, 16
    cur = 5                                # live cache entries per row
    cache = rng.standard_normal((2, b, h, m, d)).astype(np.float32)
    cache[:, :, :, cur:] = 0.0
    x = rng.standard_normal((b, 3 * h * d)).astype(np.float32)
    lens = np.full((b, 1), cur, np.int32)
    out, new_cache = IF.masked_multihead_attention(
        paddle.to_tensor(x), cache_kv=paddle.to_tensor(cache),
        sequence_lengths=paddle.to_tensor(lens))
    qkv = x.reshape(b, 3, h, d)
    q, kn, vn = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    kc = np.concatenate([cache[0][:, :, :cur], kn[:, :, None]], axis=2)
    vc = np.concatenate([cache[1][:, :, :cur], vn[:, :, None]], axis=2)
    scores = np.einsum("bhd,bhmd->bhm", q, kc) / np.sqrt(d)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhm,bhmd->bhd", p, vc).reshape(b, h * d)
    np.testing.assert_allclose(out.numpy(), want, rtol=1e-5, atol=1e-5)
    # the cache gained this step's k/v at slot `cur`
    nc = new_cache.numpy()
    np.testing.assert_allclose(nc[0][:, :, cur], kn, rtol=1e-6)
    np.testing.assert_allclose(nc[1][:, :, cur], vn, rtol=1e-6)


def test_masked_multihead_attention_rejects_quant_args():
    import paddle_tpu.incubate.nn.functional as IF
    with pytest.raises(NotImplementedError):
        IF.masked_multihead_attention(
            paddle.to_tensor(np.zeros((1, 12), np.float32)),
            cache_kv=paddle.to_tensor(np.zeros((2, 1, 1, 4, 4), np.float32)),
            sequence_lengths=paddle.to_tensor(np.zeros((1, 1), np.int32)),
            qkv_out_scale=paddle.to_tensor(np.ones((3, 1, 4), np.float32)))


def test_masked_multihead_attention_rejects_full_cache():
    import paddle_tpu.incubate.nn.functional as IF
    b, h, m, d = 1, 2, 4, 8
    cache = np.zeros((2, b, h, m, d), np.float32)
    x = np.zeros((b, 3 * h * d), np.float32)
    with pytest.raises(ValueError, match="cache is full"):
        IF.masked_multihead_attention(
            paddle.to_tensor(x), cache_kv=paddle.to_tensor(cache),
            sequence_lengths=paddle.to_tensor(np.full((b, 1), m, np.int32)))


def test_weight_updates_reflected_without_decoder_rebuild():
    """Weights are a jit ARGUMENT, not a capture: after an update the same
    compiled decoder must produce the new model's tokens (and no stale
    arrays are pinned by a rebuilt cache)."""
    model = _model(seed=9)
    rng = np.random.default_rng(9)
    ids = rng.integers(0, 61, (1, 6)).astype(np.int32)
    a, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4)
    dec_before = model.__dict__["_decode_cache"]
    # perturb one projection hard enough to change the argmax path
    w = model.model.layers[0].self_attn.q_proj.weight
    w._data = w._data + 0.5
    b, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4)
    assert model.__dict__["_decode_cache"] is dec_before   # no rebuild
    want = _greedy_oracle(model, ids, 4)
    np.testing.assert_array_equal(b.numpy(), want)
    assert not np.array_equal(a.numpy(), b.numpy())


def test_masked_multihead_attention_traced_overflow_is_nan():
    """Under tracing the full-cache guard cannot raise; the overflowed
    row's output must be NaN-poisoned, never silently wrong."""
    import jax
    import paddle_tpu.incubate.nn.functional as IF

    b, h, m, d = 2, 2, 4, 8
    cache = jnp.zeros((2, b, h, m, d), jnp.float32)
    x = jnp.ones((b, 3 * h * d), jnp.float32)
    lens = jnp.array([[2], [m]], jnp.int32)       # row 1 overflows

    def f(x_, cache_, lens_):
        out, _ = IF.masked_multihead_attention(
            paddle.to_tensor(x_), cache_kv=paddle.to_tensor(cache_),
            sequence_lengths=paddle.to_tensor(lens_))
        return out._data

    out = jax.jit(f)(x, cache, lens)
    assert np.isfinite(np.asarray(out[0])).all()
    assert np.isnan(np.asarray(out[1])).all()


def test_gpt_greedy_generate_matches_full_recompute():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(12)
    cfg = GPTConfig.tiny(vocab_size=53, hidden_size=32, layers=2, heads=4,
                         seq=64, num_experts=0)
    model = GPTForCausalLM(cfg)
    rng = np.random.default_rng(12)
    ids = rng.integers(0, 53, (2, 6)).astype(np.int32)
    want = _greedy_oracle(model, ids, 3)
    got, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=3)
    np.testing.assert_array_equal(got.numpy(), want)


def _moe_model(gate="naive", seed=13, experts=4, top_k=2):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    cfg = GPTConfig.tiny(vocab_size=53, hidden_size=32, layers=2, heads=4,
                         seq=64, num_experts=experts, moe_every=1,
                         moe_top_k=top_k, moe_gate=gate)
    return GPTForCausalLM(cfg)


def test_gpt_moe_greedy_generate_matches_full_recompute():
    """MoE decode parity: with an unbounded gate (naive = no capacity
    dropping, eval policy deterministic) the cached decode must reproduce
    the full-recompute greedy tokens exactly."""
    model = _moe_model(gate="naive")
    model.eval()
    rng = np.random.default_rng(31)
    ids = rng.integers(0, 53, (2, 6)).astype(np.int32)
    want = _greedy_oracle(model, ids, 5)
    got, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5)
    np.testing.assert_array_equal(got.numpy(), want)


def test_gpt_moe_generate_gshard_and_quant_smoke():
    """A GShard gate's eval capacity dropping depends on batch composition,
    which a cached decode cannot reproduce — generate() must refuse LOUDLY
    rather than silently diverge from model(x). With _capacity_override
    making eval routing no-drop, decode runs and matches the
    full-recompute oracle exactly; weight-only quant composes (attention
    projections quantize, expert banks stay fp)."""
    model = _moe_model(gate="gshard", seed=14)
    model.eval()
    rng = np.random.default_rng(32)
    ids = rng.integers(0, 53, (2, 5)).astype(np.int32)
    with pytest.raises(NotImplementedError, match="capacity"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=4)
    for blk in model.transformer.h:
        if getattr(blk, "is_moe", False):
            blk.mlp._capacity_override = 64  # >= tokens-per-forward: no-drop
    want = _greedy_oracle(model, ids, 4)
    toks, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4)
    np.testing.assert_array_equal(toks.numpy(), want)
    q8, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                           quant="weight_only_int8")
    assert q8.numpy().shape == (2, 4)
    # a too-small override means the eval forward WOULD drop: refuse
    for blk in model.transformer.h:
        if getattr(blk, "is_moe", False):
            blk.mlp._capacity_override = 4
    with pytest.raises(ValueError, match="tokens-per-forward"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=4)
    # expert banks are NOT in the quant cache (3-D fp weights)
    refs, leaves = model.__dict__["_quant_weights_cache"]["weight_only_int8"]
    assert not any(".mlp." in k for k in leaves)
    assert any(".attn." in k for k in leaves)


def test_gpt_moe_expert_list_backend_rejected():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import MoELayer
    import paddle_tpu.nn as nn
    paddle.seed(15)
    cfg = GPTConfig.tiny(vocab_size=53, hidden_size=32, layers=2, heads=4,
                         seq=64, num_experts=2, moe_every=1)
    model = GPTForCausalLM(cfg)
    blk = model.transformer.h[0]
    blk.mlp = MoELayer(32, 64, num_expert=2, gate="naive",
                       experts=[nn.Linear(32, 32) for _ in range(2)])
    ids = np.zeros((1, 4), np.int32)
    with pytest.raises(NotImplementedError, match="batched-expert"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=2)


def test_untying_head_rebuilds_decoder():
    """Head tying is baked into the traced logits branch: changing it must
    rebuild the decoder, not silently keep the old branch."""
    import paddle_tpu.nn as nn

    model = _model(tied=True, seed=15)
    rng = np.random.default_rng(15)
    ids = rng.integers(0, 61, (1, 5)).astype(np.int32)
    model.generate(paddle.to_tensor(ids), max_new_tokens=2)
    dec_tied = model.__dict__["_decode_cache"]
    paddle.seed(99)
    model.lm_head = nn.Linear(32, 61, bias_attr=False)   # untie
    got, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=3)
    assert model.__dict__["_decode_cache"] is not dec_tied
    want = _greedy_oracle(model, ids, 3)
    np.testing.assert_array_equal(got.numpy(), want)


def test_generate_with_tp_sharded_weights_matches_serial():
    """Serving decode on a mesh: weights enter the compiled generate loop
    as (possibly TP-sharded) jit arguments, so GSPMD propagates the
    Megatron layout through prefill + decode with no decoder changes —
    tokens must match the serial run exactly."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.parallel import SpmdTrainer, make_hybrid_mesh

    model = _model(seed=21)
    rng = np.random.default_rng(21)
    ids = rng.integers(0, 61, (2, 8)).astype(np.int32)
    ref, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5)

    tr = SpmdTrainer(model,
                     opt.SGD(learning_rate=0.0,
                             parameters=model.parameters()),
                     lambda m, x, y: m.compute_loss(m(x), y),
                     mesh=make_hybrid_mesh(mp=4))
    tr._place_params()
    q = model.model.layers[0].self_attn.q_proj.weight._data
    assert "mp" in str(q.sharding.spec)          # really TP-sharded now
    model.__dict__.pop("_decode_cache", None)    # fresh trace, sharded args
    got, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5)
    np.testing.assert_array_equal(got.numpy(), ref.numpy())


def test_block_multihead_attention_matches_contiguous_cache():
    """Paged decode attention (PagedAttention-style serving kernel,
    reference block_multi_head_attention_kernel.cu): gathering each row's
    pages through its block table must equal dense attention over the
    logically-contiguous cache, and this step's K/V must land in the
    right page slot."""
    import paddle_tpu.incubate.nn.functional as IF

    rng = np.random.default_rng(40)
    b, h, d, bs, nb, mp = 2, 2, 8, 4, 10, 3   # pool of 10 pages, 3 per row
    kpool = rng.standard_normal((nb, h, bs, d)).astype(np.float32)
    vpool = rng.standard_normal((nb, h, bs, d)).astype(np.float32)
    # row 0 owns pages [7, 2], row 1 owns [5, 0, 3]
    tables = np.array([[7, 2, -1], [5, 0, 3]], np.int32)
    lens = np.array([[5], [9]], np.int32)     # cached tokens per row
    x = rng.standard_normal((b, 3 * h * d)).astype(np.float32)

    out, _, kc2, vc2 = IF.block_multihead_attention(
        paddle.to_tensor(x), paddle.to_tensor(kpool),
        paddle.to_tensor(vpool), seq_lens_decoder=paddle.to_tensor(lens),
        block_tables=paddle.to_tensor(tables), block_size=bs)

    qkv = x.reshape(b, 3, h, d)
    q, kn, vn = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    kc2_np, vc2_np = kc2.numpy(), vc2.numpy()
    for row in range(b):
        ln = int(lens[row, 0])
        page = tables[row, ln // bs]
        slot = ln % bs
        # the step write landed in the row's current page
        np.testing.assert_allclose(kc2_np[page, :, slot], kn[row], rtol=1e-6)
        np.testing.assert_allclose(vc2_np[page, :, slot], vn[row], rtol=1e-6)
        # contiguous-cache oracle from the UPDATED pools
        pages = [p for p in tables[row] if p >= 0]
        kfull = np.concatenate([kc2_np[p].transpose(1, 0, 2)
                                for p in pages])[:ln + 1]  # [T, H, D]
        vfull = np.concatenate([vc2_np[p].transpose(1, 0, 2)
                                for p in pages])[:ln + 1]
        scores = np.einsum("hd,thd->ht", q[row], kfull) / np.sqrt(d)
        pr = np.exp(scores - scores.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        want = np.einsum("ht,thd->hd", pr, vfull).reshape(h * d)
        np.testing.assert_allclose(out.numpy()[row], want, rtol=1e-5,
                                   atol=1e-5)


def test_block_multihead_attention_rejects_prefill_and_quant():
    import paddle_tpu.incubate.nn.functional as IF

    b, h, d, bs = 1, 1, 4, 2
    kpool = paddle.to_tensor(np.zeros((2, h, bs, d), np.float32))
    x = paddle.to_tensor(np.zeros((b, 3 * h * d), np.float32))
    tables = paddle.to_tensor(np.zeros((b, 1), np.int32))
    lens = paddle.to_tensor(np.zeros((b, 1), np.int32))
    with pytest.raises(NotImplementedError, match="prefill"):
        IF.block_multihead_attention(
            x, kpool, kpool,
            seq_lens_encoder=paddle.to_tensor(np.ones((b, 1), np.int32)),
            seq_lens_decoder=lens, block_tables=tables, block_size=bs)
    with pytest.raises(NotImplementedError, match="quant"):
        IF.block_multihead_attention(
            x, kpool, kpool, seq_lens_decoder=lens, block_tables=tables,
            block_size=bs,
            cache_k_quant_scales=paddle.to_tensor(np.ones(1, np.float32)))


def test_block_multihead_attention_guards():
    """Page-boundary safety: an unassigned (-1) page or an outgrown block
    table raises eagerly and NaN-poisons (write-dropped) under tracing —
    never wraps into another sequence's pool page."""
    import jax as _jax
    import paddle_tpu.incubate.nn.functional as IF

    b, h, d, bs, nb = 1, 1, 4, 2, 4
    kpool = np.ones((nb, h, bs, d), np.float32)
    x = np.ones((b, 3 * h * d), np.float32)
    tables = np.array([[1, -1]], np.int32)
    full = np.array([[2]], np.int32)          # page 0 full, next unassigned
    kp = paddle.to_tensor(kpool)
    with pytest.raises(ValueError, match="unassigned"):
        IF.block_multihead_attention(
            paddle.to_tensor(x), kp, kp,
            seq_lens_decoder=paddle.to_tensor(full),
            block_tables=paddle.to_tensor(tables), block_size=bs)
    with pytest.raises(ValueError, match="outgrew"):
        IF.block_multihead_attention(
            paddle.to_tensor(x), kp, kp,
            seq_lens_decoder=paddle.to_tensor(np.array([[4]], np.int32)),
            block_tables=paddle.to_tensor(tables), block_size=bs)

    # traced: same inputs NaN-poison the bad row, drop the write, and do
    # NOT touch pool page nb-1 (the raw -1 wrap target)
    def f(x_, kp_, lens_, tab_):
        out, _, kc, _ = IF.block_multihead_attention(
            paddle.to_tensor(x_), paddle.to_tensor(kp_),
            paddle.to_tensor(kp_), seq_lens_decoder=paddle.to_tensor(lens_),
            block_tables=paddle.to_tensor(tab_), block_size=bs)
        return out._data, kc._data

    out, kc = _jax.jit(f)(x, kpool, full, tables)
    assert np.isnan(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(kc), kpool)  # nothing written


def _seq_logprob(model, ids, cont):
    """Total log-prob of continuation `cont` given prompt `ids` under the
    model (full recompute)."""
    cur = np.concatenate([ids, cont[None]], axis=1)
    logits = model(paddle.to_tensor(cur)).numpy().astype(np.float64)
    lp = 0.0
    for t, tok in enumerate(cont):
        row = logits[0, ids.shape[1] - 1 + t]
        row = row - row.max()
        lp += row[tok] - np.log(np.exp(row).sum())
    return lp


def test_beam_search_never_worse_than_greedy():
    model = _model(seed=41)
    rng = np.random.default_rng(41)
    ids = rng.integers(0, 61, (1, 6)).astype(np.int32)
    greedy, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5)
    beam, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                             num_beams=4)
    lp_g = _seq_logprob(model, ids, greedy.numpy()[0])
    lp_b = _seq_logprob(model, ids, beam.numpy()[0])
    assert lp_b >= lp_g - 1e-6, (lp_b, lp_g)


def test_beam_one_equals_greedy():
    model = _model(seed=42)
    rng = np.random.default_rng(42)
    ids = rng.integers(0, 61, (2, 6)).astype(np.int32)
    a, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4)
    b, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                          num_beams=1)
    np.testing.assert_array_equal(a.numpy(), b.numpy())


def test_beam_search_eos_finishes_beams():
    model = _model(seed=43)
    rng = np.random.default_rng(43)
    ids = rng.integers(0, 61, (1, 5)).astype(np.int32)
    free, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                             num_beams=3)
    eos = int(free.numpy()[0, 0])
    got, fin = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                              num_beams=3, eos_token_id=eos)
    g = got.numpy()[0]
    if (g == eos).any():
        first = int(np.argmax(g == eos))
        assert (g[first:] == eos).all()       # eos persists on the beam
    assert fin.numpy().shape == (1,)


def test_beam_sampling_rejected():
    model = _model(seed=44)
    ids = np.zeros((1, 4), np.int32)
    with pytest.raises(NotImplementedError, match="beam search with samp"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=2, num_beams=2,
                       do_sample=True)


def test_block_multihead_attention_block_size_authority():
    """The cache layout is authoritative: an explicit mismatching
    block_size is rejected; -1/64 mean unset."""
    import paddle_tpu.incubate.nn.functional as IF

    b, h, d, bs = 1, 1, 4, 2
    kp = paddle.to_tensor(np.zeros((2, h, bs, d), np.float32))
    x = paddle.to_tensor(np.zeros((b, 3 * h * d), np.float32))
    tables = paddle.to_tensor(np.zeros((b, 1), np.int32))
    lens = paddle.to_tensor(np.zeros((b, 1), np.int32))
    with pytest.raises(ValueError, match="does not match the cache page"):
        IF.block_multihead_attention(x, kp, kp, seq_lens_decoder=lens,
                                     block_tables=tables, block_size=8)
    with pytest.raises(NotImplementedError, match="cachekv_quant"):
        IF.block_multihead_attention(x, kp, kp, seq_lens_decoder=lens,
                                     block_tables=tables, block_size=bs,
                                     use_dynamic_cachekv_quant=True)
    # default 64 is treated as unset: works with a 2-slot cache
    out, _, _, _ = IF.block_multihead_attention(
        x, kp, kp, seq_lens_decoder=lens, block_tables=tables)
    assert np.isfinite(out.numpy()).all()


def test_gpt_beam_search_never_worse_than_greedy():
    """The beam loop is decoder-agnostic: same property holds for GPT."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(45)
    cfg = GPTConfig.tiny(vocab_size=53, hidden_size=32, layers=2, heads=4,
                         seq=64, num_experts=0)
    model = GPTForCausalLM(cfg)
    rng = np.random.default_rng(45)
    ids = rng.integers(0, 53, (1, 6)).astype(np.int32)
    greedy, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=3)
    beam, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=3,
                             num_beams=3)
    lp_g = _seq_logprob(model, ids, greedy.numpy()[0])
    lp_b = _seq_logprob(model, ids, beam.numpy()[0])
    assert lp_b >= lp_g - 1e-6, (lp_b, lp_g)


def test_repetition_penalty_steers_away_from_seen_tokens():
    model = _model(seed=46)
    rng = np.random.default_rng(46)
    ids = rng.integers(0, 61, (1, 6)).astype(np.int32)
    base, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4)
    pen, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                            repetition_penalty=1000.0)
    # a huge penalty must keep the FIRST generated token out of the
    # prompt's token set (unseen tokens are unpenalized)
    assert pen.numpy()[0, 0] not in set(ids[0].tolist())
    # and no token repeats within the penalized continuation
    g = pen.numpy()[0]
    assert len(set(g.tolist())) == len(g), g
    # neutral penalty is the default path
    neutral, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                                repetition_penalty=1.0)
    np.testing.assert_array_equal(neutral.numpy(), base.numpy())
    with pytest.raises(NotImplementedError, match="repetition_penalty"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=2, num_beams=2,
                       repetition_penalty=2.0)


# -- weight-only int8 decode (reference weight_only_linear/llm_int8) ----------

def _snap_quant(model, bits):
    """Overwrite every quantizable matrix with its int8/int4-representable
    projection (quantize->dequantize), so the quant decode is LOSSLESS up
    to summation-order ulps and must reproduce the fp tokens exactly."""
    from paddle_tpu.generation import _decoder_for, _wq
    from paddle_tpu.quantization._kernels import dequantize_weight_arrays
    dec = _decoder_for(model)
    names, _lm = dec.quant_plan()
    for name, t in model.named_state().items():
        if name in names:
            q, s = _wq(t._data, bits=bits)
            t._data = dequantize_weight_arrays(
                q, s, n_rows=t._data.shape[0]).astype(t._data.dtype)


@pytest.mark.parametrize("algo,bits", [("weight_only_int8", 8),
                                       ("weight_only_int4", 4)])
@pytest.mark.parametrize("tied", [False, True])
def test_weight_only_decode_lossless_weights_exact(tied, algo, bits):
    model = _model(tied=tied, seed=21)
    _snap_quant(model, bits)
    if tied:
        # the tied head quantizes the embedding TABLE too (__lm::q source)
        emb = model.model.embed_tokens.weight
        from paddle_tpu.generation import _wq
        from paddle_tpu.quantization._kernels import dequantize_weight_arrays
        q, s = _wq(emb._data.T, bits=bits)
        emb._data = dequantize_weight_arrays(
            q, s, n_rows=emb._data.T.shape[0]).T.astype(emb._data.dtype)
    rng = np.random.default_rng(21)
    ids = rng.integers(0, 61, (2, 7)).astype(np.int32)
    fp, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=8)
    qq, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                           quant=algo)
    np.testing.assert_array_equal(fp.numpy(), qq.numpy())


def test_weight_only_int8_pytree_and_cache():
    from paddle_tpu.generation import _decoder_for
    model = _model(seed=22)
    rng = np.random.default_rng(22)
    ids = rng.integers(0, 61, (1, 5)).astype(np.int32)
    out1, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=3,
                             quant="weight_only_int8")
    refs, qw = model.__dict__["_quant_weights_cache"]["weight_only_int8"]
    # the cache payload is ONLY int8/scale leaves (no fp copies pinned),
    # and the invalidation snapshot is weakrefs
    import weakref
    assert all(isinstance(r, weakref.ref) for r in refs.values())
    qleaves = [k for k in qw if k.endswith("::q")]
    assert qleaves and all(qw[k].dtype == jnp.int8 for k in qleaves)
    assert set(qw) == {k for k in qw if k.endswith(("::q", "::s"))}
    # second call with unchanged weights reuses the cached quantization
    model.generate(paddle.to_tensor(ids), max_new_tokens=3,
                   quant="weight_only_int8")
    cache = model.__dict__["_quant_weights_cache"]
    assert cache["weight_only_int8"][1] is qw
    # int4 coexists in the cache without evicting the int8 snapshot
    model.generate(paddle.to_tensor(ids), max_new_tokens=3,
                   quant="weight_only_int4")
    assert cache["weight_only_int8"][1] is qw
    assert cache["weight_only_int4"][1] is not qw
    # swapping any weight array invalidates the snapshot cache
    w = model.model.layers[0].self_attn.q_proj.weight
    w._data = w._data + 0.5
    out3, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=3,
                             quant="weight_only_int8")
    assert cache["weight_only_int8"][1] is not qw
    # and the fp path still works interleaved (different pytree signature)
    model.generate(paddle.to_tensor(ids), max_new_tokens=3)


def test_weight_only_int8_gpt_and_beam():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(23)
    cfg = GPTConfig.tiny(vocab_size=53, hidden_size=32, layers=2, heads=4,
                         seq=64)
    model = GPTForCausalLM(cfg)
    rng = np.random.default_rng(23)
    ids = rng.integers(0, 53, (2, 6)).astype(np.int32)
    toks, fin = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                               quant="weight_only_int8")
    assert toks.numpy().shape == (2, 4)
    assert (toks.numpy() >= 0).all() and (toks.numpy() < 53).all()
    # beam search threads the same quantized pytree
    btoks, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                              num_beams=2, quant="weight_only_int8")
    assert btoks.numpy().shape == (2, 4)
    with pytest.raises(NotImplementedError, match="weight_only_int8"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                       quant="int4")


def test_equal_config_models_share_compiled_decoders():
    """The decoder is a static jit arg hashed by config: a second model
    with the same architecture (predictor-pool clone, reloaded
    checkpoint) must NOT recompile the generate program."""
    from paddle_tpu import generation as G
    m1 = _model(seed=51)
    rng = np.random.default_rng(51)
    ids = rng.integers(0, 61, (1, 6)).astype(np.int32)
    m1.generate(paddle.to_tensor(ids), max_new_tokens=4)
    dec1 = G._decoder_for(m1)
    gen_jit = G._DEC_JIT[dec1][0]
    size = gen_jit._cache_size()
    registry = len(G._DEC_JIT)
    m2 = _model(seed=52)          # same config, different weights
    a, _ = m2.generate(paddle.to_tensor(ids), max_new_tokens=4)
    assert G._DEC_JIT[G._decoder_for(m2)][0] is gen_jit  # same entry
    assert gen_jit._cache_size() == size      # shared executable
    assert len(G._DEC_JIT) == registry        # no new registry entry
    # and it really used m2's weights, not m1's
    b, _ = m1.generate(paddle.to_tensor(ids), max_new_tokens=4)
    assert not np.array_equal(a.numpy(), b.numpy())


def test_decoder_jit_registry_is_bounded():
    """Cycling many architectures must not grow executables forever: the
    registry LRU-evicts, dropping the evicted decoder's whole jit cache."""
    from paddle_tpu import generation as G

    class _FakeDec:       # hashable stand-in for a decoder fingerprint
        pass

    saved = dict(G._DEC_JIT)
    try:
        first = _FakeDec()
        G._jits_for(first)
        for _ in range(G._DEC_JIT_MAX + 3):
            G._jits_for(_FakeDec())
        assert len(G._DEC_JIT) <= G._DEC_JIT_MAX
        assert first not in G._DEC_JIT        # oldest evicted
    finally:
        G._DEC_JIT.clear()
        G._DEC_JIT.update(saved)              # don't evict real decoders


def test_moe_block_mutation_rebuilds_decoder():
    """Mutating MoE blocks after a generate() must rebuild the cached
    decoder (stale routing would silently diverge from forward)."""
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import MoELayer
    from paddle_tpu.incubate.distributed.models.moe.gate import BaseGate
    import paddle_tpu.nn as nn
    model = _moe_model(gate="naive", seed=16)
    model.eval()
    ids = np.zeros((1, 4), np.int32)
    model.generate(paddle.to_tensor(ids), max_new_tokens=2)
    # swap to the unsupported list backend AFTER the decoder was cached:
    # the guard must still fire (decoder rebuilt, not reused stale)
    model.transformer.h[0].mlp = MoELayer(
        32, 64, num_expert=4, gate="naive",
        experts=[nn.Linear(32, 32) for _ in range(4)])
    with pytest.raises(NotImplementedError, match="batched-expert"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=2)

    # custom gate forward overrides are rejected loudly, not mis-decoded
    class WeirdGate(BaseGate):
        def forward(self, x):
            return super().forward(x * 2.0)

    model2 = _moe_model(gate="naive", seed=17)
    model2.eval()
    model2.generate(paddle.to_tensor(ids), max_new_tokens=2)
    model2.transformer.h[0].mlp.gate = WeirdGate(32, 4)
    with pytest.raises(NotImplementedError, match="WeirdGate"):
        model2.generate(paddle.to_tensor(ids), max_new_tokens=2)
