"""FlashMask sparse-mask attention: Pallas kernel (interpret mode) vs the
dense-mask oracle, canonicalization semantics, and the functional wrapper.

Reference semantics: paddle.nn.functional.flashmask_attention
(flash_attention.py:1299) — column-wise startend_row_indices with
causal x {1,2}-col and non-causal x {2,4}-col forms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.kernels import flash_pallas as fp
from paddle_tpu.nn.functional.attention import (_canonical_startend,
                                                _flashmask_dense_visible,
                                                _sdpa_reference)


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setattr(fp, "_INTERPRET", True)
    yield


def _rand_bhsd(b, h, s, d, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    return q, k, v


def _doc_bounds_causal(s, doc_len, b, h):
    """Causal document masking: key column j's visible rows end at the end
    of j's document — the canonical flashmask use case."""
    j = np.arange(s)
    doc_end = (j // doc_len + 1) * doc_len
    se = np.broadcast_to(doc_end.astype(np.int32)[None, None, :, None],
                         (b, h, s, 1))
    return jnp.asarray(se)


def _oracle_bhsd(q, k, v, visible):
    # dense-mask reference in [b, h, s, d] layout
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(visible, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, v)


@pytest.mark.parametrize("causal,ncol", [(True, 1), (True, 2), (False, 2),
                                         (False, 4)])
def test_kernel_matches_dense_oracle(causal, ncol):
    b, h, s, d = 1, 2, 256, 64
    q, k, v = _rand_bhsd(b, h, s, d)
    rng = np.random.default_rng(1)
    if causal and ncol == 1:
        se = _doc_bounds_causal(s, 64, b, h)
    elif causal:
        lts = rng.integers(1, s, (b, h, s, 1))
        lte = np.minimum(lts + rng.integers(0, s, (b, h, s, 1)), s)
        se = jnp.asarray(np.concatenate([lts, lte], -1).astype(np.int32))
    elif ncol == 2:
        lts = rng.integers(1, s, (b, h, s, 1))
        ute = rng.integers(0, s, (b, h, s, 1))
        se = jnp.asarray(np.concatenate([lts, ute], -1).astype(np.int32))
    else:
        lts = rng.integers(1, s, (b, h, s, 1))
        lte = np.minimum(lts + rng.integers(0, 64, (b, h, s, 1)), s)
        uts = rng.integers(0, s, (b, h, s, 1))
        ute = np.minimum(uts + rng.integers(0, 64, (b, h, s, 1)), s)
        se = jnp.asarray(
            np.concatenate([lts, lte, uts, ute], -1).astype(np.int32))
    bounds = _canonical_startend(se, s, causal)
    visible = _flashmask_dense_visible(bounds, s, s, causal, None)
    out = fp.flashmask_attention(q, k, v, bounds, causal, None, None, 128,
                                 128)
    ref = _oracle_bhsd(q, k, v, visible)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_kernel_gradients_match_dense_oracle():
    b, h, s, d = 1, 1, 256, 64
    q, k, v = _rand_bhsd(b, h, s, d, seed=2)
    se = _doc_bounds_causal(s, 128, b, h)
    bounds = _canonical_startend(se, s, True)
    visible = _flashmask_dense_visible(bounds, s, s, True, None)
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def f_kernel(q, k, v):
        return jnp.sum(fp.flashmask_attention(q, k, v, bounds, True, None,
                                              None, 128, 128) * w)

    def f_ref(q, k, v):
        return jnp.sum(_oracle_bhsd(q, k, v, visible) * w)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4,
                                   rtol=2e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("block_q,block_k", [(256, 512), (512, 256),
                                             (512, 512), (None, None)],
                         ids=["256x512", "512x256", "512x512", "default"])
def test_large_tiles_with_documents_inside_a_tile(block_q, block_k):
    """Documents of 64 and 192 tokens under tiles of 256 and 512: a tile
    holds several documents, so the block skip is coarser than the mask and
    the mask inside the tile does the rest. out, dq, dk, dv against the
    dense oracle; the last case takes the tiles the call sizes itself."""
    b, h, s, d = 1, 1, 512, 64
    q, k, v = _rand_bhsd(b, h, s, d, seed=4)
    j = np.arange(s)
    doc_end = np.where(j < 256, (j // 64 + 1) * 64,
                       np.minimum(256 + ((j - 256) // 192 + 1) * 192, s))
    se = jnp.asarray(np.broadcast_to(
        doc_end.astype(np.int32)[None, None, :, None], (b, h, s, 1)))
    bounds = _canonical_startend(se, s, True)
    visible = _flashmask_dense_visible(bounds, s, s, True, None)
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def run(attention):
        out, vjp = jax.vjp(attention, q, k, v)
        return (out, *vjp(jnp.broadcast_to(w, out.shape)))

    got = run(lambda q, k, v: fp.flashmask_attention(
        q, k, v, bounds, True, None, None, block_q, block_k))
    want = run(lambda q, k, v: _oracle_bhsd(q, k, v, visible))
    for a, b_, name, tol in zip(got, want, ("out", "dq", "dk", "dv"),
                                (2e-5, 2e-4, 2e-4, 2e-4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=tol,
                                   rtol=tol, err_msg=name)


def test_fully_masked_rows_produce_zero_output():
    # a column band masking every off-diagonal row still leaves the diagonal
    # visible; but a window of 0 keys with causal band from row 0 masks rows
    # below the diagonal entirely -> those rows see only themselves
    b, h, s, d = 1, 1, 256, 64
    q, k, v = _rand_bhsd(b, h, s, d, seed=3)
    se = jnp.zeros((b, h, s, 1), jnp.int32)  # LTS=0: whole lower tri masked
    bounds = _canonical_startend(se, s, True)
    out = fp.flashmask_attention(q, k, v, bounds, True, None, None, 128, 128)
    # with causal + full lower-tri mask, only the diagonal survives:
    # softmax over a single element -> out[i] == v[i]
    np.testing.assert_allclose(np.asarray(out), np.asarray(v), atol=2e-5,
                               rtol=2e-5)


def test_functional_wrapper_dense_path_and_shapes():
    # CPU path (no TPU): wrapper must take [b, s, h, d] layout and fall back
    # to the dense-mask path with identical numerics
    b, s, h, d = 2, 64, 2, 32
    rng = np.random.default_rng(4)
    q = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    k = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    se = paddle.to_tensor(np.asarray(_doc_bounds_causal(s, 16, b, h)))
    out = F.flashmask_attention(q, k, v, se, causal=True)
    assert tuple(out.shape) == (b, s, h, d)
    bounds = _canonical_startend(se._data, s, True)
    visible = _flashmask_dense_visible(bounds, s, s, True, None)
    ref = _sdpa_reference(q._data, k._data, v._data, mask=visible)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), atol=2e-5)
    # masking matters: differs from unmasked causal attention
    un = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    assert not np.allclose(out.numpy(), un.numpy(), atol=1e-3)


def test_functional_wrapper_gqa_broadcast():
    b, s, h, kvh, d = 1, 32, 4, 2, 16
    rng = np.random.default_rng(5)
    q = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    k = paddle.to_tensor(
        rng.standard_normal((b, s, kvh, d)).astype(np.float32))
    v = paddle.to_tensor(
        rng.standard_normal((b, s, kvh, d)).astype(np.float32))
    se = paddle.to_tensor(np.asarray(_doc_bounds_causal(s, 8, b, kvh)))
    out = F.flashmask_attention(q, k, v, se, causal=True)
    assert tuple(out.shape) == (b, s, h, d)
    # oracle: expand kv heads per GQA group
    kr = np.repeat(k.numpy(), h // kvh, axis=2)
    vr = np.repeat(v.numpy(), h // kvh, axis=2)
    bounds = _canonical_startend(se._data, s, True)
    bounds = jnp.repeat(bounds, h // kvh, axis=1)
    visible = _flashmask_dense_visible(bounds, s, s, True, None)
    ref = _sdpa_reference(q._data, jnp.asarray(kr), jnp.asarray(vr),
                          mask=visible)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), atol=2e-5)


def test_functional_window_size_and_lse():
    b, s, h, d = 1, 32, 1, 16
    rng = np.random.default_rng(6)
    q = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    k = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    out = F.flashmask_attention(q, k, v, None, causal=True, window_size=4)
    # manual sliding-window causal oracle
    i = np.arange(s)[:, None]
    j = np.arange(s)[None, :]
    visible = (i >= j) & (i <= j + 4)
    ref = _sdpa_reference(q._data, k._data, v._data,
                          mask=jnp.asarray(visible[None, None]))
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), atol=2e-5)
    # lse return
    se = paddle.to_tensor(np.asarray(_doc_bounds_causal(s, 8, b, h)))
    out2, lse = F.flashmask_attention(q, k, v, se, causal=True,
                                      return_softmax_lse=True)
    assert tuple(lse.shape) == (b, h, s)
    assert np.isfinite(lse.numpy()).all()


def test_functional_grad_flows():
    b, s, h, d = 1, 32, 1, 16
    rng = np.random.default_rng(7)
    q = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    q.stop_gradient = False
    k = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    se = paddle.to_tensor(np.asarray(_doc_bounds_causal(s, 8, b, h)))
    out = F.flashmask_attention(q, k, v, se, causal=True)
    out.sum().backward()
    assert q.grad is not None
    assert np.isfinite(q.grad.numpy()).all()
    assert float(np.abs(q.grad.numpy()).sum()) > 0


def test_bad_startend_shapes_rejected():
    b, s, h, d = 1, 32, 1, 16
    rng = np.random.default_rng(8)
    q = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32))
    with pytest.raises(ValueError):
        F.flashmask_attention(q, q, q, paddle.to_tensor(
            np.zeros((b, h, s, 3), np.int32)), causal=True)
    with pytest.raises(ValueError):
        F.flashmask_attention(q, q, q, paddle.to_tensor(
            np.zeros((b, h, 7, 1), np.int32)), causal=True)


def test_llama_packed_documents_flashmask_matches_dense_mask():
    """Model-level flashmask wiring: training a packed-document batch with
    attn_startend_row_indices must equal the dense-mask path (logits AND
    grads), while never materializing the [S, S] mask."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(31)
    S, DOC = 32, 8
    cfg = LlamaConfig.tiny(vocab_size=67, hidden_size=32, layers=2, heads=4,
                           kv_heads=2, seq=S)
    cfg.use_flash_attention = False
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(31)
    ids = paddle.to_tensor(rng.integers(0, 67, (2, S)).astype(np.int32))

    j = np.arange(S)
    doc_end = ((j // DOC + 1) * DOC).astype(np.int32)
    se = paddle.to_tensor(
        np.broadcast_to(doc_end[None, None, :, None], (2, 1, S, 1)).copy())
    out_fm = model(ids, attn_startend_row_indices=se)
    loss_fm = out_fm.sum()
    loss_fm.backward()
    g_fm = np.asarray(
        model.model.layers[0].self_attn.q_proj.weight.grad.numpy()).copy()
    for p in model.parameters():
        p.clear_gradient()

    # dense oracle: causal AND same-document
    same_doc = (j[:, None] // DOC) == (j[None, :] // DOC)
    visible = np.tril(np.ones((S, S), bool)) & same_doc
    dense = paddle.to_tensor(visible[None, None])
    out_dense = model(ids, attention_mask=dense)
    out_dense.sum().backward()
    g_dense = np.asarray(
        model.model.layers[0].self_attn.q_proj.weight.grad.numpy())

    np.testing.assert_allclose(out_fm.numpy(), out_dense.numpy(), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(g_fm, g_dense, atol=2e-4, rtol=2e-4)


def test_llama_chunked_loss_accepts_flashmask_bounds():
    """The memory path (forward_loss + loss_chunk_size) must serve packed
    documents too — same loss as the plain flashmask forward."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(33)
    S, DOC = 32, 8
    cfg = LlamaConfig.tiny(vocab_size=67, hidden_size=32, layers=2, heads=4,
                           kv_heads=2, seq=S)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(33)
    ids = paddle.to_tensor(rng.integers(0, 67, (2, S)).astype(np.int32))
    j = np.arange(S)
    se = paddle.to_tensor(np.broadcast_to(
        (((j // DOC) + 1) * DOC).astype(np.int32)[None, None, :, None],
        (2, 1, S, 1)).copy())
    plain = model.compute_loss(
        model(ids, attn_startend_row_indices=se), ids)
    chunked = model.forward_loss(ids, ids, loss_chunk_size=8,
                                 attn_startend_row_indices=se)
    np.testing.assert_allclose(chunked.numpy(), plain.numpy(), rtol=1e-5)
    # mask + bounds together is rejected, not silently dropped
    with pytest.raises(NotImplementedError, match="cannot be combined"):
        model(ids, attention_mask=paddle.to_tensor(
            np.ones((1, 1, S, S), bool)), attn_startend_row_indices=se)
