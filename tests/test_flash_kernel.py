"""Pallas flash-attention kernel vs the XLA reference (interpret mode on CPU).

Mirrors the reference's OpTest pattern (test/legacy_test/op_test.py): forward
against an oracle, analytic grads against the oracle's vjp.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import flash_pallas as fp


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setattr(fp, "_INTERPRET", True)
    yield


def _rand_qkv(b, h, s, d, dtype, kv_s=None):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, h, kv_s or s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, kv_s or s, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _rand_qkv(1, 2, 256, 64, jnp.float32)
    out = fp.flash_attention(q, k, v, causal, None, 128, 128)
    ref = fp._reference_bhsd(q, k, v, causal, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_reference(causal):
    q, k, v = _rand_qkv(1, 2, 256, 64, jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(fp.flash_attention(q, k, v, causal, None, 128, 128)
                       * jnp.cos(jnp.arange(64, dtype=jnp.float32)))

    def f_ref(q, k, v):
        return jnp.sum(fp._reference_bhsd(q, k, v, causal, None)
                       * jnp.cos(jnp.arange(64, dtype=jnp.float32)))

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4, err_msg=f"d{name}")


def test_bfloat16_close():
    q, k, v = _rand_qkv(1, 1, 128, 64, jnp.bfloat16)
    out = fp.flash_attention(q, k, v, True, None, 128, 128)
    ref = fp._reference_bhsd(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), True, None)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_multi_block_kv_accumulation():
    # kv longer than q: exercises cross-block online-softmax accumulation.
    # The tiles are pinned: sized from the shapes, 384 keys are ONE block
    q, k, v = _rand_qkv(1, 1, 128, 64, jnp.float32, kv_s=384)
    assert fp.choose_tiles("fwd", 128, 384, 64, 4).block_k == 384
    out = fp.flash_attention(q, k, v, False, None, 128, 128)
    ref = fp._reference_bhsd(q, k, v, False, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.slow
def test_causal_kv_longer_than_q():
    """Bottom-right-aligned causal mask (kv-cache decode): query i attends
    keys up to i + (sk - sq), matching the XLA reference convention."""
    q, k, v = _rand_qkv(1, 2, 128, 64, jnp.float32, kv_s=384)
    out = fp.flash_attention(q, k, v, True, None, 128, 128)
    ref = fp._reference_bhsd(q, k, v, True, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)

    def f_kernel(q, k, v):
        return jnp.sum(fp.flash_attention(q, k, v, True, None, 128, 128) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(fp._reference_bhsd(q, k, v, True, None) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4, err_msg=f"d{name}")


# -- unequal and large tiles ---------------------------------------------------

def _out_and_grads(attention, q, k, v):
    w = jnp.cos(jnp.arange(q.shape[-1], dtype=jnp.float32))
    out, vjp = jax.vjp(attention, q, k, v)
    return (out, *vjp(jnp.broadcast_to(w, out.shape).astype(out.dtype)))


TILES = [(128, 256), (256, 128), (256, 512), (512, 512)]
# (sq, sk, causal): self-attention both ways, and a bottom-right-aligned
# causal mask whose diagonal crosses tile edges (offset 512)
CASES = [(1024, 1024, True), (1024, 1024, False), (512, 1024, True)]


@pytest.mark.parametrize("sq,sk,causal", CASES,
                         ids=["causal", "full", "causal_kv_longer"])
@pytest.mark.parametrize("block_q,block_k", TILES,
                         ids=[f"{a}x{b}" for a, b in TILES])
def test_unequal_and_large_tiles_match_reference(block_q, block_k, sq, sk,
                                                 causal):
    """out, dq, dk, dv against the oracle on tiles that are not square and
    not 128: the diagonal falls inside tiles and at their edges."""
    q, k, v = _rand_qkv(1, 1, sq, 64, jnp.float32, kv_s=sk)
    got = _out_and_grads(
        lambda q, k, v: fp.flash_attention(q, k, v, causal, None, block_q,
                                           block_k), q, k, v)
    want = _out_and_grads(
        lambda q, k, v: fp._reference_bhsd(q, k, v, causal, None), q, k, v)
    for a, b, name, tol in zip(got, want, ("out", "dq", "dk", "dv"),
                               (2e-5, 2e-4, 2e-4, 2e-4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                                   rtol=tol, err_msg=name)


def test_backward_kernels_take_their_own_tiles():
    """dq and dkv are sized apart: a call may run them on different
    (block_q, block_k) and still return the oracle's gradients."""
    q, k, v = _rand_qkv(1, 1, 512, 64, jnp.float32)
    g = jnp.ones_like(q)
    out, lse = fp._flash_forward(q, k, v, True, None, 256, 512)
    got = fp._flash_backward(q, k, v, out, lse, g, True, None, (512, 128),
                             (128, 256))
    _, vjp = jax.vjp(lambda q, k, v: fp._reference_bhsd(q, k, v, True, None),
                     q, k, v)
    for a, b, name in zip(got, vjp(g), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4, err_msg=name)


def test_default_tiles_match_reference():
    """Nothing pinned, nothing tuned: the call sizes its own tiles (one
    block a side at 512 tokens) and agrees with the oracle."""
    q, k, v = _rand_qkv(1, 2, 512, 64, jnp.float32)
    got = _out_and_grads(lambda q, k, v: fp.flash_attention(q, k, v, True),
                         q, k, v)
    want = _out_and_grads(
        lambda q, k, v: fp._reference_bhsd(q, k, v, True, None), q, k, v)
    for a, b, name, tol in zip(got, want, ("out", "dq", "dk", "dv"),
                               (2e-5, 2e-4, 2e-4, 2e-4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                                   rtol=tol, err_msg=name)
