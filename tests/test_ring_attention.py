"""Ring attention (context parallel over sep axis) vs full-attention oracle."""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import ProcessMesh
from paddle_tpu.parallel.ring_attention import ring_attention


def _mesh_sep(n=4):
    return ProcessMesh(shape=[n], dim_names=["sep"],
                       process_ids=list(range(n)))


def _oracle(q, k, v, causal):
    d = q.shape[-1]
    qh = q.transpose(0, 2, 1, 3).astype(np.float32)
    kh = k.transpose(0, 2, 1, 3).astype(np.float32)
    vh = v.transpose(0, 2, 1, 3).astype(np.float32)
    scores = qh @ kh.transpose(0, 1, 3, 2) / np.sqrt(d)
    if causal:
        s = scores.shape[-1]
        mask = np.tril(np.ones((s, s), bool))
        scores = np.where(mask, scores, -1e30)
    e = np.exp(scores - scores.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    return (p @ vh).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full(causal):
    import jax
    b, s, h, d = 2, 32, 2, 8
    rng = np.random.default_rng(0)
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, h, d)).astype(np.float32)
    v = rng.standard_normal((b, s, h, d)).astype(np.float32)
    mesh = _mesh_sep(4)
    out = jax.jit(lambda a, bb, c: ring_attention(a, bb, c, mesh, "sep",
                                                  causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), _oracle(q, k, v, causal),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_ring_gradients_match():
    import jax
    import jax.numpy as jnp
    b, s, h, d = 1, 16, 2, 4
    rng = np.random.default_rng(1)
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, h, d)).astype(np.float32)
    v = rng.standard_normal((b, s, h, d)).astype(np.float32)
    mesh = _mesh_sep(4)

    def ring_loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, "sep", causal=True) ** 2)

    def full_loss(q, k, v):
        import math
        qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
        kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
        vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
        scores = qh @ jnp.swapaxes(kh, -1, -2) / math.sqrt(d)
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -1e30)
        p = jax.nn.softmax(scores, -1)
        out = jnp.swapaxes(p @ vh, 1, 2)
        return jnp.sum(out ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=3e-4, atol=3e-5)


def test_llama_context_parallel_matches_serial():
    """Llama trained with sep=4 sequence sharding == serial run."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel import SpmdTrainer, make_hybrid_mesh

    cfg = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4,
                           kv_heads=4, seq=32)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, 64, (2, 32)).astype(np.int32))

    def loss_fn(m, x, y):
        return m.compute_loss(m(x), y)

    paddle.seed(21)
    m_serial = LlamaForCausalLM(cfg)
    t_s = SpmdTrainer(m_serial, opt.SGD(learning_rate=0.05,
                                        parameters=m_serial.parameters()),
                      loss_fn, mesh=None)
    serial = [float(t_s.train_step(ids, ids).numpy()) for _ in range(3)]

    paddle.seed(21)
    m_cp = LlamaForCausalLM(cfg)
    mesh = make_hybrid_mesh(dp=2, sep=4)
    t_p = SpmdTrainer(m_cp, opt.SGD(learning_rate=0.05,
                                    parameters=m_cp.parameters()),
                      loss_fn, mesh=mesh, seq_axis="sep")
    par = [float(t_p.train_step(ids, ids).numpy()) for _ in range(3)]
    np.testing.assert_allclose(par, serial, rtol=2e-3)
