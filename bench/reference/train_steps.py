"""Plain reference of the first training steps: loss, gradients and AdamW.

float32 ``jax.numpy`` at the highest matmul precision over a block file's
``embed`` / ``block`` / ``head``: one sequence at a time, layer by layer in
the order of the adapter's walk, the backward through ``jax.vjp`` of the same
functions. It imports nothing of the system under test and makes its own
weights from the seed.

It takes ONE gradient a layer from ONE visit, and the gradient of the leaves
outside the layers through ``embed`` and ``head`` alone. So it follows a walk
of ``"block"`` stops that visits every layer once and refuses any other
(``one_visit_order``): a layer's gradient summed over its visits, and a stop
between layers, come with the model that needs them. A block is handed no
leaves outside it (``top`` is ``None`` here), so one that reads them fails at
once and loses no term in silence.

What it holds to is what the training configurations state: parameters are
STORED in bfloat16 (the update is computed in float32 from the stored value
and rounded once on store; there is no float32 master copy), AdamW with
float32 moments, decoupled weight decay on every leaf, the gradient clipped to
a global norm before the moments see it, bias correction by the step count.

``q`` swaps the matmul operands' precision (the control); ``fault`` plants one
of the faults the tests and PERF.md read: ``half_batch`` leaves the second half
of the rows out and takes the mean over the rest.
"""
from __future__ import annotations

import importlib
from functools import partial

import jax
import jax.numpy as jnp

from ..lib import weights as W


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@partial(jax.jit, static_argnums=(0, 1, 4))
def _fwd_layer(ref, cfg_items, lw, xs, q):
    cfg = dict(cfg_items)
    lw = _f32(lw)
    return jax.lax.map(lambda x: ref.block(None, lw, x, cfg, q), xs)


@partial(jax.jit, static_argnums=(0, 1, 5))
def _bwd_layer(ref, cfg_items, lw, xs, dys, q):
    """Gradients of one block over every row: (d weights, d inputs)."""
    cfg = dict(cfg_items)
    lw = _f32(lw)

    def body(acc, xy):
        x, dy = xy
        _, vjp = jax.vjp(lambda w, a: ref.block(None, w, a, cfg, q), lw, x)
        dw, dx = vjp(dy)
        return jax.tree_util.tree_map(jnp.add, acc, dw), dx

    zero = jax.tree_util.tree_map(jnp.zeros_like, lw)
    return jax.lax.scan(body, zero, (xs, dys))


@partial(jax.jit, static_argnums=(0, 1))
def _embed(ref, cfg_items, top, ids):
    cfg = dict(cfg_items)
    top = _f32(top)
    return jax.lax.map(lambda r: ref.embed(top, r, cfg), ids)


@partial(jax.jit, static_argnums=(0, 1))
def _embed_grad(ref, cfg_items, top, ids, dxs):
    cfg = dict(cfg_items)
    _, vjp = jax.vjp(
        lambda t: jax.lax.map(lambda r: ref.embed(t, r, cfg), ids), _f32(top))
    return vjp(dxs)[0]


@partial(jax.jit, static_argnums=(0, 1, 5))
def _head_loss(ref, cfg_items, top, xs, labels, q):
    """Mean shifted cross entropy over all rows, with d top and d inputs."""
    cfg = dict(cfg_items)
    top = _f32(top)
    n = xs.shape[0] * (xs.shape[1] - 1)

    def row_loss(t, x, y):
        logp = jax.nn.log_softmax(ref.head(t, x, cfg, q)[:-1], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, y[1:, None], axis=-1)) / n

    def body(acc, xy):
        x, y = xy
        loss, (dt, dx) = jax.value_and_grad(row_loss, (0, 1))(top, x, y)
        return (acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], dt)), dx

    zero = (jnp.float32(0), jax.tree_util.tree_map(jnp.zeros_like, top))
    (loss, dtop), dxs = jax.lax.scan(body, zero, (xs, labels))
    return loss, dtop, dxs


@jax.jit
def _adamw(p, g, m, v, scale, step, lr, wd, b1, b2, eps):
    def one(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step)) + eps)
        new = p.astype(jnp.float32) * (1 - lr * wd) - lr * upd
        return new.astype(p.dtype), m, v
    out = {k: one(p[k], g[k], m[k], v[k]) for k in p}
    return ({k: o[0] for k, o in out.items()}, {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def one_visit_order(arch, cfg):
    """The layers in the order the walk visits them, where the walk is one
    this reference can follow; ValueError, with the reason, where it is not."""
    stops = list(arch.walk(cfg))
    other = sorted({name for name, i in stops if name != "block" or i is None})
    if other:
        raise ValueError(
            f"the training reference follows a walk of blocks only, and this "
            f"one also stops at {other}: the backward through a stop between "
            f"layers is not written")
    order = [i for _, i in stops]
    if sorted(order) != list(range(arch.n_layers(cfg))):
        raise ValueError(
            f"the training reference takes one gradient a layer from one "
            f"visit, and this walk visits the {arch.n_layers(cfg)} layers in "
            f"{len(order)} stops ({order[:8]}...): summing a layer's gradient "
            f"over its visits is not written")
    return order


def follow(arch, cfg, seed, batches, hyper, q=None, fault=None,
           dtype=jnp.bfloat16):
    """Follow ``len(batches)`` steps from the seed's weights.

    batches: int32 arrays [rows, seq], labels equal to inputs. hyper: ``lr``,
    ``weight_decay``, ``clip_norm``, ``beta1``, ``beta2``, ``epsilon``.
    Returns ``{"losses": [...], "grad_norm": {leaf: norm of the first clipped
    gradient}, "change_norm": {leaf: norm of the parameter's change after the
    last step}}``, leaves by their full names."""
    ref = importlib.import_module(arch.REFERENCE)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    n_layers = arch.n_layers(cfg)
    order = one_visit_order(arch, cfg)
    fused = W.fused_of(arch)
    top = dict(W.top_weights(arch, cfg, seed, dtype))
    layers = [dict(W.layer_weights(arch, cfg, seed, i, dtype))
              for i in range(n_layers)]
    groups = [top] + layers          # group 0 is the leaves outside blocks
    zeros = lambda t: {k: jnp.zeros(v.shape, jnp.float32) for k, v in t.items()}
    moms = [zeros(g) for g in groups]
    vels = [zeros(g) for g in groups]
    prefix = [""] + [arch.layer_prefix(i) for i in range(n_layers)]
    losses, grad_norm = [], {}
    for step, ids in enumerate(batches, start=1):
        ids = jnp.asarray(ids)
        if fault == "half_batch":
            ids = ids[: ids.shape[0] // 2]
        xs = [_embed(ref, items, top, ids)]
        for i in order:
            xs.append(_fwd_layer(ref, items, layers[i], xs[-1], q))
        loss, dtop, dx = _head_loss(ref, items, top, xs.pop(), ids, q)
        losses.append(float(loss))
        grads = [None] * (n_layers + 1)
        for i in reversed(order):
            grads[i + 1], dx = _bwd_layer(ref, items, layers[i], xs.pop(), dx, q)
        demb = _embed_grad(ref, items, top, ids, dx)
        grads[0] = jax.tree_util.tree_map(jnp.add, dtop, demb)
        del dx, dtop, demb, xs
        sq = [W.part_squares(fused, g) for g in grads]
        total = jnp.sqrt(sum(sum(s.values()) for s in sq))
        clip = jnp.float32(hyper["clip_norm"])
        scale = clip / jnp.maximum(total, clip)
        if step == 1:
            for pre, s in zip(prefix, sq):
                for k, v in s.items():
                    grad_norm[pre + k] = float(jnp.sqrt(v) * scale)
        for gi in range(n_layers + 1):
            groups[gi], moms[gi], vels[gi] = _adamw(
                groups[gi], grads[gi], moms[gi], vels[gi], scale,
                jnp.float32(step), jnp.float32(hyper["lr"]),
                jnp.float32(hyper["weight_decay"]), jnp.float32(hyper["beta1"]),
                jnp.float32(hyper["beta2"]), jnp.float32(hyper["epsilon"]))
            grads[gi] = None
        top, layers = groups[0], groups[1:]
    change = {}
    first = [W.top_weights(arch, cfg, seed, dtype)] + [
        W.layer_weights(arch, cfg, seed, i, dtype) for i in range(n_layers)]
    for pre, now, was in zip(prefix, groups, first):
        for k, v in W.part_squares(fused, W.difference(now, was)).items():
            change[pre + k] = float(jnp.sqrt(v))
    return {"losses": losses, "grad_norm": grad_norm, "change_norm": change}
