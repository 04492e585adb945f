"""A toy LOOPED architecture for the tests, adapter and plain reference in
one module: ``layers`` layers visited ``passes`` times over, the norm of
``top`` at the end of every pass. It is no model's; it has what the harness
reads of an adapter off the chip and nothing else."""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.common import mm

REFERENCE = __name__
CONFIG = {"architecture": "toy-looped", "hidden": 32, "layers": 2, "passes": 3,
          "vocab_size": 64}


def n_layers(cfg):
    return cfg["layers"]


def layer_prefix(i):
    return f"layers.{i}."


def top_specs(cfg):
    h, v = cfg["hidden"], cfg["vocab_size"]
    return [("embed.weight", (v, h), ("normal", 1.0)),
            ("norm.weight", (h,), ("near_one", 0.1)),
            ("head.weight", (h, v), ("normal", 0.2))]


def layer_specs(cfg, i):
    h = cfg["hidden"]
    return [("mix.weight", (h, h), ("normal", 0.1)),
            ("up.weight", (h, 2 * h), ("normal", 0.2)),
            ("down.weight", (2 * h, h), ("normal", 0.2))]


def walk(cfg):
    one_pass = [("block", i) for i in range(cfg["layers"])]
    return (one_pass + [("pass_end", None)]) * cfg["passes"]


# -- the plain reference ------------------------------------------------------
def embed(top, ids, cfg):
    return top["embed.weight"][ids]


def block(top, lw, x, cfg, q=None):
    """Each row mixed with the mean of the rows up to it, then two products."""
    upto = jnp.cumsum(x, axis=0) / jnp.arange(1, x.shape[0] + 1)[:, None]
    x = x + mm(upto, lw["mix.weight"], q)
    return x + mm(jnp.tanh(mm(x, lw["up.weight"], q)), lw["down.weight"], q)


def pass_end(top, lw, x, cfg, q=None):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) \
        * top["norm.weight"]


def head(top, x, cfg, q=None):
    return mm(x, top["head.weight"], q)
