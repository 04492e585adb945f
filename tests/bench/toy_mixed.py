"""A toy MIXED architecture for the tests, adapter and plain reference in one
module: layer 0 holds one matrix, the layers after it a gate, an up and a
down projection, and each kind has a step of its own. It is no model's."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import mm

REFERENCE = __name__
CONFIG = {"architecture": "toy-mixed", "hidden": 24, "layers": 3,
          "vocab_size": 48}


def n_layers(cfg):
    return cfg["layers"]


def layer_prefix(i):
    return f"layers.{i}."


def top_specs(cfg):
    h, v = cfg["hidden"], cfg["vocab_size"]
    return [("embed.weight", (v, h), ("normal", 1.0)),
            ("head.weight", (h, v), ("normal", 0.2))]


def layer_specs(cfg, i):
    h = cfg["hidden"]
    if i == 0:
        return [("dense.weight", (h, h), ("normal", 0.1))]
    return [("gate.weight", (h, 2 * h), ("normal", 0.2)),
            ("up.weight", (h, 2 * h), ("normal", 0.2)),
            ("down.weight", (2 * h, h), ("normal", 0.2))]


def walk(cfg):
    return [("dense", 0)] + [("gated", i) for i in range(1, cfg["layers"])]


# -- the plain reference ------------------------------------------------------
def embed(top, ids, cfg):
    return top["embed.weight"][ids]


def dense(top, lw, x, cfg, q=None):
    upto = jnp.cumsum(x, axis=0) / jnp.arange(1, x.shape[0] + 1)[:, None]
    return x + mm(upto, lw["dense.weight"], q)


def gated(top, lw, x, cfg, q=None):
    m = jax.nn.silu(mm(x, lw["gate.weight"], q)) * mm(x, lw["up.weight"], q)
    return x + mm(m, lw["down.weight"], q)


def head(top, x, cfg, q=None):
    return mm(x, top["head.weight"], q)
