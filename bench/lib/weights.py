"""Seeded weights, made on the device in the type they are served in.

An architecture lists its leaves as ``(name, shape, init)``: the leaves outside
the layers once (``top_specs(cfg)``) and each layer's (``layer_specs(cfg, i)``).
One jitted program per DISTINCT list makes them from the seed; the layer index
is a traced argument, so layers whose lists are equal share one program,
however many they are, and a model whose layers are of two kinds compiles two.
The plain references call the same programs layer by layer, so they compute on
the very values the system was given and hold one layer at a time.

``init`` is ``("normal", std)`` or ``("near_one", std)`` (1 + std * normal,
for norm scales, so that the scale path is exercised).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


_TOP = 1 << 20     # the index the leaves outside the blocks are keyed by


def seed_key(seed: int):
    """A key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@partial(jax.jit, static_argnums=(0, 3))
def _make(specs, key, index, dtype):
    key = jax.random.fold_in(key, index)
    out = {}
    for i, (name, shape, (how, std)) in enumerate(specs):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if how == "normal":
            v = z * std
        elif how == "near_one":
            v = 1.0 + z * std
        else:
            raise ValueError(f"unknown init {how!r} for {name}")
        out[name] = v.astype(dtype)
    return out


def _hashable(specs):
    return tuple((n, tuple(int(d) for d in s), (h, float(v)))
                 for n, s, (h, v) in specs)


def top_weights(arch, cfg, seed, dtype=jnp.bfloat16):
    """The leaves outside the blocks, by their full names."""
    return _make(_hashable(arch.top_specs(cfg)), seed_key(seed), _TOP,
                 jnp.dtype(dtype))


def layer_weights(arch, cfg, seed, layer, dtype=jnp.bfloat16):
    """Layer ``layer``'s leaves, by their names inside the layer."""
    return _make(_hashable(arch.layer_specs(cfg, layer)), seed_key(seed),
                 jnp.int32(layer), jnp.dtype(dtype))


def all_weights(arch, cfg, seed, dtype=jnp.bfloat16):
    """Every leaf under the name the system's state dict gives it."""
    out = dict(top_weights(arch, cfg, seed, dtype))
    for i in range(arch.n_layers(cfg)):
        for k, v in layer_weights(arch, cfg, seed, i, dtype).items():
            out[arch.layer_prefix(i) + k] = v
    return out


@partial(jax.jit, static_argnums=(0,))
def part_squares(fused, tree):
    """Sum of squares of every leaf, a fused leaf (``fused``: (name suffix,
    parts) pairs, split along the last axis) by its parts, ``name#i``: a
    fused qkv projection is three leaves to the comparison, since the key's
    bias has a gradient of nought where the query's and the value's have one."""
    out = {}
    for name, a in tree.items():
        a = a.astype(jnp.float32)
        n = next((k for sfx, k in fused if name.endswith(sfx)), 1)
        if n == 1:
            out[name] = jnp.sum(jnp.square(a))
        else:
            for i, piece in enumerate(jnp.split(a, n, axis=-1)):
                out[f"{name}#{i}"] = jnp.sum(jnp.square(piece))
    return out


@jax.jit
def difference(now, was):
    """``now - was`` leaf by leaf in float32 (``was`` names the leaves)."""
    return {k: now[k].astype(jnp.float32) - was[k].astype(jnp.float32)
            for k in was}


def fused_of(arch):
    return tuple(sorted(getattr(arch, "FUSED", {}).items()))
