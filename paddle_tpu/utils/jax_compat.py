"""The one spelling of the JAX entry points whose call sites are linted.

``shard_map`` goes through here so every manual region gets its specs
validated against the mesh first (shardcheck's runtime twin); the lint
rules in ``analysis/rules.py`` keep call sites pointed at this module.
``axis_size`` and ``tpu_compiler_params`` are the installed JAX's own
names, re-exported so kernels and collectives import from one place.
"""
from __future__ import annotations

import jax

axis_size = jax.lax.axis_size


def shard_map(f, mesh=None, in_specs=None, out_specs=None, **kw):
    """``jax.shard_map`` with the mesh axes of the specs checked HERE,
    with the SHD rule id in the message, instead of failing deep inside
    spec resolution. Deferred import: distributed.mesh must not load
    while this module initializes."""
    if mesh is not None:
        from ..distributed.mesh import validate_specs
        validate_specs(mesh, in_specs, out_specs)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def tpu_compiler_params(**kw):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kw)


__all__ = ["shard_map", "axis_size", "tpu_compiler_params"]
