"""What a script that runs on the chip does before anything else.

Two rules from the measurement guide, each written once: a run that is
meant for the accelerator fails when JAX found none (JAX itself carries on
on the CPU after a failed libtpu start), and JAX's persistent compile cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says or else at ONE fixed path
inside the checkout — the path is part of the cache key, so a directory
that moves never hits — and keys a program by its metadata too, so that a
profile names this commit's scopes and lines. Neither runs on ``import paddle_tpu``, and the
helper never turns the cache on for a CPU backend: CPU cache entries
abort on reload in the sandbox, so the test suite stays cold.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: where the compile cache goes when the environment names no place
DEFAULT_COMPILE_CACHE = os.path.join(_REPO_ROOT, ".jax_cache")


def device_summary() -> dict:
    """The default backend as JAX reports it."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu() -> dict:
    """``device_summary()`` of a TPU backend, or RuntimeError naming what
    JAX found instead. Backend start-up errors propagate."""
    found = device_summary()
    if found["platform"] != "tpu":
        raise RuntimeError(
            f"this run needs a TPU; JAX's default backend is "
            f"{found['platform']!r} ({found['count']} x {found['kind']})")
    return found


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at its one place and
    return it (None: no cache). With ``JAX_COMPILATION_CACHE_DIR`` set
    JAX has already read it: the place is not changed. Otherwise
    ``<repo>/.jax_cache`` — unless the backend is the CPU, which stays
    cold. Call it before the first compilation.

    The cache key takes the programs' metadata in (scope paths, source
    lines). JAX leaves it out by default, and a program read back from the
    cache then carries the names of whatever commit compiled it first: a
    profile of this commit would show the last one's scopes and lines, or
    none (seen on the chip in PR 26: the serving step's new scopes were
    missing from every operation's ``tf_op`` until this was set)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env and jax.devices()[0].platform == "cpu":
        return None
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE
