"""Global RNG state for eager execution.

Reference parity: paddle.seed / generator state (python/paddle/framework/random.py).
TPU-native design: a single threaded JAX PRNG key; eager random ops fold in a
monotonically increasing counter so each eager call gets a fresh, reproducible key.
Functional/jitted paths (jit.to_static, nn functional_call) should pass explicit
keys instead of consuming global state.
"""
from __future__ import annotations

import threading

import jax


class _GeneratorState(threading.local):
    # `key` is created lazily on first use: materializing a PRNGKey initializes
    # the JAX backend, which must never happen at `import paddle_tpu` time:
    # a process that only imports the package must not take the chip.
    def __init__(self):
        self.seed_value = 0
        self.key = None
        self.counter = 0


_state = _GeneratorState()


def _base_key():
    if _state.key is None:
        _state.key = jax.random.PRNGKey(_state.seed_value)
    return _state.key


def seed(value: int):
    """Seed the global generator (parity: paddle.seed). Lazy: no backend init."""
    _state.seed_value = int(value)
    _state.key = None
    _state.counter = 0
    return _state


def get_rng_state():
    return (_state.seed_value, _state.counter)


def set_rng_state(state):
    seed_value, counter = state
    seed(seed_value)
    _state.counter = int(counter)


class _TracedKey(threading.local):
    def __init__(self):
        self.stack = []


_traced = _TracedKey()


class key_context:
    """Derive keys from an explicit (possibly traced) base key.

    Used by jit.to_static so random ops inside a compiled program take their
    randomness from a per-call input key instead of baking the global state into
    the trace.
    """

    def __init__(self, base_key):
        self.base_key = base_key
        self.counter = 0

    def __enter__(self):
        _traced.stack.append(self)
        return self

    def __exit__(self, *exc):
        _traced.stack.pop()
        return False


def next_key():
    """Fresh PRNG key for one eager random op."""
    if _traced.stack:
        ctx = _traced.stack[-1]
        ctx.counter += 1
        return jax.random.fold_in(ctx.base_key, ctx.counter)
    _state.counter += 1
    return jax.random.fold_in(_base_key(), _state.counter)


def split_key(n: int):
    return jax.random.split(next_key(), n)
