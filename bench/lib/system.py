"""The system under test, built for one configuration.

The only file besides the architecture adapters that imports ``paddle_tpu``
model code. The model is built with every parameter pointing at ONE shared
zero array per shape (a user-level global initializer), so construction
allocates and computes next to nothing; the zero arrays are let go before the
seeded weights are made in bulk on the device (a zero array a shape beside
the whole model's weights was 2.9 GB in the largest cell), and then each
parameter is bound to its weights.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import weights as W


def build_model(arch, cfg, seed, dtype="bfloat16"):
    import paddle_tpu as paddle
    from paddle_tpu.nn import initializer as I

    class _Shared(I.Initializer):
        """One zero array per (shape, dtype), shared by every parameter."""

        def __init__(self):
            self.made = {}

        def __call__(self, shape, dtype):
            key = (tuple(shape), str(dtype))
            if key not in self.made:
                self.made[key] = jnp.zeros(shape, dtype)
            return self.made[key]

    before = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    shared = _Shared()
    I.set_global_initializer(shared, shared)
    try:
        model = arch.build(cfg)
    finally:
        I.set_global_initializer(None, None)
        paddle.set_default_dtype(before)
    params = dict(model.named_parameters())
    shapes = {name: tuple(p.shape) for name, p in params.items()}
    shared.made.clear()
    for p in params.values():
        p._data = None                 # nothing reads the model until bound
    made = W.all_weights(arch, cfg, seed, jnp.dtype(dtype))
    if set(params) != set(made):
        raise ValueError("the adapter's leaves are not the model's: "
                         f"{sorted(set(params) ^ set(made))[:8]}")
    for name, p in params.items():
        if shapes[name] != tuple(made[name].shape):
            raise ValueError(f"{name}: model {shapes[name]}, adapter "
                             f"{tuple(made[name].shape)}")
        p._data = made[name]
    return model
