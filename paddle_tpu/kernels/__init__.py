"""Pallas TPU kernels — the fused-op hot list.

Reference parity: paddle/phi/kernels/fusion/gpu/ (fused_rope, fused
bias+dropout+residual+layernorm, flash attention, fused MoE dispatch). Here each
is a Pallas kernel (MXU/VMEM-aware) with an XLA reference fallback; kernels are
validated against the pure-jnp oracle in tests.
"""
import jax


def default_platform() -> str:
    """Platform of the default backend ("tpu", "cpu", ...). The one probe
    every kernel gate shares. A backend that fails to initialise raises
    here: a chip that is held by another process or a failed libtpu start
    must stop the program, not select the XLA path or the interpreter."""
    return jax.devices()[0].platform


def on_tpu() -> bool:
    return default_platform() == "tpu"


from . import autotune  # noqa: E402,F401  (defines FLAGS_use_autotune)
