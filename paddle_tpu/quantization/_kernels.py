"""Raw-array weight-only int8/int4 kernels shared by the quantization API
(`weight_quantize`/`weight_only_linear`, reference ops.yaml) and the
serving decode path (`paddle_tpu.generation`, quant="weight_only_int*").

One implementation so the two surfaces cannot drift numerically. jax-only
imports — safe for any module to import at load time.
"""
from __future__ import annotations

import jax.numpy as jnp

# the one algo registry both public surfaces (quantization.weight_quantize
# and generation.generate(quant=...)) validate against
ALGO_BITS = {"weight_only_int8": 8, "weight_only_int4": 4,
             "weight_only_fp8": "fp8_e4m3"}

# float8_e4m3fn has NO inf: out-of-range casts produce nan, so every
# quantizer clips to +-finfo.max BEFORE the cast (reference
# nn/quant/format.py:37 does the same clip)
FP8_MAX = {"fp8_e4m3": 448.0, "fp8_e5m2": 57344.0}
FP8_DTYPE = {"fp8_e4m3": jnp.float8_e4m3fn, "fp8_e5m2": jnp.float8_e5m2}


def pack_int4_rows(q8):
    """Pack int4 values held in an int8 array [in, out] into nibbles along
    axis 0 -> int8 [ceil(in/2), out]: even rows in the low nibble, odd rows
    in the high nibble (reference weight_quantize packs the same way). An
    odd row count gets a zero pad row that unpack_int4_rows slices off."""
    n = q8.shape[0]
    if n % 2:
        q8 = jnp.concatenate(
            [q8, jnp.zeros((1,) + q8.shape[1:], q8.dtype)], axis=0)
    even = q8[0::2]
    odd = q8[1::2]
    return ((odd << 4) | (even & 0x0F)).astype(jnp.int8)


def unpack_int4_rows(packed, n_rows):
    """Inverse of pack_int4_rows: int8 [p, out] -> int8 [n_rows, out] with
    sign extension. XLA fuses this into the consumer (the dot reads 4
    bits/weight from HBM)."""
    even = (packed << 4) >> 4        # arithmetic shifts sign-extend
    odd = packed >> 4
    full = jnp.stack([even, odd], axis=1).reshape(
        (2 * packed.shape[0],) + packed.shape[1:])
    return full[:n_rows]


def quantize_weight_arrays(arr, bits: int = 8):
    """Per-output-channel symmetric quantization for a matmul weight used
    as `x @ arr` ([in, out]): returns (q, scale fp32 [out]). The fp32
    upcast makes bf16 weights quantize against the true channel max
    instead of a bf16-rounded one. bits=8 returns int8 [in, out]; bits=4
    returns nibble-packed int8 [ceil(in/2), out] (reference parity with
    weight_quantize's two-nibbles-per-int8 packing — native jnp.int4 jit
    arguments hit a layout-conversion recursion on real TPU in r04
    (`RecursionError: Recursively calling jit`, not re-run since); the
    packed form keeps HBM reads at 4 bits/weight because
    XLA fuses the unpack into the dot operand)."""
    if bits == 8:
        qmax, lo, hi = 127.0, -128, 127
    elif bits == 4:
        qmax, lo, hi = 7.0, -8, 7
    elif bits in FP8_MAX:
        fmax = FP8_MAX[bits]
        a32 = arr.astype(jnp.float32)
        scale = jnp.maximum(jnp.abs(a32).max(axis=0), 1e-8) / fmax
        q = jnp.clip(a32 / scale, -fmax, fmax).astype(FP8_DTYPE[bits])
        return q, scale
    else:
        raise NotImplementedError(f"weight quantization bits={bits}")
    a32 = arr.astype(jnp.float32)
    scale = jnp.maximum(jnp.abs(a32).max(axis=0), 1e-8) / qmax
    q = jnp.clip(jnp.round(a32 / scale), lo, hi).astype(jnp.int8)
    if bits == 4:
        q = pack_int4_rows(q)
    return q, scale


def dequantize_weight_arrays(q, s, n_rows=None):
    """Dequantize the output of quantize_weight_arrays back to fp32.
    The int4-packed form REQUIRES `n_rows` (the original in-dim, used to
    detect packing and slice the pad row); int8/fp8 arrays ignore it."""
    if q.dtype == jnp.int8 and n_rows is not None and q.shape[0] != n_rows:
        q = unpack_int4_rows(q, n_rows)
    return q.astype(jnp.float32) * s


def quantize_tensor_fp8_arrays(arr, fmt: str = "fp8_e4m3"):
    """Dynamic per-tensor float8 quantization: (q float8, scale f32 scalar)
    with q ~= arr / scale, scale = absmax / format-max. The ONE home of the
    clip-before-cast rule for per-tensor scales (e4m3fn overflow is nan)."""
    fmax = FP8_MAX[fmt]
    a32 = arr.astype(jnp.float32)
    scale = jnp.maximum(jnp.abs(a32).max(), 1e-8) / fmax
    q = jnp.clip(a32 / scale, -fmax, fmax).astype(FP8_DTYPE[fmt])
    return q, scale


def quant_matmul_arrays(x, q, s):
    """(x @ int8-or-packed-int4 matrix) with the per-output-channel scale
    applied to the fp32-upcast result — mathematically identical to
    dequantizing the matrix first (sum_i x_i q_ij s_j), but XLA reads the
    narrow integer bytes from HBM and fuses the upcast (and the int4
    nibble unpack) into the dot's operand. A packed-int4 matrix is
    recognized by its halved row count vs x's contraction dim."""
    k = x.shape[-1]
    if q.dtype == jnp.int8 and q.shape[0] != k:
        if q.shape[0] != (k + 1) // 2:
            raise ValueError(
                f"quant_matmul: weight rows {q.shape[0]} match neither the "
                f"contraction dim {k} (int8) nor its nibble-packed half")
        # two half-dots against the nibble halves: no interleaved unpack
        # buffer ever materializes (an r04 rerun showed the
        # stack+reshape unpack costing ~3x on decode), and XLA fuses each
        # shift pair into its dot's operand read
        even = ((q << 4) >> 4).astype(x.dtype)          # rows 0,2,4,...
        odd = (q >> 4)[: k // 2].astype(x.dtype)        # rows 1,3,5,...
        y = x[..., 0::2] @ even + x[..., 1::2] @ odd
        return (y.astype(jnp.float32) * s).astype(x.dtype)
    y = x @ q.astype(x.dtype)
    return (y.astype(jnp.float32) * s).astype(x.dtype)
