"""Operations and bytes the algorithm needs, from shapes alone.

The numerators of every ``*_mfu`` and ``*_roofline`` metric. They count what
the mathematics requires whatever implements it: recomputation, padding rows
and gathers the implementation adds are not work.

The adapter's ``block_matmul_params``, ``attention_flops`` and
``kv_bytes_per_token`` count what one token passes through and keeps on the
WHOLE WALK (``bench/archs/gpt2.py``): a layer that the walk visits ``T`` times
is in them ``T`` times. So a revisited layer's matrix products count once a
visit here, its weights are read once a visit in ``serve_step_bytes`` (the
visits depend on each other, and the weights do not stay on the chip between
them), and every visit's keys and values are cache of their own.
"""
from __future__ import annotations


def train_flops_per_token(arch, cfg, seq):
    """Forward and backward of one token in a sequence of ``seq``: every
    matrix product of the walk's visits and of the head twice per
    multiply-add, causal attention over the mean context seq/2, times three
    for the backward's two products per forward one. Recomputation is not
    counted."""
    forward = 2.0 * (arch.block_matmul_params(cfg) + arch.head_params(cfg)) \
        + arch.attention_flops(cfg, seq / 2.0)
    return 3.0 * forward


def flash_flops(batch, heads, seq, head_dim, causal=True):
    """Forward, dq and dkv of one flash-attention call: the forward has two
    products (QK^T, PV) of 2*s*s*d each per head, the backward five (S, dP,
    dV, dQ, dK); a causal mask halves all."""
    per_product = 2.0 * seq * seq * head_dim * batch * heads
    total = 7.0 * per_product
    return total / 2.0 if causal else total


def serve_step_flops(arch, cfg, tokens, sampled, contexts):
    """One engine step: ``tokens`` rows through every visit of the walk,
    ``sampled`` rows through the head, and each row's attention over its live
    context at every visit (``contexts``: one length per row)."""
    return (2.0 * arch.block_matmul_params(cfg) * tokens
            + 2.0 * arch.head_params(cfg) * sampled
            + sum(arch.attention_flops(cfg, c) for c in contexts))


def serve_step_bytes(arch, cfg, tokens, live_context, itemsize=2):
    """One engine step, whatever implements it: every weight once a visit,
    the live K/V of each scheduled sequence once (``live_context``: summed
    context tokens over the scheduled sequences), the new K/V written once;
    K/V as the adapter counts them, an entry a visit."""
    weights = (arch.block_matmul_params(cfg) + arch.head_params(cfg)) * itemsize
    kv = arch.kv_bytes_per_token(cfg, itemsize)
    return weights + kv * live_context + kv * tokens
