"""Read, on the chip at the cell's own size, what ``correct`` sees of the
parts of Nemotron-H's mathematics: for each seed the program's numbers, the
float8 control's, and those of the plain reference with ONE part left out or
wrong (``FAULTS``, put in the part's place by ``faulty``; the reference
module itself holds no switch) in the program's place over the same prompts
and tokens, each judged by the harness's own ``compare.judge`` against the
cell's limits. Not part of a benchmark run.

    python3 bench/tools/nemotron_faults.py --workload nemotron120-serve-batch \\
        --seconds 20 --seeds 1,2,3

One process per seed (a chip belongs to one process); ``longcat_faults``'s
way of running a stand-in (``in_the_programs_place``) and its command line.
"""
from __future__ import annotations

import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.tools import longcat_faults as base   # noqa: E402

FAULTS = ("state_dropped", "no_routed", "no_shared", "bias_in_weight")


@contextlib.contextmanager
def faulty(fault, cfg):
    """The reference while ONE part of its mathematics is left out or wrong:
    the recurrent state not carried from one token to the next (every step
    of a decoding sequence starts from nothing), the held experts' part, the
    shared expert, or the router's bias used in the weights and not only in
    the choice."""
    import jax
    import jax.numpy as jnp
    from bench.reference import nemotron_h_block as ref

    def nothing(lw, h, *rest):
        return jnp.zeros_like(h)

    def forgetful(x, b, c, dt, a, cfg):
        rep = cfg["mamba_num_heads"] // cfg["n_groups"]
        bh, ch = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)
        return (dt[..., None] * x) * jnp.sum(bh * ch, axis=-1)[..., None]

    def biased_route(lw, h, cfg, q=None):
        scores = jax.nn.sigmoid(ref.mm(h, lw["mixer.gate.weight"], q))
        biased = scores + lw["mixer.gate.e_score_correction_bias"].astype(
            jnp.float32)
        top, chosen = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
        return chosen, cfg["routed_scaling_factor"] * top \
            / jnp.sum(top, axis=-1, keepdims=True)

    name, wrong = {"state_dropped": ("recurrence", forgetful),
                   "no_routed": ("routed_part", nothing),
                   "no_shared": ("shared_part", nothing),
                   "bias_in_weight": ("route", biased_route)}[fault]
    right = getattr(ref, name)
    setattr(ref, name, wrong)
    try:
        yield cfg
    finally:
        setattr(ref, name, right)


if __name__ == "__main__":
    # ``longcat_faults``' command line and its run of one seed, over this
    # file's parts and with this file as the process a seed starts
    base.FAULTS, base.faulty = FAULTS, faulty
    base.__file__ = os.path.abspath(__file__)
    base.main()
