"""Read, on the chip at the cell's own size, what ``correct`` sees of the
parts of LongCat-Flash's mathematics: for each seed the program's numbers,
the float8 control's, and those of the plain reference with ONE part left out
or wrong (``FAULTS``, put in the part's place by ``faulty``; the reference
module itself holds no switch) in the program's place over the same prompts
and tokens, each judged by the harness's own ``compare.judge`` against the
cell's limits. Not part of a benchmark run.

    python3 bench/tools/longcat_faults.py --workload longcat560-serve-batch \\
        --seconds 20 --seeds 1,2,3

One process per seed (a chip belongs to one process). The stand-ins are read
over the first ``--sequences`` of the run's sampled requests (the longest is
the first), since each is a whole reference pass. A faulty reference need not
decode: at each position of the served sequences the token it puts first
is read against the float32 reference, as the control's is.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


FAULTS = ("no_routed", "no_zero", "no_kv_scale", "bias_in_weight")


@contextlib.contextmanager
def faulty(fault, cfg):
    """The configuration to hand the reference while ONE part of its
    mathematics is left out or wrong: the held experts' part, the zero
    experts' part, the latent's rescaling ``s_kv`` (the published flag, off),
    or the router's bias used in the weight and not only in the choice."""
    import jax
    import jax.numpy as jnp
    from bench.reference import longcat_flash_block as ref

    def nothing(lw, h, *rest):
        return jnp.zeros_like(h)

    def biased_route(lw, h, cfg, q=None):
        scores = jax.nn.softmax(
            ref.mm(h, lw["mlp.router.classifier.weight"], q), axis=-1)
        biased = scores + lw["mlp.router.e_score_correction_bias"].astype(
            jnp.float32)
        top, chosen = jax.lax.top_k(biased, cfg["moe_topk"])
        return chosen, cfg["routed_scaling_factor"] * top

    name, wrong = {"no_routed": ("held_part", nothing),
                   "no_zero": ("zero_part", nothing),
                   "no_kv_scale": (None, None),
                   "bias_in_weight": ("route", biased_route)}[fault]
    if name is None:
        yield dict(cfg, mla_scale_kv_lora=False)
        return
    right = getattr(ref, name)
    setattr(ref, name, wrong)
    try:
        yield cfg
    finally:
        setattr(ref, name, right)


def in_the_programs_place(arch, cfg, seed, seqs, ref, q=None):
    """``serving.control_numbers`` for any stand-in: the reference computed
    from ``cfg`` (the cell's, or what ``faulty`` yields) with the
    control's rounding ``q`` or none, read against the float32 reference's
    logits ``ref``."""
    import numpy as np
    from bench.lib import serving
    low = serving.reference_logits(arch, cfg, seed, seqs, q)
    return serving._numbers(np.concatenate([
        serving.token_gaps(r, np.argmax(l, axis=-1)) for r, l in zip(ref, low)]))


def one(args):
    from bench import run as R
    from bench.lib import compare, serving, spec
    from bench.reference.common import fp8
    cell = spec.Cell(args.workload)
    device = R.start(cell)
    _, out = R.drive(cell, args)
    arch, cfg = cell.arch(), cell.config
    seqs = out["sequences"][:args.sequences]
    line = {"seed": args.seed, "program": out["numbers"],
            "device": device["kind"],
            "served_tokens": sum(len(s) for _, s in seqs),
            "serve_tok_s": out["measured"]["serve_tok_s"]}
    ref = serving.reference_logits(arch, cfg, args.seed, seqs)
    stand_ins = {"control_fp8": in_the_programs_place(arch, cfg, args.seed,
                                                      seqs, ref, q=fp8)}
    for fault in FAULTS:
        with faulty(fault, cfg) as wrong:
            stand_ins["fault_" + fault] = in_the_programs_place(
                arch, wrong, args.seed, seqs, ref)
    line["correct"] = {"program": compare.judge(out["numbers"], cell)[0]}
    for name, numbers in stand_ins.items():
        line[name] = numbers
        line["correct"][name] = compare.judge(numbers, cell)[0]
    print("FAULTS " + json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sequences", type=int, default=3)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int)                       # internal
    args = ap.parse_args()
    if args.seed is not None:
        return one(args)
    for seed in args.seeds.split(","):
        rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                             "--workload", args.workload, "--seconds",
                             str(args.seconds), "--sequences",
                             str(args.sequences), "--seeds", "x", "--seed",
                             seed]).returncode
        if rc:
            print("FAULTS " + json.dumps({"seed": seed, "rc": rc}), flush=True)


if __name__ == "__main__":
    main()
