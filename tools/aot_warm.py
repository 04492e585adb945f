#!/usr/bin/env python
"""Pre-populate an AOT program-artifact cache for a named config.

Chip time (and a preemptible pod slot) is too expensive to spend
tracing: this tool compiles+exports the programs a named configuration
will need into a ``paddle_tpu.aot.ArtifactStore`` ahead of time, so the
real run — or a supervised restart generation, or a serving scale-up
replica — warm-starts with cache hits. Run it on the SAME topology the
artifacts must serve (the fingerprint commits to device kind/count and
mesh axes: a cache warmed on CPU is a clean miss, never a wrong hit,
on TPU).

    python tools/aot_warm.py --cache runs/r0/aot --config toy-trainer
    python tools/aot_warm.py --cache runs/r0/aot --config tiny-llama-serve \
        --max-seqs 8 --token-budget 64
    python tools/aot_warm.py --cache runs/r0/aot --stats

Named configs:

  toy-trainer       the drill/test toy SpmdTrainer step (Sequential
                    4->16->1, SGD+MSE) — the ``spmd_train_step`` program
  tiny-llama-serve  tiny Llama ServingEngine (construction warms the
                    ``serve_engine_step`` program from avals alone)
  tiny-gpt-serve    tiny GPT variant of the same
  tiny-llama-serve-mp2 / tiny-gpt-serve-mp2
                    the same serving programs under an mp=2 tensor-
                    parallel mesh (weights column/row-split, KV pools
                    per-KV-head) — pre-populates the TP engine
                    artifacts.
                    ``--mp N`` overrides the degree on any serve
                    config; the mesh geometry is part of the
                    fingerprint, so every degree is its own artifact.
  tiny-llama-serve-prefill / tiny-llama-serve-decode
                    the disaggregated pool programs: the prefill-role
                    engine's wide chunked-prefill step (token budget 64)
                    and the decode-role engine's token-thin step
                    (token budget 16). The ROLE is scheduler policy,
                    not program shape — what forks the artifact is the
                    per-role token budget, which is exactly the point
                    of the split (decode never rides a prefill-width
                    program). ``--role`` sets it on any serve config.

Exit code 0 = every program for the config is now in the ledger
(freshly exported, or already present = a hit).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CONFIGS = ("toy-trainer", "tiny-llama-serve", "tiny-gpt-serve",
           "tiny-llama-serve-mp2", "tiny-gpt-serve-mp2",
           "tiny-llama-serve-prefill", "tiny-llama-serve-decode")


def _ensure_host_devices(n: int) -> None:
    """A TP warm needs n visible devices BEFORE jax initializes; on a
    CPU host that is the forced-host-platform flag (on real TPU
    topologies the devices are simply there)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
            f"{max(n, 2)}").strip()


def warm_toy_trainer(cache: str, seed: int = 1234) -> dict:
    """One real train step through SpmdTrainer(aot_cache=cache): traces,
    exports, publishes ``spmd_train_step`` (or hits if already warm)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.parallel import SpmdTrainer

    paddle.seed(seed)
    np.random.seed(seed % (2 ** 31))
    x = np.random.randn(64, 4).astype(np.float32)
    y = (x @ np.random.randn(4, 1)).astype(np.float32)
    net = nn.Sequential(nn.Linear(4, 16), nn.ReLU(), nn.Linear(16, 1))
    mse = nn.MSELoss()

    def loss_fn(model, xb, yb):
        return mse(model(xb), yb)

    trainer = SpmdTrainer(net, optimizer.SGD(learning_rate=0.01,
                                             parameters=net.parameters()),
                          loss_fn, aot_cache=cache)
    trainer.train_step(paddle.to_tensor(x), paddle.to_tensor(y))
    trainer.block()
    return dict(trainer._step_fn.stats)


def warm_serve(cache: str, family: str, seed: int = 3, max_seqs: int = 8,
               token_budget: int = 64, block_size: int = 16,
               quant=None, mp: int = 1, role=None) -> dict:
    """Construct a ServingEngine over the tiny model: construction
    materializes ``serve_engine_step`` from avals (no tokens run).
    ``mp > 1`` warms the tensor-parallel program instead — the sharded
    engine that serving replicas deserialize.
    ``role`` warms a disaggregated pool's engine (the prefill/decode
    budgets produce differently-shaped programs)."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import EngineConfig, ServingEngine

    paddle.seed(seed)
    if family == "llama":
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig.tiny(vocab_size=61, hidden_size=32, layers=2,
                               heads=4, kv_heads=2, seq=64)
        cfg.use_flash_attention = False
        model = LlamaForCausalLM(cfg)
    else:
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        cfg = GPTConfig.tiny(vocab_size=53, hidden_size=32, layers=2,
                             heads=4, seq=64)
        model = GPTForCausalLM(cfg)
    engine = ServingEngine(model, EngineConfig(
        max_seqs=max_seqs, token_budget=token_budget,
        block_size=block_size, quant=quant, aot_cache=cache,
        mesh=mp if mp > 1 else None, role=role))
    return {"warm": engine.aot_warm_result, "mp": mp, "role": role,
            **dict(engine._step_call.stats)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cache", required=True,
                    help="artifact-store directory (created if absent)")
    ap.add_argument("--config", choices=CONFIGS, default=None,
                    help="named program set to warm")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--max-seqs", type=int, default=8)
    ap.add_argument("--token-budget", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--quant", default=None,
                    help="serving weight quantization (int8|int4)")
    ap.add_argument("--mp", type=int, default=None,
                    help="tensor-parallel degree for the serve configs "
                         "(default 1; the -mp2 named configs imply 2)")
    ap.add_argument("--role", choices=("prefill", "decode"), default=None,
                    help="disaggregated pool role for the serve configs "
                         "(the -prefill/-decode named configs imply it, "
                         "with token budgets 64/16)")
    ap.add_argument("--stats", action="store_true",
                    help="print the cache ledger and exit")
    args = ap.parse_args(argv)
    mp = args.mp
    if mp is None:
        mp = 2 if args.config and args.config.endswith("-mp2") else 1
    if mp > 1:
        _ensure_host_devices(mp)   # must land before jax initializes
    role = args.role
    if role is None and args.config:
        if args.config.endswith("-prefill"):
            role = "prefill"
        elif args.config.endswith("-decode"):
            role = "decode"
    token_budget = args.token_budget
    if role == "decode" and args.config and \
            args.config.endswith("-decode") and token_budget == 64:
        # the decode pool's whole point is the token-thin program
        token_budget = 16

    from paddle_tpu.aot.store import ArtifactStore
    store = ArtifactStore(args.cache)
    if args.stats:
        print(json.dumps({"stats": store.stats(),
                          "entries": store.keys()}, indent=1,
                         sort_keys=True, default=str))
        return 0
    if args.config is None:
        ap.error("--config (or --stats) is required")
    # artifacts serve only the backend they were compiled on: say which
    from paddle_tpu.utils import chip
    device = chip.device_summary()
    chip.enable_compile_cache()
    t0 = time.monotonic()
    if args.config == "toy-trainer":
        stats = warm_toy_trainer(args.cache, seed=args.seed)
    else:
        family = "llama" if "llama" in args.config else "gpt"
        stats = warm_serve(args.cache, family, seed=args.seed,
                           max_seqs=args.max_seqs,
                           token_budget=token_budget,
                           block_size=args.block_size, quant=args.quant,
                           mp=mp, role=role)
    dt = time.monotonic() - t0
    ok = stats.get("fallbacks", 0) == 0
    print(f"aot_warm: {args.config} on {device['count']} x "
          f"{device['kind']} -> {args.cache} in {dt:.2f}s "
          f"({stats}); store now holds "
          f"{store.stats()['artifacts']} artifact(s)")
    if not ok:
        print("aot_warm: FALLBACK occurred — the program was not "
              "published; see the log above", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
