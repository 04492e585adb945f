#!/usr/bin/env python
"""mem_report: per-chip memory budget breakdown + the what-fits planner.

The reading half of the memory observability plane
(``paddle_tpu/profiler/memwatch.py``): joins the measured evidence — the
memory watcher's ledger rows (pool split, watermarks, near-OOM dumps)
and the AOT cache's per-program ``memory_analysis`` stats (temp /
argument / output bytes) — into one budget table, and answers the
question every config change starts with, **"does this fit?"**, with no
devices attached:

    python tools/mem_report.py                      # budget report from
                                                    # the committed ledger
    python tools/mem_report.py --plan --preset llama2-7b \\
        --mesh mp=4,sharding=8 --dtype bf16 --batch 32 --context 4096 \\
        --optimizer adamw --zero 2 --fits 16       # per-chip prediction
    python tools/mem_report.py --self-check         # planner math vs the
                                                    # committed fixture

The planner (``plan()``) predicts per-chip bytes from pure config
arithmetic — the same abstract-shape reasoning shardcheck's layout
evaluator applies, reduced to closed form so the tool stays stdlib-only
(jax-free bootstrap; a capacity question must not wait on a framework
import). The parameter count is EXACT for the Llama family this repo
trains and serves (validated against live CPU array bytes in
tests/test_memwatch.py); the components:

  * ``params``      — param count x dtype bytes, / mp (TP annotations),
                      / sharding at ZeRO-3 (FSDP storage);
  * ``gradients``   — params-shaped, / sharding at ZeRO >= 2
                      (reduce-scatter layout);
  * ``optimizer``   — f32 moment slots per optimizer family (adamw 2,
                      momentum 1 in param dtype, sgd 0), / sharding at
                      ZeRO >= 1;
  * ``activations`` — layers x act-factor(remat) x per-chip batch x
                      context x hidden x dtype bytes. The act factor is
                      a DOCUMENTED coarse model (full remat keeps layer
                      boundaries only); this component is an estimate
                      and is labeled as such in the output;
  * ``kv_cache``    — (serve mode) 2 x layers x kv_heads x head_dim x
                      page geometry x kv dtype, / mp (pools shard
                      per-head) — exactly the engine's preallocated
                      ``_kp``/``_vp`` byte count;
  * ``workspace``   — XLA temp bytes when an AOT ``memory_analysis``
                      figure is supplied (--workspace or the stats
                      file); otherwise 0 with a note.

This is the memory-per-chip cost term ROADMAP item 3's sharding
auto-planner needs (score a candidate mesh without hardware), and the
capacity pre-check for item 5's 32k-128k-context serving rungs.
``--self-check`` pins the arithmetic against the committed fixture
(``tools/mem_plan_baseline.json``) and runs in ``tools/lint.py``'s
default pass.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bootstrap import REPO, bootstrap_pkg  # noqa: E402

bootstrap_pkg()

FIXTURE = os.path.join(REPO, "tools", "mem_plan_baseline.json")

#: storage bits per element by dtype spelling (int4 packs two per byte)
DTYPE_BITS = {
    "float32": 32, "fp32": 32, "f32": 32,
    "bfloat16": 16, "bf16": 16, "float16": 16, "fp16": 16,
    "int8": 8, "fp8": 8, "float8": 8, "float8_e4m3fn": 8,
    "int4": 4,
}

#: f32 moment slots the optimizer stores per parameter ("dtype" marks
#: families whose state follows the param dtype instead of f32)
OPTIMIZER_STATE = {
    "adamw": {"slots": 2, "bits": 32},
    "adam": {"slots": 2, "bits": 32},
    "momentum": {"slots": 1, "bits": None},  # velocity in param dtype
    "sgd": {"slots": 0, "bits": 32},
}

#: live-activation multiplier per transformer layer, by remat policy —
#: a documented coarse model: "full" keeps only layer-boundary
#: activations (input + output of the checkpointed block), "dots" also
#: keeps the MXU matmul outputs, "off" keeps every intermediate
#: (qkv/scores/mlp expansions; flash attention assumed, no seq^2 term).
ACT_FACTORS = {"full": 2, "dots": 4, "off": 14}

#: named model configs the CLI accepts without a framework import
#: (dims mirror paddle_tpu.models.llama.LlamaConfig constructors)
PRESETS = {
    "toy": {"vocab_size": 61, "hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "max_position_embeddings": 64},
    "tiny-llama-serve": {
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "max_position_embeddings": 128},
    "llama2-7b": {
        "vocab_size": 32000, "hidden_size": 4096,
        "intermediate_size": 11008, "num_hidden_layers": 32,
        "num_attention_heads": 32, "num_key_value_heads": None,
        "max_position_embeddings": 4096},
    "llama2-13b": {
        "vocab_size": 32000, "hidden_size": 5120,
        "intermediate_size": 13824, "num_hidden_layers": 40,
        "num_attention_heads": 40, "num_key_value_heads": None,
        "max_position_embeddings": 4096},
}


def _bits(dtype: str) -> int:
    try:
        return DTYPE_BITS[str(dtype).lower()]
    except KeyError:
        raise ValueError(
            f"unknown dtype {dtype!r} (want one of {sorted(DTYPE_BITS)})")


def _bytes_of(count: int, bits: int) -> int:
    return (int(count) * int(bits)) // 8


def param_counts(cfg: dict) -> dict:
    """Exact per-family parameter counts for the Llama architecture
    (q/k/v/o projections, SwiGLU gate/up/down, RMSNorm pairs + final,
    tied-or-separate embedding/lm_head). Validated against the real
    model's ``named_parameters`` in tests/test_memwatch.py."""
    h = int(cfg["hidden_size"])
    inter = int(cfg["intermediate_size"])
    layers = int(cfg["num_hidden_layers"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg.get("num_key_value_heads") or heads)
    vocab = int(cfg["vocab_size"])
    tied = bool(cfg.get("tie_word_embeddings", False))
    hd = h // heads
    attention = h * heads * hd + 2 * h * kv * hd + heads * hd * h
    mlp = 3 * h * inter
    norms = 2 * h
    embedding = vocab * h * (1 if tied else 2)
    total = embedding + layers * (attention + mlp + norms) + h
    return {"embedding": embedding, "attention": layers * attention,
            "mlp": layers * mlp, "norms": layers * norms + h,
            "total": total}


def plan(cfg: dict, *, mesh: dict = None, dtype: str = "float32",
         mode: str = "train", optimizer: str = "adamw",
         zero_stage: int = 1, batch: int = 1, context: int = None,
         remat: str = "full", accumulate_steps: int = 1,
         kv_dtype: str = None, block_size: int = 16,
         num_blocks: int = None, max_seqs: int = 8,
         workspace_bytes: int = 0, hbm_gib: float = None,
         role: str = None) -> dict:
    """Devices-free per-chip memory prediction. See module docstring for
    the component model; every figure is integer bytes so the committed
    fixture pins the arithmetic exactly.

    ``role`` (serve mode only; None = unified engine) prices a
    disaggregated pool's KV separately. The two pools want opposite
    shapes: a PREFILL pool needs DEPTH — every in-flight prefill holds
    its whole prompt's pages only until the hand-off, so ``max_seqs``
    is the concurrent-prefill count and ``context`` the prompt budget —
    while a DECODE pool needs RESIDENCY — sequences hold their pages
    for the whole decode lifetime, so ``max_seqs`` is the resident
    batch and ``context`` the full prompt+output length. Both roles
    additionally price ``kv_staging``: one max-depth request's pages
    live OUTSIDE the pool during a hand-off (the export's gathered
    copies on the prefill side, the pre-scatter arrays on the decode
    side), which the unified engine never pays."""
    if mode not in ("train", "serve"):
        raise ValueError(f"mode must be train|serve, got {mode!r}")
    if role not in (None, "prefill", "decode"):
        raise ValueError(
            f"role must be prefill|decode|None, got {role!r}")
    if role is not None and mode != "serve":
        raise ValueError("role= is a serve-mode term (engine pools)")
    if remat not in ACT_FACTORS:
        raise ValueError(
            f"remat must be one of {sorted(ACT_FACTORS)}, got {remat!r}")
    if optimizer not in OPTIMIZER_STATE:
        raise ValueError(
            f"optimizer must be one of {sorted(OPTIMIZER_STATE)}, "
            f"got {optimizer!r}")
    if zero_stage not in (0, 1, 2, 3):
        raise ValueError(f"zero_stage must be 0-3, got {zero_stage}")
    mesh = dict(mesh or {})
    mp = max(int(mesh.get("mp", 1)), 1)
    sharding = max(int(mesh.get("sharding", 1)), 1)
    dp = max(int(mesh.get("dp", 1)), 1)
    data_degree = dp * sharding  # SpmdTrainer batch_axes=("dp","sharding")
    counts = param_counts(cfg)
    bits = _bits(dtype)
    h = int(cfg["hidden_size"])
    layers = int(cfg["num_hidden_layers"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg.get("num_key_value_heads") or heads)
    hd = h // heads
    ctx = int(context or cfg.get("max_position_embeddings") or 2048)

    components = {}
    estimates = []
    if mode == "train":
        components["params"] = _bytes_of(counts["total"], bits) \
            // mp // (sharding if zero_stage >= 3 else 1)
        components["gradients"] = _bytes_of(counts["total"], bits) \
            // mp // (sharding if zero_stage >= 2 else 1)
        opt = OPTIMIZER_STATE[optimizer]
        obits = opt["bits"] if opt["bits"] is not None else bits
        components["optimizer"] = \
            _bytes_of(counts["total"] * opt["slots"], obits) \
            // mp // (sharding if zero_stage >= 1 else 1)
        per_chip_batch = max(batch // (data_degree
                                       * max(accumulate_steps, 1)), 1)
        components["activations"] = layers * ACT_FACTORS[remat] \
            * _bytes_of(per_chip_batch * ctx * h, bits) // mp
        estimates.append("activations")
    else:
        components["params"] = _bytes_of(counts["total"], bits) // mp
        kbits = _bits(kv_dtype) if kv_dtype else bits
        pages = num_blocks if num_blocks is not None \
            else max_seqs * -(-ctx // block_size)
        # exactly the engine's _kp + _vp preallocation:
        # 2 pools x [layers, pages, kv_heads, block, head_dim]
        components["kv_cache"] = _bytes_of(
            2 * layers * pages * kv * block_size * hd, kbits) // mp
        # packed ragged batch activations are token_budget-sized: noise
        if role is not None:
            # one max-depth request's pages in flight across the pool
            # boundary (export copies / pre-scatter arrays), beyond the
            # pool itself — the hand-off's working-set tax
            staging_pages = -(-ctx // block_size)
            components["kv_staging"] = _bytes_of(
                2 * layers * staging_pages * kv * block_size * hd,
                kbits) // mp
    components["workspace"] = int(workspace_bytes)
    if not workspace_bytes:
        estimates.append("workspace")

    per_chip = sum(components.values())
    out = {
        "schema": 1,
        "mode": mode,
        "dtype": dtype,
        # role key only present when set, so pre-disagg fixture cases
        # (and their committed expectations) stay byte-identical
        **({"role": role} if role is not None else {}),
        "mesh": {"mp": mp, "sharding": sharding, "dp": dp},
        "zero_stage": zero_stage if mode == "train" else None,
        "context": ctx,
        "params_count": counts,
        "components": components,
        "estimate_components": sorted(estimates),
        "per_chip_bytes": per_chip,
    }
    if hbm_gib is not None:
        hbm = int(hbm_gib * (1 << 30))
        out["hbm_bytes"] = hbm
        out["fits"] = per_chip <= hbm
        out["headroom_bytes"] = hbm - per_chip
    else:
        out["hbm_bytes"] = None
        out["fits"] = None
        out["headroom_bytes"] = None
    return out


# -- self-check (lint-gated) --------------------------------------------------
def self_check(fixture_path: str = FIXTURE) -> list:
    """Planner math vs the committed fixture; returns a list of
    human-readable mismatch strings (empty = green). Exact integer
    comparison: the planner has no clocks and no floats in its output
    except fits/headroom, which the fixture pins too."""
    try:
        with open(fixture_path) as f:
            fixture = json.load(f)
    except (OSError, ValueError) as e:
        return [f"fixture unreadable: {e}"]
    problems = []
    for case in fixture.get("cases", []):
        name = case.get("name", "?")
        try:
            got = plan(case["cfg"], **case.get("kwargs", {}))
        except Exception as e:  # noqa: BLE001 — a raise IS the finding
            problems.append(f"{name}: plan() raised {e!r}")
            continue
        want = case.get("expect")
        if got != want:
            for key in sorted(set(got) | set(want or {})):
                if got.get(key) != (want or {}).get(key):
                    problems.append(
                        f"{name}: {key} drifted — got {got.get(key)!r}, "
                        f"fixture {(want or {}).get(key)!r}")
    if not fixture.get("cases"):
        problems.append("fixture has no cases")
    return problems


# -- budget report from measured evidence -------------------------------------
def _fmt_bytes(v) -> str:
    if v is None:
        return "-"
    v = float(v)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(v) < 1024 or unit == "TiB":
            return f"{v:.1f}{unit}" if unit != "B" else f"{int(v)}B"
        v /= 1024.0


def report(ledger_path: str, aot_stats_path: str = None) -> dict:
    """Join the measured memory evidence into one budget view: the
    newest mem_snapshot row (pool split + watermarks + pressure reason)
    and every per-program static footprint (aot_stats rows' ``mem``
    blocks, or a live PADDLE_AOT_STATS file)."""
    from paddle_tpu.profiler import evidence

    rows, _ = evidence.read_rows(ledger_path)
    mem_rows = [r for r in rows if r.get("kind") == "mem_snapshot"]
    programs = {}
    for r in rows:
        if r.get("kind") == "program_cost" and \
                isinstance((r.get("data") or {}).get("mem"), dict):
            programs[r["data"]["program"]] = dict(r["data"]["mem"])
    if aot_stats_path and os.path.exists(aot_stats_path):
        for r in evidence.ingest_aot_stats(aot_stats_path):
            if isinstance((r.get("data") or {}).get("mem"), dict):
                programs[r["data"]["program"]] = dict(r["data"]["mem"])
    latest = mem_rows[-1] if mem_rows else None
    return {
        "ledger": os.path.basename(ledger_path),
        "mem_rows": len(mem_rows),
        "latest": (latest or {}).get("data"),
        "device_kind": (latest or {}).get("device_kind"),
        "programs": programs,
    }


def render_report(rep: dict) -> str:
    lines = [f"mem_report — ledger {rep['ledger']} "
             f"({rep['mem_rows']} mem row(s))"]
    latest = rep.get("latest")
    if latest:
        last = latest.get("last") or {}
        lines.append(
            f"  latest snapshot [{rep.get('device_kind') or '?'}]: "
            f"in use {_fmt_bytes(last.get('bytes_in_use'))}"
            + (f" / limit {_fmt_bytes(last.get('bytes_limit'))}"
               if last.get("bytes_limit") else "")
            + f"  (reason: {latest.get('reason')})")
        pools = last.get("pools") or {}
        for name in sorted(pools):
            if pools[name]:
                lines.append(f"    {name:<10} {_fmt_bytes(pools[name])}")
        wm = (latest.get("watermarks") or {})
        if wm.get("peak_bytes_in_use"):
            lines.append(f"    watermark  "
                         f"{_fmt_bytes(wm['peak_bytes_in_use'])}"
                         + (f"  ({wm.get('peak_fraction', 0) * 100:.1f}% "
                            "of limit)" if wm.get("peak_fraction") else ""))
    else:
        lines.append("  no mem_snapshot rows in the ledger yet "
                     "(arm PADDLE_MEMWATCH and ingest a dump)")
    progs = rep.get("programs") or {}
    if progs:
        lines.append("  static per-program footprint "
                     "(AOT memory_analysis):")
        for name in sorted(progs):
            m = progs[name]
            lines.append(
                f"    {name:<22} temp {_fmt_bytes(m.get('temp_bytes'))}  "
                f"args {_fmt_bytes(m.get('argument_bytes'))}  "
                f"out {_fmt_bytes(m.get('output_bytes'))}")
    return "\n".join(lines) + "\n"


def render_plan(p: dict) -> str:
    lines = [f"what-fits — mode {p['mode']}, dtype {p['dtype']}, "
             f"mesh {p['mesh']}, context {p['context']}"
             + (f", zero {p['zero_stage']}"
                if p["zero_stage"] is not None else "")]
    lines.append(f"  params count      "
                 f"{p['params_count']['total']:,}")
    for name, b in sorted(p["components"].items()):
        est = " (estimate)" if name in p["estimate_components"] else ""
        lines.append(f"  {name:<17} {_fmt_bytes(b):>10}{est}")
    lines.append(f"  per-chip total    {_fmt_bytes(p['per_chip_bytes']):>10}")
    if p["hbm_bytes"] is not None:
        verdict = "FITS" if p["fits"] else "DOES NOT FIT"
        lines.append(
            f"  vs {_fmt_bytes(p['hbm_bytes'])} HBM: {verdict} "
            f"(headroom {_fmt_bytes(p['headroom_bytes'])})")
    return "\n".join(lines) + "\n"


def _parse_mesh(spec: str) -> dict:
    out = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        axis, _, deg = part.partition("=")
        out[axis.strip()] = int(deg)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--self-check", action="store_true",
                    help="planner math vs the committed fixture "
                         "(tools/mem_plan_baseline.json); exit 1 on drift")
    ap.add_argument("--update-fixture", action="store_true",
                    help="recompute the committed fixture's expectations "
                         "from the current planner (review the diff!)")
    ap.add_argument("--plan", action="store_true",
                    help="run the what-fits planner instead of the "
                         "evidence report")
    ap.add_argument("--preset", choices=sorted(PRESETS), default="toy")
    ap.add_argument("--mode", choices=("train", "serve"), default="train")
    ap.add_argument("--role", choices=("prefill", "decode"), default=None,
                    help="serve mode: price a disaggregated pool "
                         "(prefill = depth, decode = residency; both "
                         "add the hand-off kv_staging term)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--kv-dtype", default=None)
    ap.add_argument("--mesh", default="", help="e.g. mp=4,sharding=8,dp=1")
    ap.add_argument("--zero", type=int, default=1)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--context", type=int, default=None)
    ap.add_argument("--remat", choices=sorted(ACT_FACTORS), default="full")
    ap.add_argument("--optimizer", choices=sorted(OPTIMIZER_STATE),
                    default="adamw")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None)
    ap.add_argument("--max-seqs", type=int, default=8)
    ap.add_argument("--workspace", type=int, default=0,
                    help="XLA temp bytes (from an AOT memory_analysis row)")
    ap.add_argument("--fits", type=float, default=None, metavar="GIB",
                    help="HBM budget to verdict against (e.g. 16)")
    ap.add_argument("--ledger", default=None, metavar="FILE",
                    help="evidence ledger JSONL to report on (built by "
                         "tools/perf_resolve.py --build)")
    ap.add_argument("--aot-stats", default=None,
                    help="live PADDLE_AOT_STATS file to join per-program "
                         "memory_analysis from")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    if args.self_check:
        problems = self_check()
        if problems:
            for p in problems:
                print(f"mem_report self-check: {p}", file=sys.stderr)
            return 1
        with open(FIXTURE) as f:
            n = len(json.load(f).get("cases", []))
        print(f"mem_report self-check: {n} fixture case(s) match the "
              "planner exactly")
        return 0

    if args.update_fixture:
        with open(FIXTURE) as f:
            fixture = json.load(f)
        for case in fixture.get("cases", []):
            case["expect"] = plan(case["cfg"], **case.get("kwargs", {}))
        tmp = f"{FIXTURE}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(fixture, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, FIXTURE)
        print(f"rewrote {FIXTURE} ({len(fixture.get('cases', []))} cases)")
        return 0

    if args.plan:
        p = plan(PRESETS[args.preset], mesh=_parse_mesh(args.mesh),
                 dtype=args.dtype, mode=args.mode, optimizer=args.optimizer,
                 zero_stage=args.zero, batch=args.batch,
                 context=args.context, remat=args.remat,
                 kv_dtype=args.kv_dtype, block_size=args.block_size,
                 num_blocks=args.num_blocks, max_seqs=args.max_seqs,
                 workspace_bytes=args.workspace, hbm_gib=args.fits,
                 role=args.role)
        print(json.dumps(p, indent=1, sort_keys=True) if args.as_json
              else render_plan(p), end="")
        return 0

    if not args.ledger:
        ap.error("the report mode needs --ledger FILE")
    rep = report(args.ledger, args.aot_stats)
    print(json.dumps(rep, indent=1, sort_keys=True) if args.as_json
          else render_report(rep), end="")
    return 0


if __name__ == "__main__":
    sys.exit(main())
