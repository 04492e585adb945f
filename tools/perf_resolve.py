#!/usr/bin/env python
"""perf_resolve: turn a perf-evidence ledger into flag decisions.

Instead of re-profiling on every chip session, read the evidence already
recorded — probe ladders, bench rounds, mfu_lab rungs, autotune winners,
AOT cost stats — and emit a perf config: per device kind, the flag values / kernel block
sizes / policies the measurements justify, where EVERY decision cites
the evidence-row ids that back it. ``framework.flags.apply_perf_config``
applies matching, non-stale decisions at process startup and is never
load-bearing; ``tools/lint.py --perf-config`` asserts the provenance
(every cited id exists in the ledger, every flag exists in the FLAGS_*
registry). The ledger and the config are named by the caller; the repo
commits neither (``PERF_LEDGER.jsonl`` at the root is the driver's file
and has another schema).

    L=runs/evidence.jsonl C=runs/perf_config.json
    python tools/perf_resolve.py --ledger $L --out $C --build
                                    # ingest the root's artifacts, resolve
    python tools/perf_resolve.py --ledger $L --out $C          # resolve
    python tools/perf_resolve.py --ledger $L --out $C --check  # exit 1 on
                                                               # drift

Determinism contract (test-pinned): the same ledger bytes produce a
byte-identical config — no wall clocks, no mtimes, all iteration sorted,
conflicts tie-broken by (round desc, source priority, row id asc).
jax-free (lint.py-style package bootstrap): resolution is file-to-file
and runs on any machine, with or without a chip.

Decision rules (each cites its evidence):

  * ``use_pallas_fused`` — True only when the newest probe round's
    ``fused`` AND ``fused_adamw`` tiers both passed (bench's fused-AdamW
    regression veto, made persistent); False when either failed.
  * ``use_autotune``   — True when tuned block winners exist for the
    device (autotune rows); False when flash tiers were measured but no
    winner was ever recorded (the cache would serve nothing).
  * kernel_blocks      — every autotune winner for the device, keyed by
    the cache's own (kernel, *signature) JSON key.
  * ``remat_policy``   — from mfu_lab remat A/B rungs (tag vs
    tag-noremat): the measured faster side ('off' | 'full'), consumed
    by SpmdTrainer when the caller passes no explicit policy.

Window status: a ``probe_failed`` row NEWER than the round a device's
evidence came from marks the device ``carried`` (the last window died;
decisions are consciously inherited, not silently fresh). A decision is
``stale`` only when a newer SUCCESSFUL probe round exists that the
decision's evidence predates — apply_perf_config refuses stale
decisions.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bootstrap import REPO, bootstrap_pkg  # noqa: E402

bootstrap_pkg()
from paddle_tpu.profiler import evidence  # noqa: E402

#: conflict tie-break: lower = more authoritative for the same round
SOURCE_PRIORITY = ("probe", "bench_session", "mfu_lab", "bench",
                   "autotune", "aot_stats", "runlog", "bench_serve",
                   "flight", "mem")


def _prio(source: str) -> int:
    try:
        return SOURCE_PRIORITY.index(source)
    except ValueError:
        return len(SOURCE_PRIORITY)


def _row_rank(row) -> tuple:
    """Deterministic preference order: newest round first, then source
    priority, then row id (pure string) as the final tie-break."""
    rnum, rstr = evidence.round_order(row.get("round"))
    return (-rnum, rstr, _prio(row.get("source", "")), row["id"])


def _ledger_digest(rows) -> str:
    blob = "\n".join(sorted(r["id"] for r in rows)).encode()
    return hashlib.blake2b(blob, digest_size=8).hexdigest()


def _probe_tiers(rows):
    """{tier: best row} for a device's probe_step rows (newest round,
    tie-broken deterministically)."""
    tiers = {}
    for row in sorted((r for r in rows if r["kind"] == "probe_step"),
                      key=_row_rank):
        tier = row["data"].get("tier")
        if tier and tier not in tiers:
            tiers[tier] = row
    return tiers


def _decide_fused(tiers):
    """True ONLY when BOTH veto tiers ran and passed: a round whose
    ladder never reached fused_adamw (probe time-budget cap) leaves the
    regression veto untested — the flag must not flip on from a partial
    round."""
    fused = tiers.get("fused")
    adamw = tiers.get("fused_adamw")
    if fused is None and adamw is None:
        return None
    seen = [r for r in (fused, adamw) if r is not None]
    missing = sorted(t for t, r in (("fused", fused),
                                    ("fused_adamw", adamw)) if r is None)
    failed = sorted(r["data"]["tier"] for r in seen if not r["ok"])
    all_ok = not missing and not failed
    if all_ok:
        reason = "probe fused and fused_adamw tiers both passed"
    else:
        parts = []
        if failed:
            parts.append(f"tier(s) failed: {', '.join(failed)}")
        if missing:
            parts.append(f"tier(s) not run: {', '.join(missing)}")
        reason = ("probe " + "; ".join(parts)
                  + " (fused-AdamW regression veto)")
    return {
        "value": all_ok,
        "evidence": sorted(r["id"] for r in seen),
        "reason": reason,
    }


def _decide_autotune(rows, tiers):
    winners = sorted((r for r in rows if r["kind"] == "autotune_winner"),
                     key=_row_rank)
    if winners:
        return {
            "value": True,
            "evidence": sorted(r["id"] for r in winners[:16]),
            "reason": f"{len(winners)} tuned block winner(s) on record",
        }
    flash = sorted((tiers[t] for t in ("flash_fwd", "flash_bwd",
                                       "flashmask") if t in tiers),
                   key=_row_rank)
    if not flash:
        return None
    return {
        "value": False,
        "evidence": sorted(r["id"] for r in flash),
        "reason": "no tuned block winners on record; flash tiers were "
                  "measured at the static 128x128 default — enabling the "
                  "flag would pay first-use timing with nothing cached",
    }


def _decide_remat(rows):
    """mfu_lab A/B: '<tag>' vs '<tag>-noremat' — the measured faster side
    becomes the device's FLAGS_remat_policy ('off' = skip checkpoint
    wrapping, 'full' = recompute everything), which SpmdTrainer reads
    when the caller passes no explicit policy."""
    rungs = {}
    for row in sorted((r for r in rows if r["kind"] == "lab_rung"
                       and r["ok"]), key=_row_rank):
        tag = row["data"].get("tag")
        if tag and tag not in rungs:
            rungs[tag] = row
    for tag in sorted(rungs):
        if not tag.endswith("-noremat"):
            continue
        base = rungs.get(tag[:-len("-noremat")])
        if base is None:
            continue
        noremat = rungs[tag]
        base_tps = evidence._num(base["data"].get("tps")) or 0.0
        nr_tps = evidence._num(noremat["data"].get("tps")) or 0.0
        if not (base_tps and nr_tps):
            continue
        return {
            "value": "off" if nr_tps > base_tps else "full",
            "evidence": sorted([base["id"], noremat["id"]]),
            "reason": (f"measured {nr_tps:.0f} tok/s without remat vs "
                       f"{base_tps:.0f} with (mfu_lab A/B)"),
        }
    return None


def _kernel_blocks(rows):
    out = {}
    for row in sorted((r for r in rows if r["kind"] == "autotune_winner"),
                      key=_row_rank):
        key = json.dumps([row["data"]["kernel"]]
                         + list(row["data"]["signature"]))
        if key not in out:
            out[key] = {"block": row["data"]["block"],
                        "evidence": [row["id"]]}
    return out


def _window(rows, all_rows, decided_round, device_kind):
    """Device window status: carried when a probe_failed row is newer
    than the round the decisions came from. A failed row that NAMES a
    different device belongs to that device's window; one with no
    device_kind (a dead backend never said which device it was) counts
    against every device."""
    if decided_round is None:
        return {"status": "none", "evidence": [],
                "reason": "no probe evidence for this device"}
    dnum = evidence.round_order(decided_round)
    failed = sorted(
        (r for r in all_rows if r["kind"] == "probe_failed"
         and r.get("device_kind") in (None, device_kind)
         and evidence.round_order(r.get("round")) > dnum),
        key=_row_rank)
    if failed:
        newest = failed[0]
        return {
            "status": "carried",
            "evidence": [newest["id"]],
            "reason": ("a newer probe window failed "
                       f"({newest['data'].get('error', '?')[:120]}); "
                       f"decisions carried from {decided_round}"),
        }
    return {"status": "fresh", "evidence": [], "reason":
            f"newest probe evidence is round {decided_round}"}


def resolve(rows, ledger_name=None):
    """Pure ledger-rows -> config-dict resolution (no I/O, no clocks)."""
    by_device = {}
    for row in rows:
        dk = row.get("device_kind")
        if dk:
            by_device.setdefault(dk, []).append(row)
    devices = {}
    for dk in sorted(by_device):
        drows = by_device[dk]
        tiers = _probe_tiers(drows)
        probe_rounds = sorted(
            {r.get("round") for r in drows if r["kind"] == "probe_step"},
            key=evidence.round_order)
        decided_round = probe_rounds[-1] if probe_rounds else None
        newest_ok_round = decided_round  # probe_step rows exist => probe ran
        flags = {}
        for name, decide in (("use_pallas_fused",
                              lambda: _decide_fused(tiers)),
                             ("use_autotune",
                              lambda: _decide_autotune(drows, tiers)),
                             ("remat_policy",
                              lambda: _decide_remat(drows))):
            decision = decide()
            if decision is None:
                continue
            # stale = superseded: a newer SUCCESSFUL probe round exists
            # that this decision's evidence predates (by construction
            # the decisions above always read the newest round, so stale
            # only triggers for carried-in ledgers merged from older
            # trees). Round-LESS evidence (the autotune cache file has
            # no round in its name) cannot be ordered against probe
            # rounds and is never marked stale by them.
            ev_rounds = [r.get("round") for r in drows
                         if r["id"] in set(decision["evidence"])
                         and r.get("round") is not None]
            decision["stale"] = bool(
                ev_rounds and newest_ok_round is not None
                and max(evidence.round_order(r) for r in ev_rounds)
                < evidence.round_order(newest_ok_round))
            flags[name] = decision
        devices[dk] = {
            "round": decided_round,
            "window": _window(drows, rows, decided_round, dk),
            "flags": flags,
            "kernel_blocks": _kernel_blocks(drows),
        }
    return {
        "schema": 1,
        "generated_by": "tools/perf_resolve.py",
        "ledger": ledger_name,
        "ledger_rows": len(rows),
        "ledger_digest": _ledger_digest(rows),
        "tie_break": "(round desc, source priority, row id asc)",
        "devices": devices,
    }


def render(config) -> str:
    """The byte-identical serialization (sorted keys, indent 1, trailing
    newline)."""
    return json.dumps(config, indent=1, sort_keys=True) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ledger", required=True,
                    help="evidence ledger JSONL to read (and, with "
                         "--build, to merge into)")
    ap.add_argument("--out", required=True,
                    help="perf config JSON to write or --check")
    ap.add_argument("--build", action="store_true",
                    help="re-ingest the repo's committed artifacts into "
                         "the ledger before resolving")
    ap.add_argument("--extra", action="append", default=[],
                    metavar="FILE", help="extra artifact files to ingest "
                    "with --build (repeatable)")
    ap.add_argument("--repo", default=REPO,
                    help="artifact root for --build (default: repo root)")
    ap.add_argument("--check", action="store_true",
                    help="do not write; exit 1 if --out would change")
    args = ap.parse_args(argv)

    if args.build:
        ledger, report = evidence.build_ledger(args.repo, args.ledger,
                                               extra_paths=args.extra)
        ingested = sum(report.values())
        print(f"perf_resolve: ingested {ingested} row(s) from "
              f"{len(report)} artifact(s) into {args.ledger}")
    rows, quarantined = evidence.read_rows(args.ledger)
    if quarantined:
        print(f"perf_resolve: quarantined {len(quarantined)} malformed "
              f"ledger line(s)", file=sys.stderr)
    config = resolve(rows, os.path.basename(args.ledger))
    text = render(config)
    if args.check:
        try:
            with open(args.out) as f:
                committed = f.read()
        except OSError:
            committed = None
        if committed != text:
            print(f"perf_resolve: {args.out} is out of date with "
                  f"{args.ledger} (re-run tools/perf_resolve.py)",
                  file=sys.stderr)
            return 1
        print(f"perf_resolve: {args.out} matches the ledger "
              f"({len(rows)} rows)")
        return 0
    tmp = f"{args.out}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, args.out)
    n_flags = sum(len(d["flags"]) for d in config["devices"].values())
    print(f"perf_resolve: wrote {args.out} — {len(config['devices'])} "
          f"device(s), {n_flags} flag decision(s) from {len(rows)} "
          f"evidence row(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
