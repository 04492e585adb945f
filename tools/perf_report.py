#!/usr/bin/env python
"""perf_report: offline "where did the step go" over the evidence ledger.

The serve_top of the perf plane: renders a PerfEvidence ledger (built
by ``tools/perf_resolve.py --build``) as a static report — step-time anatomy
(compute/collective/data/host fractions from runlog wall times joined
with per-program XLA cost_analysis), top programs by modeled time with
their roofline position (compute- vs memory-bound), the MFU delta
against the newest hardware training session in the ledger, the probe
tier table, serving bench summaries, and the resolver decisions in
effect per device. jax-free (lint.py-style bootstrap): reads files,
renders text.

    python tools/perf_report.py --ledger runs/evidence.jsonl
    python tools/perf_report.py --runlog runs/r0/runlog_rank0.jsonl \\
        --aot-stats runs/r0/aot_stats_0.json       # a live run alone
    python tools/perf_report.py ... --json         # machine-readable
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bootstrap import bootstrap_pkg  # noqa: E402

bootstrap_pkg()
from paddle_tpu.profiler import evidence  # noqa: E402


def _newest(rows, kind, ok_only=False):
    best, best_key = None, None
    for row in rows:
        if row["kind"] != kind or (ok_only and not row["ok"]):
            continue
        key = (evidence.round_order(row.get("round")), row["id"])
        if best_key is None or key > best_key:
            best, best_key = row, key
    return best


def build_report(rows, quarantined, config, runlog_rows, aot_rows
                 ) -> dict:
    """Pure rows -> report dict (rendering and JSON mode share it)."""
    all_rows = rows + runlog_rows + aot_rows
    by_source = {}
    for row in all_rows:
        by_source[row["source"]] = by_source.get(row["source"], 0) + 1

    anchor = _newest(all_rows, "train_session")
    summary = _newest(all_rows, "runlog_summary")
    meta = _newest(all_rows, "runlog_meta")
    costs = {}
    cost_rows = []
    for row in sorted(all_rows, key=lambda r: r["id"]):
        if row["kind"] == "program_cost" and row["data"].get("cost"):
            name = row["data"]["program"]
            if name not in costs:
                costs[name] = row["data"]["cost"]
                cost_rows.append(row)

    # the device the ANATOMY is computed for: prefer what the joined
    # run actually measured on (cost stats / runlog meta) over the
    # committed hardware anchor — joining a CPU run must not price its
    # roofline against the anchor's TPU peaks
    device_kind = None
    for row in [r for r in cost_rows] + [meta, summary, anchor]:
        if row is not None and row.get("device_kind"):
            device_kind = row["device_kind"]
            break

    anatomy = None
    last_step = (summary or {}).get("data", {}).get("last_step") or {}
    wall_ms = last_step.get("step_time_ms")
    peak_flops = (meta or {}).get("data", {}).get("peak_flops") \
        or evidence.peak_flops_for_kind(device_kind)
    peak_bw = evidence.peak_bytes_for_kind(device_kind)
    if wall_ms and costs and peak_flops:
        anatomy = evidence.attribute_step(
            wall_ms / 1000.0, costs, peak_flops, peak_bw)

    current_mfu = last_step.get("mfu")
    if current_mfu is None and anatomy is not None:
        current_mfu = anatomy.get("mfu")
    anchor_mfu = (anchor or {}).get("data", {}).get("mfu")

    probe = {}
    for row in sorted((r for r in all_rows if r["kind"] == "probe_step"),
                      key=lambda r: (evidence.round_order(r.get("round")),
                                     r["id"])):
        probe[row["data"]["tier"]] = row

    serve = _newest(all_rows, "serve_summary")
    decisions = {}
    for dk, entry in sorted((config or {}).get("devices", {}).items()):
        decisions[dk] = {
            "window": entry.get("window", {}).get("status"),
            "flags": {name: {"value": d.get("value"),
                             "stale": d.get("stale"),
                             "evidence": len(d.get("evidence") or [])}
                      for name, d in sorted(
                          (entry.get("flags") or {}).items())},
        }
    return {
        "rows": len(all_rows),
        "quarantined": len(quarantined),
        "by_source": by_source,
        "device_kind": device_kind,
        "peak_flops": peak_flops,
        "peak_bytes_per_s": peak_bw,
        "anchor": {"file": anchor["file"],
                   "mfu": anchor_mfu,
                   "tps": anchor["data"].get("value"),
                   "config": anchor["data"].get("config")}
        if anchor else None,
        "current_mfu": current_mfu,
        "mfu_delta": (current_mfu - anchor_mfu
                      if current_mfu is not None and anchor_mfu is not None
                      else None),
        "anatomy": anatomy,
        "probe_tiers": {t: r["data"] for t, r in sorted(probe.items())},
        "probe_failed": [r["data"] for r in all_rows
                         if r["kind"] == "probe_failed"],
        "serve": serve["data"] if serve else None,
        "decisions": decisions,
    }


def _bar(frac, width=28):
    frac = min(max(float(frac or 0.0), 0.0), 1.0)
    n = int(round(frac * width))
    return "[" + "#" * n + "-" * (width - n) + "]"


def render(rep: dict) -> str:
    lines = []
    srcs = "  ".join(f"{s}={n}" for s, n in sorted(rep["by_source"].items()))
    lines.append(f"paddle_tpu perf_report — {rep['rows']} evidence rows "
                 f"({srcs})")
    if rep["quarantined"]:
        lines.append(f"  quarantined {rep['quarantined']} malformed "
                     "ledger line(s)")
    lines.append("-" * 72)

    if rep["anchor"]:
        a = rep["anchor"]
        lines.append(f"mfu anchor  {a['file']}  config {a['config']}  "
                     f"{a['tps']:.0f} tok/s  mfu "
                     f"{a['mfu'] * 100:.1f}%" if a["mfu"] is not None
                     else f"mfu anchor  {a['file']}")
    if rep["current_mfu"] is not None:
        delta = rep["mfu_delta"]
        tail = (f"  delta {delta * 100:+.1f}pt vs anchor"
                if delta is not None else "")
        lines.append(f"current     mfu {rep['current_mfu'] * 100:.1f}%"
                     f"{tail}")
    elif rep["anchor"]:
        lines.append("current     no runlog evidence in ledger (anchor "
                     "carries the number)")

    anat = rep["anatomy"]
    if anat:
        lines.append("")
        lines.append(f"step anatomy (wall {anat['wall_s'] * 1e3:.1f} ms, "
                     f"device {rep['device_kind'] or '?'})")
        for comp in ("compute", "collective", "data", "host"):
            frac = anat["fractions"][comp]
            lines.append(f"  {comp:<10} {_bar(frac)} {frac * 100:5.1f}%")
        top = sorted(anat["programs"].items(),
                     key=lambda kv: -(kv[1]["modeled_s"] or 0.0))[:8]
        if top:
            lines.append("  top programs by modeled time:")
            for name, p in top:
                bound = p["bound"] or "?"
                ratio = (f"{p['ratio']:.2f}x balance"
                         if p["ratio"] is not None else "n/a")
                ms = (p["modeled_s"] or 0.0) * 1e3
                lines.append(f"    {name:<28} {ms:8.2f} ms  {bound:<7} "
                             f"({ratio})")

    if rep["probe_tiers"]:
        lines.append("")
        lines.append("probe tiers (newest round)")
        for tier, data in rep["probe_tiers"].items():
            err = data.get("error")
            note = f"FAILED: {err[:60]}" if err else \
                "  ".join(f"{k}={v}" for k, v in sorted(data.items())
                          if k not in ("tier", "sec") and
                          isinstance(v, (int, float)))
            lines.append(f"  {tier:<12} {note[:58]}")
    for fail in rep["probe_failed"]:
        lines.append(f"  !! newest probe window failed: "
                     f"{fail.get('error', '?')[:50]}")

    if rep["serve"]:
        pairs = "  ".join(f"{k}={v}" for k, v in sorted(
            rep["serve"].items()))
        lines.append("")
        lines.append(f"serving     {pairs}")

    if rep["decisions"]:
        lines.append("")
        lines.append("resolver decisions in effect (--config)")
        for dk, entry in rep["decisions"].items():
            lines.append(f"  {dk}  [window: {entry['window']}]")
            for name, d in entry["flags"].items():
                stale = "  STALE" if d["stale"] else ""
                lines.append(f"    {name:<20} = {d['value']!r:<8} "
                             f"({d['evidence']} evidence row(s)){stale}")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ledger", default=None, metavar="FILE",
                    help="evidence ledger JSONL (none: only the joined "
                         "--runlog/--aot-stats files are reported)")
    ap.add_argument("--config", default=None, metavar="FILE",
                    help="perf config whose decisions to list")
    ap.add_argument("--runlog", action="append", default=[],
                    metavar="FILE", help="join a runlog JSONL (repeatable)")
    ap.add_argument("--aot-stats", action="append", default=[],
                    metavar="FILE",
                    help="join a PADDLE_AOT_STATS file (repeatable)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    rows, quarantined = (evidence.read_rows(args.ledger)
                         if args.ledger else ([], []))
    runlog_rows = []
    for path in args.runlog:
        runlog_rows.extend(evidence.ingest_runlog(path))
    aot_rows = []
    for path in args.aot_stats:
        aot_rows.extend(evidence.ingest_aot_stats(path))
    config = None
    if args.config:
        with open(args.config) as f:
            config = json.load(f)

    rep = build_report(rows, quarantined, config, runlog_rows, aot_rows)
    if args.as_json:
        print(json.dumps(rep, indent=1, sort_keys=True))
    else:
        sys.stdout.write(render(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
