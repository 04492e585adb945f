"""Framework lint driver: all five analysis passes over the repo, CI-gated.

    python tools/lint.py                  # lint the shipped tree (exit 0)
    python tools/lint.py path/to/file.py  # lint specific files/dirs
    python tools/lint.py --fix-hints      # per-rule remediation table
    python tools/lint.py --layout-report out.json   # dump per-op report
    python tools/lint.py --update-baseline

Pass 1 (AST, stdlib-only, fast): every rule in paddle_tpu.analysis.rules
— the TPU, SHD1xx, CCY and WIR families — over paddle_tpu/, tools/,
examples/, tests/ and chip_smoke.py. Pass 2 (trace, imports JAX; skip with
--no-trace): trace-sanitizes a representative train-step function built
from the framework's own layers, and — when --schedules <dir> points at
logs captured via PADDLE_SCHEDULE_LOG — checks the recorded per-rank
collective schedules for divergence. Pass 3 (shard, imports JAX; skip
with --no-shard): abstractly evaluates a representative sharded step
over a dp×mp mesh with paddle_tpu.analysis.shardcheck — divisibility +
implicit-reshard findings (SHD2xx) plus a per-op layout report whose
stable subset is diffed against tools/layout_baseline.json (SHD210 on
drift). Pass 4 (concur, stdlib-only; skip with --no-concur): the
serving concurrency gate — the CCY1xx/2xx AST rules ride pass 1, and
paddle_tpu.analysis.concurcheck additionally proves the lock-order /
request-lifecycle registries are coherent and byte-identical to what
the runtime ordered-lock twin (PADDLE_LOCKCHECK=1) enforces (CCY5xx).
Pass 5 (wire, stdlib-only; skip with --no-wire): the wire-contract
gate — the WIR1xx AST rules ride pass 1, and
paddle_tpu.analysis.wirecheck additionally proves serving/wire.py's
WIRE_SCHEMAS registry coherent, version-hash-pinned, and
byte-identical to what the runtime sealing twin (PADDLE_WIRECHECK=1)
enforces (WIR5xx). All of it runs on CPU with no devices: the mesh is
abstract.

Findings are diffed against the committed baselines — CCY findings
against tools/concur_baseline.json, WIR findings against
tools/wire_baseline.json, everything else against
tools/lint_baseline.json (all shipped EMPTY: the tree self-hosts
clean); any finding not in its baseline prints with its rule id and fix
hint and the driver exits nonzero. tests/test_analysis.py,
tests/test_shardcheck.py, tests/test_concurcheck.py and
tests/test_wirecheck.py run the same gates as tier-1 tests.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _bootstrap_analysis_pkg():
    """Make `import paddle_tpu.analysis` work WITHOUT executing the full
    paddle_tpu/__init__.py (which imports JAX and the whole framework):
    register a bare parent package whose __path__ points at the source
    tree. When paddle_tpu is already imported (in-process test use) this
    is a no-op."""
    import types
    if "paddle_tpu" not in sys.modules:
        pkg = types.ModuleType("paddle_tpu")
        pkg.__path__ = [os.path.join(REPO, "paddle_tpu")]
        sys.modules["paddle_tpu"] = pkg

DEFAULT_PATHS = ["paddle_tpu", "tools", "examples", "tests",
                 "chip_smoke.py"]
BASELINE = os.path.join(REPO, "tools", "lint_baseline.json")
CONCUR_BASELINE = os.path.join(REPO, "tools", "concur_baseline.json")
WIRE_BASELINE = os.path.join(REPO, "tools", "wire_baseline.json")
LAYOUT_BASELINE = os.path.join(REPO, "tools", "layout_baseline.json")


def _load_baseline(path):
    try:
        with open(path) as f:
            return set(json.load(f))
    except (FileNotFoundError, json.JSONDecodeError):
        return set()


def _print_fix_hints():
    from paddle_tpu.analysis.rules import rule_table
    print("AST rules (suppress per line with  # tpu-lint: disable=<ID>):\n")
    for rid, name, sev, desc, hint in rule_table():
        print(f"  {rid} {name} [{sev}]")
        print(f"      what: {desc}")
        print(f"      fix:  {hint}\n")
    from paddle_tpu.analysis.shardcheck import SHARD_RULES  # stdlib-only
    print("Layout-evaluator rules (reported by shardcheck.layout_check):\n")
    for rid, (name, hint) in sorted(SHARD_RULES.items()):
        print(f"  {rid} {name}")
        print(f"      fix:  {hint}\n")
    from paddle_tpu.analysis.concurcheck import CONCUR_RULES  # stdlib-only
    print("Concurrency-registry rules (reported by "
          "concurcheck.concur_check):\n")
    for rid, (name, hint) in sorted(CONCUR_RULES.items()):
        print(f"  {rid} {name}")
        print(f"      fix:  {hint}\n")
    from paddle_tpu.analysis.wirecheck import WIRE_RULES  # stdlib-only
    print("Wire-registry rules (reported by wirecheck.wire_check):\n")
    for rid, (name, hint) in sorted(WIRE_RULES.items()):
        print(f"  {rid} {name}")
        print(f"      fix:  {hint}\n")
    # trace rules live beside the trace pass; import lazily (needs jax)
    try:
        from paddle_tpu.analysis.tracecheck import TRACE_RULES
    except Exception:
        print("(trace-rule table unavailable: jax not importable)")
        return
    print("Trace-sanitizer rules (reported by trace_check / "
          "check_collective_schedules):\n")
    for rid, (name, hint) in sorted(TRACE_RULES.items()):
        print(f"  {rid} {name}")
        print(f"      fix:  {hint}\n")


def _perf_config_check(config_path, ledger_path):
    """Provenance gate for a perf config (stdlib-only): every decision
    in it must cite evidence-row ids that exist in the ledger it was
    resolved from (PRF501), and every flag it names
    must exist in the statically-scanned define_flag registry (PRF502);
    an unreadable config or ledger is itself a finding (PRF503). This
    is what keeps a flag flip reviewable: the diff always carries the
    measurement rows that justify it."""
    from paddle_tpu.analysis.rules import Finding, load_flag_registry
    from paddle_tpu.profiler import evidence

    findings = []

    def bad(rule, msg, hint):
        findings.append(Finding(rule, config_path, 0, 0, msg, hint))

    try:
        with open(config_path) as f:
            config = json.load(f)
    except (OSError, ValueError) as e:
        bad("PRF503", f"perf config unreadable: {e}",
            "regenerate with tools/perf_resolve.py --build")
        return findings
    rows, quarantined = evidence.read_rows(ledger_path)
    if not rows:
        bad("PRF503", f"evidence ledger {os.path.basename(ledger_path)} "
            "is empty or unreadable",
            "rebuild it with tools/perf_resolve.py --build")
        return findings
    ids = {r["id"] for r in rows}
    flags = load_flag_registry()
    for dk, entry in sorted((config.get("devices") or {}).items()):
        sections = [("flags", entry.get("flags") or {}),
                    ("policies", entry.get("policies") or {}),
                    ("kernel_blocks", entry.get("kernel_blocks") or {}),
                    ("window", {"window": entry.get("window") or {}})]
        for section, decisions in sections:
            for name, d in sorted(decisions.items()):
                if not isinstance(d, dict):
                    continue
                cited = d.get("evidence") or []
                if section in ("flags", "policies", "kernel_blocks") \
                        and not cited:
                    bad("PRF501",
                        f"decision {dk}/{section}/{name} cites no "
                        "evidence rows",
                        "every decision must carry provenance; re-run "
                        "tools/perf_resolve.py")
                for rid in cited:
                    if rid not in ids:
                        bad("PRF501",
                            f"decision {dk}/{section}/{name} cites "
                            f"evidence id {rid!r} absent from the ledger",
                            "config and ledger are out of sync; re-run "
                            "tools/perf_resolve.py --build")
                if section == "flags" and name not in flags:
                    bad("PRF502",
                        f"decision names unknown flag {name!r} for {dk}",
                        "flags must exist as a define_flag call in the "
                        "package (see analysis.load_flag_registry)")
    return findings


def _mem_self_check():
    """What-fits planner gate (stdlib, rides the AST pass): the
    committed fixture (tools/mem_plan_baseline.json) must reproduce
    tools/mem_report.py plan() output exactly — capacity predictions
    the sharding auto-planner and serving pre-checks consume must not
    drift silently (MEM501)."""
    from paddle_tpu.analysis.rules import Finding

    tools_dir = os.path.join(REPO, "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import mem_report
    return [
        Finding("MEM501", mem_report.FIXTURE, 0, 0,
                f"what-fits planner drifted from the committed fixture: "
                f"{msg}",
                "review the change, then tools/mem_report.py "
                "--update-fixture")
        for msg in mem_report.self_check()]


def _trace_self_check():
    """Trace-sanitize a representative step function built from the
    framework's own layers — proves the dynamic pass runs on the shipped
    tree without findings (the examples' training loops are eager; this
    is their jitted equivalent)."""
    import numpy as np

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu.analysis.tracecheck import trace_check
    import jax.numpy as jnp

    def sgd_step(w, b, x, y, lr):
        pred = jnp.maximum(x @ w + b, 0.0)
        err = pred - y
        loss = (err * err).mean()
        gw = x.T @ (2.0 * (jnp.where(x @ w + b > 0, 1.0, 0.0) * err)) \
            / x.shape[0]
        gb = (2.0 * err).mean()
        return w - lr * gw, b - lr * gb, loss

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32))
    b = jnp.zeros((4,), jnp.float32)
    x = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    y = jnp.zeros((16, 4), jnp.float32)
    return trace_check(sgd_step, (w, b, x, y, 0.1),
                       label="tools/lint.py::sgd_step self-check")


def _shard_self_check(compare_baseline: bool):
    """Abstract-layout-evaluate a representative sharded step over a
    dp×mp mesh (no devices — CPU-safe): proves the SHD2xx pass runs
    clean on the shipped tree and yields the layout report whose stable
    subset is pinned by tools/layout_baseline.json.

    Returns (findings, report)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax.numpy as jnp
    from paddle_tpu.analysis.shardcheck import baseline_view, layout_check

    def step(w, b, x, y):
        # Megatron-ish layout: batch over dp, features/heads over mp.
        pred = jnp.maximum(x @ w + b, 0.0)
        err = pred - y
        return (err * err).mean()

    args = [((8, 4), "float32"), ((4,), "float32"),
            ((16, 8), "float32"), ((16, 4), "float32")]
    in_specs = [(None, "mp"), ("mp",), ("dp", None), ("dp", "mp")]
    findings, report = layout_check(
        step, args, in_specs, {"dp": 2, "mp": 2}, out_specs=[()],
        label="tools/lint.py::sharded_step self-check")
    if compare_baseline:
        from paddle_tpu.analysis.rules import Finding
        from paddle_tpu.analysis.shardcheck import SHARD_RULES
        try:
            with open(LAYOUT_BASELINE) as f:
                want = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            want = None
        got = baseline_view(report)
        if got != want:
            findings.append(Finding(
                "SHD210", LAYOUT_BASELINE, 0, 0,
                "layout report for the representative step drifted from "
                "the committed baseline",
                SHARD_RULES["SHD210"][1], "error"))
    return findings, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=None,
                    help=f"files/dirs to lint (default: {DEFAULT_PATHS})")
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--update-baseline", action="store_true",
                    help="write current findings as the new baseline")
    ap.add_argument("--fix-hints", action="store_true",
                    help="print the per-rule remediation table and exit")
    ap.add_argument("--no-trace", action="store_true",
                    help="skip the trace-sanitizer pass (no jax import)")
    ap.add_argument("--no-shard", action="store_true",
                    help="skip the abstract-layout (shardcheck) pass")
    ap.add_argument("--shard", action="store_true",
                    help="run the shardcheck pass (the default; kept as "
                         "an explicit spelling for CI scripts)")
    ap.add_argument("--no-concur", action="store_true",
                    help="skip the serving-concurrency pass (drop CCY "
                         "findings and the registry-coherence check)")
    ap.add_argument("--concur", action="store_true",
                    help="run the concurrency pass (the default; kept as "
                         "an explicit spelling for CI scripts)")
    ap.add_argument("--concur-baseline", default=CONCUR_BASELINE)
    ap.add_argument("--no-wire", action="store_true",
                    help="skip the wire-contract pass (drop WIR "
                         "findings and the registry-coherence check)")
    ap.add_argument("--wire", action="store_true",
                    help="run the wire pass (the default; kept as an "
                         "explicit spelling for CI scripts)")
    ap.add_argument("--wire-baseline", default=WIRE_BASELINE)
    ap.add_argument("--layout-report", default=None, metavar="FILE",
                    help="dump the per-op layout report JSON to FILE")
    ap.add_argument("--schedules", default=None, metavar="DIR",
                    help="check per-rank collective logs recorded via "
                         "PADDLE_SCHEDULE_LOG=DIR")
    ap.add_argument("--perf-config", default=None, metavar="FILE",
                    help="also provenance-check this perf config "
                         "against --perf-ledger")
    ap.add_argument("--perf-ledger", default=None, metavar="FILE",
                    help="evidence ledger the --perf-config must cite")
    ap.add_argument("--no-mem-check", action="store_true",
                    help="skip the mem_report what-fits fixture check")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable findings on stdout")
    args = ap.parse_args(argv)

    _bootstrap_analysis_pkg()
    if args.fix_hints:
        _print_fix_hints()
        return 0

    t0 = time.perf_counter()
    from paddle_tpu.analysis import lint_paths

    paths = [os.path.join(REPO, p) if not os.path.exists(p) else p
             for p in (args.paths or DEFAULT_PATHS)]
    findings = lint_paths(paths)
    if args.no_concur:
        findings = [f for f in findings if not f.rule.startswith("CCY")]
    if args.no_wire:
        findings = [f for f in findings if not f.rule.startswith("WIR")]
    n_ast = len(findings)

    # serving-concurrency registry coherence (stdlib, rides the AST
    # pass): the CCY1xx/2xx rules above already ran as part of
    # lint_paths; this adds the CCY5xx static/runtime coherence check
    if not args.no_concur:
        from paddle_tpu.analysis.concurcheck import concur_check
        findings.extend(concur_check())

    # wire-contract registry coherence (stdlib, rides the AST pass):
    # the WIR1xx rules above already ran as part of lint_paths; this
    # adds the WIR5xx registry/version-hash/runtime-twin self-check
    if not args.no_wire:
        from paddle_tpu.analysis.wirecheck import wire_check
        findings.extend(wire_check())

    # perf-config provenance (stdlib, rides the AST pass); the repo
    # commits no config, so this runs only when the caller names one
    if args.perf_config:
        if not args.perf_ledger:
            ap.error("--perf-config needs --perf-ledger")
        findings.extend(_perf_config_check(args.perf_config,
                                           args.perf_ledger))

    # what-fits planner self-check (stdlib, fast): committed fixture
    # must match tools/mem_report.py plan() byte-for-byte
    if not args.no_mem_check:
        findings.extend(_mem_self_check())

    if not args.no_trace:
        findings.extend(_trace_self_check())
    layout_report = None
    if not args.no_shard:
        shard_findings, layout_report = _shard_self_check(
            compare_baseline=not args.update_baseline)
        findings.extend(shard_findings)
    if args.layout_report:
        if layout_report is None:
            print("--layout-report requires the shard pass "
                  "(drop --no-shard)", file=sys.stderr)
            return 2
        with open(args.layout_report, "w") as f:
            json.dump(layout_report, f, indent=1)
        print(f"wrote layout report to {args.layout_report}")
    if args.schedules:  # needs jax only for the Finding type's module
        from paddle_tpu.analysis.schedule import load_schedules
        from paddle_tpu.analysis.tracecheck import \
            check_collective_schedules
        findings.extend(
            check_collective_schedules(load_schedules(args.schedules)))

    # CCY and WIR findings diff against their own baselines so adopting
    # (or retiring) the concurrency/wire gates never rewrites the
    # long-lived three-pass baseline file
    baseline = _load_baseline(args.baseline)
    concur_baseline = _load_baseline(args.concur_baseline)
    wire_baseline = _load_baseline(args.wire_baseline)

    def _known(f):
        if f.rule.startswith("CCY"):
            pool = concur_baseline
        elif f.rule.startswith("WIR"):
            pool = wire_baseline
        else:
            pool = baseline
        return f.key() in pool

    fresh = [f for f in findings if not _known(f)]

    if args.update_baseline:
        ccy_keys = sorted(f2.key() for f2 in findings
                          if f2.rule.startswith("CCY"))
        wir_keys = sorted(f2.key() for f2 in findings
                          if f2.rule.startswith("WIR"))
        rest_keys = sorted(f2.key() for f2 in findings
                           if not f2.rule.startswith(("CCY", "WIR")))
        with open(args.baseline, "w") as f:
            json.dump(rest_keys, f, indent=1)
        print(f"wrote {len(rest_keys)} finding keys to {args.baseline}")
        if not args.no_concur:
            with open(args.concur_baseline, "w") as f:
                json.dump(ccy_keys, f, indent=1)
            print(f"wrote {len(ccy_keys)} finding keys to "
                  f"{args.concur_baseline}")
        if not args.no_wire:
            with open(args.wire_baseline, "w") as f:
                json.dump(wir_keys, f, indent=1)
            print(f"wrote {len(wir_keys)} finding keys to "
                  f"{args.wire_baseline}")
        if layout_report is not None:
            from paddle_tpu.analysis.shardcheck import baseline_view
            with open(LAYOUT_BASELINE, "w") as f:
                json.dump(baseline_view(layout_report), f, indent=1)
            print(f"wrote layout baseline to {LAYOUT_BASELINE}")
        return 0

    if args.as_json:
        print(json.dumps([vars(f) for f in fresh], indent=1))
    else:
        for f in fresh:
            rel = os.path.relpath(f.path, REPO) if os.path.isabs(f.path) \
                else f.path
            print(f"{rel}:{f.line}: {f.rule} [{f.severity}] {f.message}")
            if f.hint:
                print(f"    fix: {f.hint}")
        dt = time.perf_counter() - t0
        known = len(findings) - len(fresh)
        print(f"\nlint: {n_ast} ast + {len(findings) - n_ast} "
              f"trace/shard/concur/wire finding(s), {known} baselined, "
              f"{len(fresh)} new ({dt:.1f}s)")
    errors = [f for f in fresh if f.severity == "error"]
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
