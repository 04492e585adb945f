"""Lint-rule registry: the framework's machine-checkable invariants.

Each rule is a small AST checker with a stable id, a severity, a one-line
description, and a fix hint the CI driver prints next to every finding.
The registry is data the rest of the subsystem consumes: ``astlint`` runs
the checkers, ``tools/lint.py --fix-hints`` prints the remediation table,
and the test suite asserts every rule fires on its fixture snippet.

Rules read their ground truth statically from the modules that own it —
the chaos probe-site registry from ``resilience/chaos.py`` (``SITES``) and
the metric-name catalog from ``profiler/instrument.py`` (``CATALOG``) are
parsed out of the source with ``ast.literal_eval``, so linting never
imports the framework (or JAX): ``tools/lint.py`` stays fast and can lint
a broken tree.

Suppression: append ``# tpu-lint: disable=TPU101`` (comma-separate for
several ids) to the offending line. Suppressions are *checked*: an unknown
rule id in a disable comment is itself a finding (TPU000).
"""
from __future__ import annotations

import ast
import functools
import os
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Tuple

__all__ = ["Finding", "Rule", "RULES", "rule_table", "get_rule",
           "load_metric_catalog", "load_chaos_sites",
           "load_flag_registry"]

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class Finding:
    """One lint finding, stable enough to diff against a baseline."""
    rule: str
    path: str
    line: int
    col: int
    message: str
    hint: str = ""
    severity: str = "error"  # error | warning

    def key(self) -> str:
        """Baseline identity: rule + file + message (line numbers drift
        with unrelated edits, so they are not part of the key). The file
        part keeps the last two path components so same-named files
        (every __init__.py) do not collide in the baseline."""
        tail = "/".join(self.path.replace(os.sep, "/").split("/")[-2:])
        return f"{self.rule}|{tail}|{self.message}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.severity}] {self.message}")


@dataclass
class Rule:
    id: str
    name: str
    description: str
    hint: str
    check: Callable  # check(ctx) -> Iterable[Finding]
    severity: str = "error"
    framework_only: bool = False      # skip for user scripts outside the pkg
    exempt_suffixes: Tuple[str, ...] = ()  # path suffixes the rule skips


RULES: Dict[str, Rule] = {}


def _register(id, name, description, hint, severity="error",
              framework_only=False, exempt_suffixes=()):
    def deco(fn):
        RULES[id] = Rule(id, name, description, hint, fn, severity,
                         framework_only, tuple(exempt_suffixes))
        return fn
    return deco


def get_rule(rule_id: str) -> Optional[Rule]:
    return RULES.get(rule_id)


def rule_table() -> List[Tuple[str, str, str, str, str]]:
    """(id, name, severity, description, hint) rows, id-sorted."""
    return [(r.id, r.name, r.severity, r.description, r.hint)
            for r in sorted(RULES.values(), key=lambda r: r.id)]


# -- static ground-truth readers ----------------------------------------------
def _literal_from_source(path: str, target: str):
    """ast.literal_eval of a top-level ``target = <literal>`` assignment."""
    with open(path, "r") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in tree.body:
        if isinstance(node, ast.Assign):
            names = [t.id for t in node.targets if isinstance(t, ast.Name)]
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target,
                                                            ast.Name):
            names = [node.target.id]
        else:
            continue
        if target in names and node.value is not None:
            return ast.literal_eval(node.value)
    raise LookupError(f"no literal assignment {target!r} in {path}")


@functools.lru_cache(maxsize=1)
def load_metric_catalog() -> frozenset:
    """The built-in metric names, read statically from
    profiler/instrument.py's CATALOG tuple."""
    path = os.path.join(_PKG_ROOT, "profiler", "instrument.py")
    return frozenset(_literal_from_source(path, "CATALOG"))


@functools.lru_cache(maxsize=1)
def load_flag_registry() -> frozenset:
    """Every flag name the package defines, read statically from
    ``define_flag("<name>", ...)`` call sites across paddle_tpu/*.py.
    Static on purpose: kernel modules register their flags on first
    import, so a runtime ``flags._FLAGS`` snapshot taken under the
    jax-free bootstrap would miss them — and the perf-config provenance
    check (tools/lint.py --perf-config) must see the full registry."""
    names = set()
    for dirpath, dirnames, filenames in os.walk(_PKG_ROOT):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                with open(path, "r") as f:
                    tree = ast.parse(f.read())
            except (OSError, SyntaxError):
                continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                callee = func.attr if isinstance(func, ast.Attribute) \
                    else getattr(func, "id", None)
                if callee == "define_flag" and node.args and \
                        isinstance(node.args[0], ast.Constant) and \
                        isinstance(node.args[0].value, str):
                    names.add(node.args[0].value)
    return frozenset(names)


@functools.lru_cache(maxsize=1)
def _chaos_sites_cached() -> Tuple[Tuple[str, str], ...]:
    path = os.path.join(_PKG_ROOT, "resilience", "chaos.py")
    return tuple(sorted(_literal_from_source(path, "SITES").items()))


def load_chaos_sites() -> Dict[str, str]:
    """{site name: probe kind}, read statically from
    resilience/chaos.py's SITES registry."""
    return dict(_chaos_sites_cached())


# -- per-file context shared by all checkers ----------------------------------
class FileContext:
    """Parsed file + the name-resolution maps the checkers share.

    ``dotted(node)`` resolves an ast.Name/Attribute chain to a fully
    qualified dotted path using the file's imports, e.g. with
    ``from jax import lax`` the expression ``lax.axis_size`` resolves to
    ``jax.lax.axis_size``; with ``from ..utils.jax_compat import shard_map``
    the name ``shard_map`` resolves to ``<...>.jax_compat.shard_map`` —
    blessed, because it reaches the shim.
    """

    def __init__(self, path: str, source: str, tree: ast.Module,
                 is_framework: bool):
        self.path = path
        self.source = source
        self.tree = tree
        self.is_framework = is_framework
        self.imports: Dict[str, str] = {}
        # one full walk, shared by every rule (the dominant lint cost)
        self._nodes: List[ast.AST] = list(ast.walk(tree))
        self._collect_imports()
        self._functions: Optional[List[ast.AST]] = None
        self._probe_map: Optional[Dict] = None
        self._det_regions: Optional[List] = None

    def nodes(self) -> List[ast.AST]:
        return self._nodes

    def _collect_imports(self):
        for node in self._nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.imports[a.asname or a.name.split(".")[0]] = \
                        a.name if a.asname else a.name.split(".")[0]
                    if a.asname:
                        self.imports[a.asname] = a.name
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if node.level:  # relative import: keep the module tail
                    mod = ("." * node.level) + mod
                for a in node.names:
                    self.imports[a.asname or a.name] = \
                        f"{mod}.{a.name}" if mod else a.name

    def dotted(self, node) -> Optional[str]:
        """Fully qualified dotted name for a Name/Attribute chain."""
        parts = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        head = self.imports.get(node.id, node.id)
        parts.append(head)
        return ".".join(reversed(parts))

    def functions(self) -> List[ast.AST]:
        if self._functions is None:
            self._functions = [n for n in self._nodes
                               if isinstance(n, (ast.FunctionDef,
                                                 ast.AsyncFunctionDef))]
        return self._functions


def _finding(rule: Rule, ctx: FileContext, node, message: str) -> Finding:
    return Finding(rule.id, ctx.path, getattr(node, "lineno", 0),
                   getattr(node, "col_offset", 0), message, rule.hint,
                   rule.severity)


def _calls_in(node) -> Iterable[ast.Call]:
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            yield n


def _own_body_walk(fn) -> Iterable[ast.AST]:
    """Walk a function's body WITHOUT descending into nested function
    definitions (each nested def is its own region for region rules)."""
    stack = list(fn.body)
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stack.extend(ast.iter_child_nodes(n))


# =============================================================================
# TPU1xx — one spelling of the linted JAX entry points (utils/jax_compat.py)
# =============================================================================
_RAW_SHARD_MAP = {"jax.shard_map", "jax.experimental.shard_map.shard_map"}
_RAW_AXIS_SIZE = {"jax.lax.axis_size", "lax.axis_size"}
_RAW_COMPILER_PARAMS_TAILS = ("pallas.tpu.CompilerParams",
                              "pallas.tpu.TPUCompilerParams")


def _is_compat(name: Optional[str]) -> bool:
    return bool(name) and ".jax_compat." in f".{name}"


@_register(
    "TPU101", "raw-shard-map",
    "raw jax.shard_map / jax.experimental.shard_map call site outside "
    "utils/jax_compat.py",
    "import shard_map from paddle_tpu.utils.jax_compat — it checks the "
    "specs' mesh axes against the mesh first (shardcheck's runtime twin), "
    "which a raw call site skips",
    exempt_suffixes=("utils/jax_compat.py",))
def _check_raw_shard_map(ctx: FileContext):
    rule = RULES["TPU101"]
    for node in ctx.nodes():
        if isinstance(node, (ast.Attribute, ast.Name)):
            d = ctx.dotted(node)
            if d in _RAW_SHARD_MAP and not _is_compat(d):
                yield _finding(rule, ctx, node,
                               f"raw shard_map reference ({d})")
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == "jax.experimental.shard_map" or (
                    mod == "jax" and any(a.name == "shard_map"
                                         for a in node.names)):
                yield _finding(rule, ctx, node,
                               f"raw shard_map import (from {mod})")


@_register(
    "TPU102", "raw-axis-size",
    "raw jax.lax.axis_size call site outside utils/jax_compat.py",
    "import axis_size from paddle_tpu.utils.jax_compat — collectives "
    "code keeps one import home for the JAX names it depends on",
    exempt_suffixes=("utils/jax_compat.py",))
def _check_raw_axis_size(ctx: FileContext):
    rule = RULES["TPU102"]
    for node in ctx.nodes():
        if isinstance(node, ast.Attribute):
            d = ctx.dotted(node)
            if d in _RAW_AXIS_SIZE and not _is_compat(d):
                yield _finding(rule, ctx, node,
                               f"raw axis_size reference ({d})")
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "") == "jax.lax" and any(
                    a.name == "axis_size" for a in node.names):
                yield _finding(rule, ctx, node,
                               "raw axis_size import (from jax.lax)")


@_register(
    "TPU103", "raw-compiler-params",
    "Pallas CompilerParams/TPUCompilerParams constructed outside "
    "utils/jax_compat.py",
    "call paddle_tpu.utils.jax_compat.tpu_compiler_params(**kw) — the "
    "class has been renamed once already; kernels keep one place to "
    "follow it",
    exempt_suffixes=("utils/jax_compat.py",))
def _check_raw_compiler_params(ctx: FileContext):
    rule = RULES["TPU103"]
    for node in ctx.nodes():
        if not isinstance(node, ast.Call):
            continue
        d = ctx.dotted(node.func)
        if not d or _is_compat(d):
            continue
        if d.endswith(_RAW_COMPILER_PARAMS_TAILS) or \
                d.endswith(("pltpu.CompilerParams",
                            "pltpu.TPUCompilerParams")):
            yield _finding(rule, ctx, node,
                           f"raw Pallas compiler-params construction ({d})")


# =============================================================================
# TPU2xx — determinism at chaos-probe sites / traced regions
# =============================================================================
_PROBE_FNS = {"site", "mangle", "poison"}
_WALLCLOCK = {"time.time", "time.time_ns", "datetime.now",
              "datetime.datetime.now", "datetime.utcnow",
              "datetime.datetime.utcnow"}
_JIT_DECORATORS = {"jax.jit", "jit", "jax.pjit", "pjit", "to_static",
                   "jit.to_static", "paddle.jit.to_static",
                   "functools.partial(jax.jit"}


def _probe_calls_uncached(ctx: FileContext, fn) -> List[ast.Call]:
    """chaos probe calls (site/mangle/poison on a chaos-ish module, or the
    bare names imported from resilience.chaos) in fn's OWN body."""
    out = []
    for n in _own_body_walk(fn):
        for c in (x for x in [n] if isinstance(x, ast.Call)):
            d = ctx.dotted(c.func)
            if not d:
                continue
            head, _, tail = d.rpartition(".")
            if tail in _PROBE_FNS and ("chaos" in head or
                                       head.endswith("_chaos")):
                out.append(c)
            elif not head and d in _PROBE_FNS and \
                    "chaos" in ctx.imports.get(d, ""):
                out.append(c)
    return out


def _probe_map(ctx: FileContext) -> Dict:
    """{function node: [probe Call nodes]} — computed once per file;
    cheap pre-filter: files never naming 'chaos' have no probes."""
    if ctx._probe_map is None:
        if "chaos" not in ctx.source:
            ctx._probe_map = {}
        else:
            ctx._probe_map = {
                fn: calls for fn in ctx.functions()
                if (calls := _probe_calls_uncached(ctx, fn))}
    return ctx._probe_map


def _probe_calls(ctx: FileContext, fn) -> List[ast.Call]:
    return _probe_map(ctx).get(fn, [])


def _is_jitted(ctx: FileContext, fn) -> bool:
    for dec in getattr(fn, "decorator_list", ()):
        target = dec.func if isinstance(dec, ast.Call) else dec
        d = ctx.dotted(target)
        if d and (d in _JIT_DECORATORS or d.endswith(".jit") or
                  d.endswith("to_static")):
            return True
    return False


def _region_label(ctx, fn):
    return ("jit-traced" if _is_jitted(ctx, fn) else "chaos-probed")


def _deterministic_regions(ctx: FileContext):
    if ctx._det_regions is None:
        probed = _probe_map(ctx)
        ctx._det_regions = [fn for fn in ctx.functions()
                            if fn in probed or _is_jitted(ctx, fn)]
    return ctx._det_regions


@_register(
    "TPU201", "wallclock-at-probe-site",
    "non-monotonic wall-clock read (time.time / datetime.now) inside a "
    "chaos-probed or jit-traced region",
    "use time.monotonic()/time.perf_counter() for deadlines and "
    "durations — wall clocks jump (NTP, suspend) and break the seeded "
    "chaos replay contract; inside jit the read executes once at trace "
    "time and bakes a stale constant",
    framework_only=True, exempt_suffixes=("resilience/chaos.py",))
def _check_wallclock(ctx: FileContext):
    rule = RULES["TPU201"]
    for fn in _deterministic_regions(ctx):
        for n in _own_body_walk(fn):
            if isinstance(n, ast.Call):
                d = ctx.dotted(n.func)
                if d in _WALLCLOCK:
                    yield _finding(
                        rule, ctx, n,
                        f"{d}() in {_region_label(ctx, fn)} function "
                        f"'{fn.name}'")


@_register(
    "TPU202", "unseeded-random-at-probe-site",
    "global (unseeded) random.* call inside a chaos-probed or jit-traced "
    "region",
    "use a seeded random.Random(seed) instance (the chaos FaultPlan "
    "carries one: plan.rng()) so the same seed replays the same run; "
    "inside jit use jax.random with an explicit key",
    framework_only=True, exempt_suffixes=("resilience/chaos.py",))
def _check_unseeded_random(ctx: FileContext):
    rule = RULES["TPU202"]
    for fn in _deterministic_regions(ctx):
        for n in _own_body_walk(fn):
            if isinstance(n, ast.Call):
                d = ctx.dotted(n.func)
                if d and d.startswith("random.") and d != "random.Random":
                    yield _finding(
                        rule, ctx, n,
                        f"{d}() in {_region_label(ctx, fn)} function "
                        f"'{fn.name}'")


@_register(
    "TPU203", "unknown-chaos-site",
    "chaos probe called with a site name absent from resilience.chaos.SITES "
    "(or with the wrong probe function for that site)",
    "add the site to the SITES registry in resilience/chaos.py (one source "
    "of truth: linter, install_plan validation, and docs all read it)",
    framework_only=True, exempt_suffixes=("resilience/chaos.py",))
def _check_chaos_sites(ctx: FileContext):
    rule = RULES["TPU203"]
    try:
        sites = load_chaos_sites()
    except (OSError, LookupError):
        return
    for fn, calls in _probe_map(ctx).items():
        for call in calls:
            if not call.args or not isinstance(call.args[0], ast.Constant) \
                    or not isinstance(call.args[0].value, str):
                continue  # dynamic site names pass through (store._run)
            name = call.args[0].value
            probe = ctx.dotted(call.func).rpartition(".")[2]
            if name not in sites:
                yield _finding(rule, ctx, call,
                               f"probe site {name!r} not in chaos.SITES")
            elif sites[name] != probe:
                yield _finding(
                    rule, ctx, call,
                    f"site {name!r} is registered for probe "
                    f"'{sites[name]}' but called via '{probe}'")


# =============================================================================
# TPU3xx — observability-plane invariants
# =============================================================================
_METRIC_METHODS = {"counter", "gauge", "histogram"}


@_register(
    "TPU301", "uncataloged-metric",
    "metric family created with a literal name absent from "
    "profiler/instrument.py's CATALOG",
    "add the family name to instrument.CATALOG (and the module docstring "
    "table) — the catalog is the stable, greppable metric API dashboards "
    "depend on",
    framework_only=True,
    exempt_suffixes=("profiler/metrics.py",))
def _check_metric_catalog(ctx: FileContext):
    rule = RULES["TPU301"]
    try:
        catalog = load_metric_catalog()
    except (OSError, LookupError):
        return
    import fnmatch as _fn
    for node in ctx.nodes():
        if not (isinstance(node, ast.Call) and
                isinstance(node.func, ast.Attribute) and
                node.func.attr in _METRIC_METHODS and node.args):
            continue
        first = node.args[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            name = first.value
            if name not in catalog:
                yield _finding(rule, ctx, node,
                               f"metric {name!r} not in instrument.CATALOG")
        elif isinstance(first, ast.JoinedStr):
            # f-string name: wildcard the formatted fields and require the
            # pattern to cover at least one cataloged family
            pat = "".join(
                v.value if isinstance(v, ast.Constant) else "*"
                for v in first.values)
            if not any(_fn.fnmatchcase(c, pat) for c in catalog):
                yield _finding(
                    rule, ctx, node,
                    f"metric f-string pattern {pat!r} matches nothing in "
                    "instrument.CATALOG")


# =============================================================================
# TPU4xx — exception hygiene around checkpoint integrity
# =============================================================================
_CKPT_LOADS = {"load_state_dict", "load_latest"}
_BROAD = {"Exception", "BaseException", "ValueError"}


def _handler_reraises(handler: ast.ExceptHandler) -> bool:
    for n in ast.walk(handler):
        if isinstance(n, ast.Raise):
            return True
    return False


@_register(
    "TPU401", "bare-except",
    "bare 'except:' swallows everything, including KeyboardInterrupt and "
    "CheckpointCorruptionError",
    "name the exception types you can actually handle (at minimum "
    "'except Exception'); let corruption and interrupts propagate")
def _check_bare_except(ctx: FileContext):
    rule = RULES["TPU401"]
    for node in ctx.nodes():
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            yield _finding(rule, ctx, node, "bare 'except:' handler")


@_register(
    "TPU402", "swallowed-ckpt-error",
    "broad except around a checkpoint load can swallow "
    "CheckpointCorruptionError (a ValueError subclass) and train from "
    "garbage",
    "catch CheckpointCorruptionError explicitly first (fall back via "
    "resilience.CheckpointManager.load_latest), or re-raise it from the "
    "broad handler")
def _check_swallowed_ckpt(ctx: FileContext):
    rule = RULES["TPU402"]
    for node in ctx.nodes():
        if not isinstance(node, ast.Try):
            continue
        loads = [c for stmt in node.body for c in _calls_in(stmt)
                 if (d := ctx.dotted(c.func)) and
                 d.rpartition(".")[2] in _CKPT_LOADS]
        if not loads:
            continue
        for h in node.handlers:
            names = []
            if h.type is None:
                names = ["<bare>"]
            else:
                types = h.type.elts if isinstance(h.type, ast.Tuple) \
                    else [h.type]
                names = [t.rpartition(".")[2] for n in types
                         if (t := (ctx.dotted(n) or ""))]
            caught = [n for n in names if n in _BROAD or n == "<bare>"]
            if caught and not _handler_reraises(h):
                yield _finding(
                    rule, ctx, h,
                    f"'except {', '.join(caught)}' around "
                    f"{loads[0].func.attr if isinstance(loads[0].func, ast.Attribute) else ctx.dotted(loads[0].func)}"
                    "() does not re-raise")


# =============================================================================
# TPU5xx — layer-construction hygiene
# =============================================================================
_MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                     ast.DictComp, ast.SetComp)
_MUTABLE_CALLS = {"list", "dict", "set", "bytearray",
                  "collections.defaultdict", "collections.OrderedDict"}


@_register(
    "TPU501", "mutable-default-arg",
    "mutable default argument in a class constructor: every instance "
    "shares ONE object, so layer state bleeds across instances",
    "default to None and materialize inside __init__ "
    "(x = [] if x is None else x)",
    framework_only=True)
def _check_mutable_defaults(ctx: FileContext):
    rule = RULES["TPU501"]
    for node in ctx.nodes():
        if not isinstance(node, ast.ClassDef):
            continue
        for item in node.body:
            if not (isinstance(item, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)) and
                    item.name == "__init__"):
                continue
            defaults = list(item.args.defaults) + \
                [d for d in item.args.kw_defaults if d is not None]
            for d in defaults:
                bad = isinstance(d, _MUTABLE_LITERALS) or (
                    isinstance(d, ast.Call) and
                    (ctx.dotted(d.func) or "") in _MUTABLE_CALLS)
                if bad:
                    yield _finding(
                        rule, ctx, d,
                        f"mutable default in {node.name}.__init__")


# SHD1xx (sharding/layout), CCY1xx/2xx (concurrency/lifecycle) and
# WIR1xx (wire-contract) rules register themselves into RULES; the
# imports sit at the bottom so each module can import this module's
# half-initialized namespace (everything they need is defined above).
from . import shard_rules  # noqa: E402,F401
from . import concur_rules  # noqa: E402,F401
from . import wire_rules  # noqa: E402,F401
