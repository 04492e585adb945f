"""SHD2xx — abstract layout evaluator (the shardcheck dynamic half).

Runs a step function abstractly (``jax.make_jaxpr`` over
``ShapeDtypeStruct`` inputs — the same shapes-only abstract
interpretation as ``jax.eval_shape``, no devices, CPU-safe with
``JAX_PLATFORMS=cpu`` and no TPU present) and propagates a simple
per-dimension layout through the jaxpr:

* **SHD201** — divisibility: a dimension sharded over mesh axes whose
  product does not divide it means per-device padding and, on shape
  drift, a recompile per distinct remainder.
* **SHD202** — implicit-reshard hotspot: an op boundary whose incoming
  layouts force the compiler to materialize data movement (all-gather
  of a sharded contracting dim, psum of a reduced sharded dim, a
  layout conflict between elementwise operands, an output constraint
  the propagated layout cannot meet) with estimated traffic above a
  threshold. The byte numbers are a *model*, not a profile — they rank
  boundaries, they do not predict wall-clock.
* **SHD210** — layout-report drift: the stable subset of the report for
  the driver's representative step differs from the committed baseline
  (``tools/layout_baseline.json``); rerun ``tools/lint.py
  --update-baseline`` after an intentional layout change.

The full per-op report (``layout_report``) is machine-readable JSON:
one record per jaxpr equation with the op name, output shape, the
propagated spec, and the estimated reshard bytes — dump it with
``tools/lint.py --layout-report out.json`` for offline inspection.

jax imports live inside functions: importing this module stays
stdlib-cheap so ``tools/lint.py --fix-hints`` can print SHARD_RULES
without jax installed.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

from .rules import Finding

__all__ = ["SHARD_RULES", "layout_check", "layout_report", "spec_tuple"]

SHARD_RULES = {
    "SHD201": ("sharded-dim-not-divisible",
               "pad or reshape the dimension to a multiple of the mesh "
               "axis size, or shard a different dimension — XLA pads "
               "silently and a drifting remainder recompiles per shape"),
    "SHD202": ("implicit-reshard-hotspot",
               "an op boundary reshards more bytes than the threshold; "
               "move the sharding constraint, pre-reshard once outside "
               "the step, or change the layout so the contraction is "
               "local (this is the accidental all-gather-per-step that "
               "10x's step time)"),
    "SHD210": ("layout-report-drift",
               "the representative step's layout report no longer "
               "matches tools/layout_baseline.json; if the layout "
               "change is intentional run tools/lint.py "
               "--update-baseline, otherwise find the op that moved"),
}

_DEF_THRESHOLD = 1 << 20  # 1 MiB per boundary


# -- spec plumbing ------------------------------------------------------------
def spec_tuple(spec, ndim: int) -> Tuple:
    """Normalize a PartitionSpec / tuple / None to an ndim-length tuple
    whose entries are None, an axis name, or a tuple of axis names."""
    if spec is None:
        return (None,) * ndim
    if isinstance(spec, str):  # shorthand: one entry, not per-character
        spec = (spec,)
    entries = list(spec)
    entries = entries[:ndim] + [None] * (ndim - len(entries))
    out = []
    for e in entries:
        if e is None or isinstance(e, str):
            out.append(e)
        else:
            t = tuple(e)
            out.append(t if len(t) != 1 else t[0])
    return tuple(out)


def _axes_of(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def _factor(entry, mesh_axes: Dict[str, int]) -> int:
    n = 1
    for a in _axes_of(entry):
        n *= int(mesh_axes.get(a, 1))
    return n


def _spec_json(spec) -> List:
    return [list(_axes_of(e)) if not isinstance(e, (str, type(None)))
            else e for e in spec]


def _nbytes(aval) -> int:
    return int(math.prod(aval.shape)) * aval.dtype.itemsize


def _replicated(ndim: int) -> Tuple:
    return (None,) * ndim


# -- findings -----------------------------------------------------------------
def _finding(rule: str, message: str, label: str, line: int = 0) -> Finding:
    name, hint = SHARD_RULES[rule]
    return Finding(rule, label, line, 0, message, hint, "error")


def _check_divisible(shape, spec, mesh_axes, what, label,
                     findings: List[Finding]):
    for d, entry in enumerate(spec):
        k = _factor(entry, mesh_axes)
        if k > 1 and shape[d] % k != 0:
            findings.append(_finding(
                "SHD201",
                f"{what}: dim {d} (size {shape[d]}) is not divisible by "
                f"axes {list(_axes_of(entry))} (size {k}) — XLA pads to "
                f"{-(-shape[d] // k) * k} per device", label))


def _eqn_line(eqn) -> int:
    """Best-effort user source line for a jaxpr equation."""
    try:
        frame = eqn.source_info.traceback.frames[0]
        return int(frame.start_line)
    except Exception:
        return 0


# -- propagation --------------------------------------------------------------
class _Prop:
    def __init__(self, mesh_axes: Dict[str, int], label: str,
                 findings: List[Finding], ops: List[dict],
                 threshold: int = _DEF_THRESHOLD):
        self.mesh_axes = mesh_axes
        self.label = label
        self.findings = findings
        self.ops = ops
        self.threshold = int(threshold)
        self.total_bytes = 0

    def _record(self, eqn, out_spec, bytes_, note):
        self.total_bytes += bytes_
        aval = eqn.outvars[0].aval if eqn.outvars else None
        self.ops.append({
            "op": eqn.primitive.name,
            "shape": list(getattr(aval, "shape", ())),
            "spec": _spec_json(out_spec) if out_spec else [],
            "reshard_bytes": int(bytes_),
            "note": note,
        })

    def _merge(self, eqn, specs, avals):
        """Elementwise merge of operand specs (size-1 dims broadcast and
        carry no layout); a conflict — two different shardings of one
        dim — costs a reshard of the later operand."""
        out_shape = eqn.outvars[0].aval.shape
        bytes_ = 0
        notes = []
        out = [None] * len(out_shape)
        for spec, aval in zip(specs, avals):
            for d, (a, b) in enumerate(zip(out, spec)):
                if b is None or a == b or aval.shape[d] == 1:
                    continue
                if a is None:
                    out[d] = b
                else:
                    bytes_ += _nbytes(aval)
                    notes.append(f"dim {d}: {_axes_of(b)} -> {_axes_of(a)}")
        return tuple(out), bytes_, ("layout conflict: " + "; ".join(notes)
                                    if notes else "")

    def _dot_general(self, eqn, specs):
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        ls, rs = specs
        bytes_ = 0
        notes = []
        for dl, dr in zip(lc, rc):
            al, ar = _axes_of(ls[dl]), _axes_of(rs[dr])
            if al and ar and al == ar:
                # both sides sharded alike: local dot + psum of the output
                out_b = _nbytes(eqn.outvars[0].aval)
                bytes_ += out_b
                notes.append(f"psum over {list(al)} ({out_b}B)")
            elif al:
                bytes_ += _nbytes(lhs)
                notes.append(f"all-gather lhs contracting dim {dl} "
                             f"({list(al)}, {_nbytes(lhs)}B)")
            elif ar:
                bytes_ += _nbytes(rhs)
                notes.append(f"all-gather rhs contracting dim {dr} "
                             f"({list(ar)}, {_nbytes(rhs)}B)")
        out_spec = tuple(
            [ls[d] for d in lb]
            + [ls[d] for d in range(lhs.ndim) if d not in lc + lb]
            + [rs[d] for d in range(rhs.ndim) if d not in rc + rb])
        return out_spec, bytes_, "; ".join(notes)

    def _reduce(self, eqn, spec):
        axes = eqn.params.get("axes", ())
        reduced = [a for d in axes for a in _axes_of(spec[d])]
        out_spec = tuple(e for d, e in enumerate(spec) if d not in axes)
        bytes_ = 0
        note = ""
        if reduced:
            bytes_ = _nbytes(eqn.outvars[0].aval)
            note = f"psum over {reduced} ({bytes_}B)"
        return out_spec, bytes_, note

    def run(self, jaxpr, env: Dict):
        from jax.extend.core import Literal

        def read(v):
            if isinstance(v, Literal):
                return _replicated(getattr(v.aval, "ndim", 0))
            return env.get(v, _replicated(getattr(v.aval, "ndim", 0)))

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            specs = [read(v) for v in eqn.invars]
            avals = [v.aval for v in eqn.invars]
            out_spec, bytes_, note = None, 0, ""
            if prim == "dot_general":
                out_spec, bytes_, note = self._dot_general(eqn, specs)
            elif prim.startswith("reduce_") and "axes" in eqn.params:
                out_spec, bytes_, note = self._reduce(eqn, specs[0])
            elif prim == "broadcast_in_dim":
                out_spec = list(_replicated(eqn.outvars[0].aval.ndim))
                for src, dst in enumerate(
                        eqn.params["broadcast_dimensions"]):
                    out_spec[dst] = specs[0][src]
                out_spec = tuple(out_spec)
            elif prim == "transpose":
                out_spec = tuple(specs[0][d]
                                 for d in eqn.params["permutation"])
            elif prim == "sharding_constraint":
                req = spec_tuple(
                    getattr(eqn.params.get("sharding"), "spec", None),
                    avals[0].ndim)
                _check_divisible(avals[0].shape, req, self.mesh_axes,
                                 f"sharding_constraint at line "
                                 f"{_eqn_line(eqn)}", self.label,
                                 self.findings)
                if specs[0] != req and any(e is not None for e in specs[0]):
                    bytes_ = _nbytes(avals[0])
                    note = (f"reshard {_spec_json(specs[0])} -> "
                            f"{_spec_json(req)}")
                out_spec = req
            elif inner is not None and prim in ("pjit", "custom_jvp_call",
                                                "custom_vjp_call",
                                                "custom_vjp_call_jaxpr",
                                                "remat", "checkpoint",
                                                "closed_call",
                                                "core_call", "xla_call"):
                sub = getattr(inner, "jaxpr", inner)
                sub_env = dict(zip(sub.invars, specs))
                self.run_sub(sub, sub_env)
                for outv, var in zip(eqn.outvars, sub.outvars):
                    env[outv] = sub_env.get(
                        var, _replicated(getattr(var.aval, "ndim", 0)))
                continue
            elif eqn.outvars and avals and all(
                    getattr(a, "ndim", -1) == 0
                    or (getattr(a, "ndim", -1) == eqn.outvars[0].aval.ndim
                        and all(s == o or s == 1 for s, o in
                                zip(a.shape, eqn.outvars[0].aval.shape)))
                    for a in avals):
                out_spec, bytes_, note = self._merge(eqn, specs, avals)
            else:
                # unknown structural op: layout knowledge stops here
                out_spec = None
                if any(any(e is not None for e in s) for s in specs):
                    note = "sharding dropped (unmodeled op)"
            for v in eqn.outvars:
                nd = getattr(v.aval, "ndim", 0)
                env[v] = (out_spec if out_spec is not None
                          and len(out_spec) == nd else _replicated(nd))
            self._record(eqn, env[eqn.outvars[0]] if eqn.outvars else (),
                         bytes_, note)
            if bytes_:
                line = _eqn_line(eqn)
                if bytes_ > self.threshold:
                    self.findings.append(_finding(
                        "SHD202",
                        f"op {prim!r} reshards ~{bytes_} bytes per step "
                        f"({note})", self.label, line))

    def run_sub(self, jaxpr, env):
        self.run(jaxpr, env)


# -- public API ---------------------------------------------------------------
def layout_check(fn, args: Sequence, in_specs: Sequence,
                 mesh_axes: Dict[str, int],
                 out_specs: Optional[Sequence] = None, *,
                 reshard_threshold: int = _DEF_THRESHOLD,
                 label: str = "layout_check"):
    """Abstractly evaluate `fn`'s layout. Returns (findings, report).

    args: flat sequence of arrays / ShapeDtypeStructs / (shape, dtype)
    tuples. in_specs: one PartitionSpec-like per arg. mesh_axes:
    {axis name: size} — no devices are required, the mesh is abstract.
    out_specs (optional): requested output layouts, checked against the
    propagated ones.
    """
    import jax
    import numpy as np

    structs = []
    for a in args:
        if isinstance(a, tuple) and len(a) == 2 and \
                not hasattr(a, "shape"):
            structs.append(jax.ShapeDtypeStruct(a[0], np.dtype(a[1])))
        else:
            structs.append(jax.ShapeDtypeStruct(a.shape, a.dtype))

    findings: List[Finding] = []
    ops: List[dict] = []
    specs = [spec_tuple(s, st.ndim) for s, st in zip(in_specs, structs)]
    for i, (st, sp) in enumerate(zip(structs, specs)):
        _check_divisible(st.shape, sp, mesh_axes, f"input {i}", label,
                         findings)

    # one abstract trace (eval_shape semantics: shapes only, no devices)
    closed = jax.make_jaxpr(fn)(*structs)
    jaxpr = closed.jaxpr

    prop = _Prop(dict(mesh_axes), label, findings, ops,
                 threshold=reshard_threshold)
    env = dict(zip(jaxpr.invars, specs))
    prop.run(jaxpr, env)

    out_leaves = [v for v in jaxpr.outvars]
    propagated = [env.get(v, _replicated(getattr(v.aval, "ndim", 0)))
                  for v in out_leaves]
    outputs = []
    for i, (v, got) in enumerate(zip(out_leaves, propagated)):
        nd = getattr(v.aval, "ndim", 0)
        rec = {"shape": list(getattr(v.aval, "shape", ())),
               "dtype": str(getattr(v.aval, "dtype", "?")),
               "spec": _spec_json(got)}
        if out_specs is not None and i < len(out_specs):
            want = spec_tuple(out_specs[i], nd)
            _check_divisible(v.aval.shape, want, mesh_axes,
                             f"output {i}", label, findings)
            rec["requested"] = _spec_json(want)
            if want != got and any(e is not None for e in got):
                b = _nbytes(v.aval)
                prop.total_bytes += b
                if b > prop.threshold:
                    findings.append(_finding(
                        "SHD202",
                        f"output {i} reshards ~{b} bytes to meet "
                        f"out_spec {_spec_json(want)} (propagated "
                        f"{_spec_json(got)})", label))
        outputs.append(rec)

    report = {
        "label": label,
        "mesh": {k: int(v) for k, v in mesh_axes.items()},
        "inputs": [{"shape": list(st.shape), "dtype": str(st.dtype),
                    "spec": _spec_json(sp)}
                   for st, sp in zip(structs, specs)],
        "outputs": outputs,
        "ops": ops,
        "total_reshard_bytes": int(prop.total_bytes),
        "violations": sorted(f.key() for f in findings),
    }
    return findings, report


def layout_report(fn, args, in_specs, mesh_axes, out_specs=None, **kw):
    """Just the JSON-ready report half of layout_check."""
    return layout_check(fn, args, in_specs, mesh_axes, out_specs, **kw)[1]


# the stable subset tools/lint.py diffs against tools/layout_baseline.json
# ("ops" is excluded: primitive spellings drift across jax versions)
BASELINE_KEYS = ("label", "mesh", "inputs", "outputs",
                 "total_reshard_bytes", "violations")


def baseline_view(report: dict) -> dict:
    return {k: report[k] for k in BASELINE_KEYS}
