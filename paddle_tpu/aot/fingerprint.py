"""Cache-key anatomy for AOT program artifacts.

A persistent compiled-program cache is only safe if a stale or
foreign artifact can never be *silently* loaded: the stock persistent
XLA compile cache stays off for the CPU tests for exactly that reason
(tests/conftest.py), so this module errs hard on the side of "any mismatch is a
miss, never a wrong hit". One key commits to every input that can change
the compiled program:

  * **topology** — device platform/kind/count, process count, and the
    canonical mesh-axis registry (``distributed.mesh.KNOWN_AXES``): an
    artifact exported on one device assembly never loads on another.
  * **avals** — the abstract shapes/dtypes of every input leaf plus the
    pytree structure (the caller-supplied signature string), and the
    repr of any explicit shardings the caller compiled with.
  * **flags** — the full ``framework.flags`` registry value map.
    Over-inclusion is deliberate: a flag that cannot affect tracing
    costs at most a spurious miss, while omitting one that can would be
    a wrong hit.
  * **versions** — jax + jaxlib versions (the StableHLO producer).
  * **source** — a digest of every ``.py`` file in the ``paddle_tpu``
    package (the traced framework code) plus a recursive code-object
    digest of the wrapped function itself (covers closures defined
    outside the package).
  * **extras** — caller-supplied discriminators (optimizer class,
    engine geometry, quantization mode, ...), ``repr``-ed.

``fingerprint()`` returns ``(key_hex, components)``; the components dict
is stored in the artifact's meta file so a surprising miss can be
diffed against what is on disk (``explain_miss``).
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Any, Dict, Optional, Sequence, Tuple

_PKG_DIGEST_CACHE: Dict[str, str] = {}


def _blake(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=16).hexdigest()


def package_digest() -> str:
    """Content digest over every .py file of the paddle_tpu package —
    the "source fingerprint of the traced code". Cached per process
    (the package does not change under a running process)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cached = _PKG_DIGEST_CACHE.get(root)
    if cached is not None:
        return cached
    h = hashlib.blake2b(digest_size=16)
    # lazy walk: the in-place dirnames assignment only prunes/orders
    # traversal when os.walk is consumed as a generator (sorted() over
    # the walk would exhaust it first, making the pruning dead code)
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            h.update(os.path.relpath(path, root).encode())
            try:
                with open(path, "rb") as f:
                    h.update(f.read())
            except OSError:
                h.update(b"<unreadable>")
    digest = h.hexdigest()
    _PKG_DIGEST_CACHE[root] = digest
    return digest


def _const_repr(c) -> str:
    """Deterministic repr for a code constant. frozensets (set-literal
    membership tests compile to them) iterate in hash order, which
    varies per process under PYTHONHASHSEED randomization — raw repr()
    would turn every restart into a spurious cache miss. Tuples recurse
    because a tuple const may nest a frozenset."""
    if isinstance(c, frozenset):
        return "frozenset{" + ",".join(sorted(map(_const_repr, c))) + "}"
    if isinstance(c, tuple):
        return "(" + ",".join(_const_repr(x) for x in c) + ")"
    return repr(c)


def _value_repr(v, depth: int = 0) -> str:
    """Deterministic repr for a VALUE reached through a function's
    defaults / closure cells / partial bindings / referenced globals:
    scalars and containers of scalars repr by value (so a user changing
    ``weight=0.5`` to ``0.9`` forks the key); callables digest by their
    code; 0-d array-likes (np/jax scalars) by dtype+value, other
    array-likes by shape+dtype (their VALUES are the caller's job to
    commit via key_extras — see the trainer's buffer digest); anything
    else only its type — a generic object repr embeds the memory
    address, which would turn every restart into a spurious miss."""
    if depth > 6:  # self-referential containers must terminate
        return "<deep>"
    if isinstance(v, (int, float, complex, str, bytes, bool, type(None))):
        return repr(v)
    if isinstance(v, (tuple, list)):
        return "[" + ",".join(_value_repr(x, depth + 1) for x in v) + "]"
    if isinstance(v, (set, frozenset)):
        return "{" + ",".join(sorted(_value_repr(x, depth + 1)
                                     for x in v)) + "}"
    if isinstance(v, dict):
        items = sorted(((repr(k), _value_repr(x, depth + 1))
                        for k, x in v.items()))
        return "{" + ",".join(f"{k}:{x}" for k, x in items) + "}"
    import types
    if isinstance(v, types.ModuleType):
        # a module HAS .shape/.dtype attributes (np.shape is a function)
        # but is no array; name identity is all a key needs from it
        return f"<module {getattr(v, '__name__', '?')}>"
    if callable(v):
        qn = getattr(v, "__qualname__", type(v).__qualname__)
        return f"<fn {getattr(v, '__module__', '?')}.{qn}:{code_digest(v)}>"
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        try:
            shape = tuple(v.shape)
            if shape == ():
                return f"<scalar {v.dtype}={v.item()!r}>"
            return f"<array {v.dtype}{shape}>"
        except Exception:  # noqa: BLE001 — shape/dtype only array-like
            pass
    return f"<{type(v).__module__}.{type(v).__qualname__}>"


def stable_repr(v) -> str:
    """Address-safe deterministic repr for arbitrary structures callers
    embed in ``key_extras`` (e.g. the serving decoder's ``_static_key``,
    which for MoE configs holds live FUNCTION objects — raw ``repr``
    would bake a per-process memory address into the key and turn every
    replica into a permanent miss)."""
    return _value_repr(v)


def code_digest(fn) -> str:
    """Recursive digest of a callable: code objects (bytecode, consts,
    names) PLUS the values bound outside the bytecode — __defaults__ /
    __kwdefaults__, functools.partial args and keywords, and closure
    cell contents — unwrapping partial / bound methods / __wrapped__.
    A user's ``def loss(p, y, weight=0.5)`` (or partial(loss,
    weight=0.5), or a closure over a scalar) lives exactly in those
    slots: omitting any of them is a silent wrong hit. Falls back to
    the qualified name for builtins and C callables."""
    import functools
    seen = set()
    h = hashlib.blake2b(digest_size=16)

    def visit_code(code):
        if id(code) in seen:
            return
        seen.add(id(code))
        h.update(code.co_code)
        h.update(repr(code.co_names).encode())
        h.update(repr(code.co_varnames).encode())
        h.update(repr(code.co_freevars).encode())
        for const in code.co_consts:
            if hasattr(const, "co_code"):
                visit_code(const)
            else:
                h.update(_const_repr(const).encode())

    def visit_value(v, depth):
        if callable(v):
            visit(v, depth)
        else:
            h.update(_value_repr(v).encode())

    def visit(f, depth=0):
        if depth > 8 or f is None or id(f) in seen:
            return
        seen.add(id(f))
        while isinstance(f, functools.partial):
            h.update(b"partial")
            for a in f.args:
                visit_value(a, depth + 1)
            for k in sorted(f.keywords or {}):
                h.update(k.encode())
                visit_value(f.keywords[k], depth + 1)
            f = f.func
        f = getattr(f, "__wrapped__", f)
        f = getattr(f, "__func__", f)  # bound method -> function
        code = getattr(f, "__code__", None)
        if code is None:
            # callable instance or C callable: digest a deterministic
            # identity (NEVER repr(obj) — that embeds the memory address,
            # which would make every process/instance a spurious miss)
            qn = getattr(f, "__qualname__", None)
            if not isinstance(qn, str):
                qn = f"{type(f).__module__}.{type(f).__qualname__}"
            h.update(qn.encode())
            call = getattr(type(f), "__call__", None)
            if getattr(call, "__code__", None) is not None:
                visit(call, depth + 1)
            return
        visit_code(code)
        # module-global bindings the bytecode references by name: a
        # constant read from the enclosing module (``LR = 0.5`` above a
        # cached loss_fn) is traced into the program exactly like a
        # default or closure value, and package_digest cannot see user
        # modules. USER modules only: inside pinned packages the source
        # is already committed (package_digest for paddle_tpu, the
        # versions component for jax/numpy), and their module-level
        # runtime state (dispatch counters, lazily-populated registries)
        # must NOT fold into the key — it shifts across a single train
        # step and would turn identical restarts into spurious misses.
        # Builtins (print, len, ...) resolve past __globals__ and are
        # skipped by the `in g` test. Values: immutable scalar consts
        # hash by value, callables by code, mutable containers never.
        mod = (getattr(f, "__module__", "") or "").split(".", 1)[0]
        if mod not in ("paddle_tpu", "jax", "jaxlib", "numpy"):
            names: set = set()

            def _collect(c):
                names.update(c.co_names)
                for const in c.co_consts:
                    if hasattr(const, "co_code"):
                        _collect(const)

            def _is_const(v):
                if isinstance(v, (int, float, complex, str, bytes, bool,
                                  type(None))):
                    return True
                if isinstance(v, (tuple, frozenset)):
                    return all(_is_const(x) for x in v)
                # np/jax scalars (0-d, value-hashed by _value_repr)
                return getattr(v, "shape", None) == () and \
                    hasattr(v, "dtype")

            _collect(code)
            g = getattr(f, "__globals__", None) or {}
            for n in sorted(names):
                if n not in g:
                    continue
                v = g[n]
                if callable(v):
                    visit(v, depth + 1)
                elif _is_const(v):
                    h.update(n.encode())
                    h.update(_value_repr(v).encode())
        for d in getattr(f, "__defaults__", None) or ():
            visit_value(d, depth + 1)
        for k in sorted(getattr(f, "__kwdefaults__", None) or {}):
            h.update(k.encode())
            visit_value(f.__kwdefaults__[k], depth + 1)
        # closure cells: a cached fn closing over another fn (e.g. a
        # decoder method) misses when that code changes; a closed-over
        # scalar misses when its value changes
        for cell in getattr(f, "__closure__", None) or ():
            try:
                v = cell.cell_contents
            except ValueError:
                continue
            visit_value(v, depth + 1)

    visit(fn)
    return h.hexdigest()


def module_digest(layer) -> str:
    """Digest of a Layer TREE: per sublayer (root included) the path
    name, class identity, the forward's code, and every scalar instance
    attribute. ``code_digest(type(model).forward)`` alone cannot tell
    ``Sequential(Linear, ReLU, Linear)`` from ``Sequential(Linear, GELU,
    Linear)`` (identical param names/shapes, identical container
    forward), nor two LayerNorms differing only in ``eps`` — values the
    traced program bakes in as constants. Scalar attrs are taken from
    ``vars``: over-inclusion costs a spurious miss, omission a wrong
    hit (module docstring)."""
    if not hasattr(layer, "named_sublayers"):  # bare-callable "model"
        return code_digest(layer)
    h = hashlib.blake2b(digest_size=16)
    for name, sub in layer.named_sublayers(include_self=True):
        cls = type(sub)
        h.update(name.encode())
        h.update(f"{cls.__module__}.{cls.__qualname__}".encode())
        fwd = getattr(cls, "forward", None)
        if fwd is not None:
            h.update(code_digest(fwd).encode())
        for k in sorted(vars(sub)):
            v = vars(sub)[k]
            if isinstance(v, (int, float, str, bool, type(None))):
                h.update(f"{k}={v!r};".encode())
            elif isinstance(v, (tuple, list)) and all(
                    isinstance(x, (int, float, str, bool, type(None)))
                    for x in v):
                h.update(f"{k}={list(v)!r};".encode())
    return h.hexdigest()


def topology() -> Dict[str, Any]:
    """Device assembly + canonical mesh-axis registry."""
    import jax

    from ..distributed.mesh import KNOWN_AXES
    devices = jax.devices()
    kinds: Dict[str, int] = {}
    for d in devices:
        k = f"{d.platform}:{getattr(d, 'device_kind', '?')}"
        kinds[k] = kinds.get(k, 0) + 1
    return {
        "platform": devices[0].platform if devices else "none",
        "device_kinds": dict(sorted(kinds.items())),
        "device_count": len(devices),
        "process_count": jax.process_count(),
        "mesh_axes": list(KNOWN_AXES),
    }


def flag_values() -> Dict[str, Any]:
    """The FULL flag registry (see module docstring: over-inclusion is
    the safe direction for a cache key)."""
    from ..framework import flags as _flags
    return {k: _flags._FLAGS[k] for k in sorted(_flags._FLAGS)}


def versions() -> Dict[str, str]:
    import jax
    try:
        import jaxlib
        jl = getattr(jaxlib, "__version__", "?")
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jl = "?"
    return {"jax": jax.__version__, "jaxlib": jl}


def avals_signature(avals_tree) -> str:
    """Canonical string for a pytree of ShapeDtypeStruct-likes: the tree
    structure plus shape/dtype per leaf. Deterministic across processes
    (no object ids)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(avals_tree)
    parts = [str(treedef)]
    for leaf in leaves:
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", type(leaf).__name__)
        parts.append(f"{dtype}[{','.join(map(str, shape))}]")
    return ";".join(parts)


def fingerprint(name: str, avals_sig: str, fn=None,
                extras: Sequence = (),
                shardings: Optional[str] = None
                ) -> Tuple[str, Dict[str, Any]]:
    """Compute the cache key for program `name` over inputs `avals_sig`.

    Returns ``(key_hex, components)``. `extras` entries are repr-ed in
    order; `shardings` is the caller's repr of any explicit in/out
    shardings the program compiles with."""
    components = {
        "name": name,
        "avals": avals_sig,
        "shardings": shardings or "",
        "topology": topology(),
        "flags": flag_values(),
        "versions": versions(),
        "source": {
            "package": package_digest(),
            "fn": code_digest(fn) if fn is not None else "",
        },
        "extras": [repr(e) for e in extras],
    }
    blob = json.dumps(components, sort_keys=True, default=str)
    return _blake(blob.encode()), components


def explain_miss(components: Dict[str, Any],
                 stored: Dict[str, Any]) -> Dict[str, Tuple[Any, Any]]:
    """Diff two component dicts (live vs an artifact's stored meta):
    {component: (live, stored)} for every top-level mismatch — the
    debugging surface for "why did this restart recompile"."""
    out = {}
    for k in sorted(set(components) | set(stored)):
        a, b = components.get(k), stored.get(k)
        if a != b:
            out[k] = (a, b)
    return out


__all__ = ["fingerprint", "avals_signature", "package_digest",
           "code_digest", "module_digest", "stable_repr", "topology",
           "flag_values", "versions", "explain_miss"]
