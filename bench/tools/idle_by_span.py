"""Where the host was while the device stood idle, and what the device ran,
by the program's own names, for one traced run:

    python3 bench/tools/idle_by_span.py .bench_trace/<cell>

Prints three tables from the trace directory's ``*.trace.json.gz``
(``bench.lib.spans``): idle seconds of the device by program span
(``serve.*``, ``train.*``), each gap cut at the edges of the spans inside it
and each piece given to the innermost span over it; the program's spans
by name (count, seconds, mean); device seconds by scope path, largest first,
with the operations that carry no scope and, beside each path, how much of it
is operations read under a neighbour's scope.
Needs no chip.
"""
from __future__ import annotations

import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import spans as S, trace as T     # noqa: E402


def tables(t, top=24):
    """The three tables as lists of rows of strings."""
    ops = t["ops"]
    lo, hi = ops[0][0], max(o[0] + o[1] for o in ops)
    busy = T.busy_us(ops)
    idle = S.idle_by_span(t)
    first = [["idle seconds by program span", "s", "% of slice"]]
    for name in sorted(idle, key=idle.get, reverse=True):
        first.append([name, f"{idle[name]:.6f}",
                      f"{100e6 * idle[name] / (hi - lo):.3f}"])
    first.append(["(slice; busy; idle over the floor)",
                  f"{(hi - lo) / 1e6:.6f}; {busy / 1e6:.6f}",
                  f"{sum(idle.values()):.6f}"])
    by = defaultdict(lambda: [0, 0.0])
    for _, dur, name, _ in t["spans"]:
        by[name][0] += 1
        by[name][1] += dur
    second = [["program span", "count", "s", "mean ms"]]
    for name in sorted(by, key=lambda n: by[n][1], reverse=True):
        n, us = by[name]
        second.append([name, str(n), f"{us / 1e6:.6f}", f"{us / 1e3 / n:.4f}"])
    gap = S.between(t, "serve.step")
    if gap:
        n = len(S.named(t, "serve.step")) - 1
        second.append(["(between serve.step)", str(n), f"{gap / 1e6:.6f}",
                       f"{gap / 1e3 / n:.4f}"])
    scope = defaultdict(lambda: [0, 0.0, 0.0])   # ops, us, of it inferred
    for op in ops:
        key = (S.scope_of(op) or "(no scope)") if op[3] else "(no tf_op)"
        scope[key][0] += 1
        scope[key][1] += op[1]
        scope[key][2] += op[1] * (bool(op[3]) and not op[4])
    third = [["device seconds by scope path", "ops", "s", "% of busy",
              "of it inferred"]]
    ranked = sorted(scope, key=lambda k: scope[k][1], reverse=True)
    for key in ranked[:top]:
        n, us, lent = scope[key]
        third.append([key, str(n), f"{us / 1e6:.6f}", f"{100 * us / busy:.3f}",
                      f"{100 * lent / busy:.3f}"])
    n = sum(scope[k][0] for k in ranked[top:])
    us, lent = (sum(scope[k][i] for k in scope) for i in (1, 2))
    rest = sum(scope[k][1] for k in ranked[top:])
    if rest:
        third.append([f"({len(ranked) - top} more)", str(n), f"{rest / 1e6:.6f}",
                      f"{100 * rest / busy:.3f}", ""])
    third.append(["(all; inferred: no tf_op of its own)", str(len(ops)),
                  f"{us / 1e6:.6f}", f"{100 * us / busy:.3f}",
                  f"{100 * lent / busy:.3f}"])
    return first, second, third


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = T.find(argv[0])
    t = S.load(path) if path else None
    if t is None:
        print(f"no trace with program spans under {argv[0]}", file=sys.stderr)
        return 1
    for table in tables(t):
        width = max(len(r[0]) for r in table)
        for row in table:
            print("  ".join([row[0].ljust(width)] + [c.rjust(20) for c in row[1:]]))
        print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
