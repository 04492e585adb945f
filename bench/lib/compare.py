"""The numbers that decide ``correct``, each beside its limit.

Limits live in ``bench/limits/<cell>.json`` (name -> limit), set from the
readings PERF.md gives; a number without a limit there is reported and not
judged.
"""
from __future__ import annotations

import json
import os
import statistics


def worst_leaf_gap(program: dict, reference: dict, skip=()):
    """The largest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some leaves are all but zero)."""
    if set(program) != set(reference):
        raise ValueError("leaves differ: "
                         f"{sorted(set(program) ^ set(reference))[:6]}")
    floor = statistics.median(reference.values())
    worst, at = 0.0, None
    for name, ref in reference.items():
        if name in skip:
            continue
        gap = abs(program[name] - ref) / max(ref, floor)
        if gap > worst:
            worst, at = gap, name
    return worst, at


def still_leaves(reference_grad: dict, share=1e-3):
    """Leaves whose reference gradient is nought to rounding: under
    ``share`` of the median leaf's. Adam moves them by round-off alone, so
    their change is not compared."""
    floor = share * statistics.median(reference_grad.values())
    return {n for n, g in reference_grad.items() if g < floor}


def training_numbers(program: dict, reference: dict) -> dict:
    """``program`` and ``reference``: ``losses``, ``grad_norm``,
    ``change_norm`` as ``reference.train_steps.follow`` returns them."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"]), 1):
        out[f"loss{i}_gap"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], _ = worst_leaf_gap(program["grad_norm"],
                                             reference["grad_norm"])
    out["change_norm_gap"], _ = worst_leaf_gap(
        program["change_norm"], reference["change_norm"],
        skip=still_leaves(reference["grad_norm"]))
    return out


def judge(numbers: dict, cell) -> tuple:
    """(correct, {name: {"value", "limit"}}). A number that is missing
    (None) or NaN fails its limit."""
    path = os.path.join(cell.bench_dir, "limits", cell.name + ".json")
    with open(path) as f:
        limits = json.load(f)["limits"]
    compared, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        compared[name] = {"value": value, "limit": limit}
        if limit is not None and (value is None or not value <= limit):
            ok = False
    missing = [n for n in limits if n not in numbers]
    if missing:
        raise ValueError(f"limits without a number in this run: {missing}")
    return ok, compared
