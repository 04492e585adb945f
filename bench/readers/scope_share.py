"""Device time under a scope or a kernel of the program (``bench.lib.spans``:
a whole name in the operation's scope path or its own name), as a share of
the traced slice's busy time: the metric file's ``scope`` (a name or a list)
or ``kernel`` (the same). With ``inferred`` only the part of it whose
operations carry no scope of their own and are read under a neighbour's (0
where the scope is there and all of it is its own). With ``flops`` (``fwd``
or ``bwd``) instead a share of the roofline: the flash forward's 2 or the
backward's 5 of the 7 products of ``flops.flash_flops``, once per layer and
``train.step`` span, over the peak, over every execution's time (a forward
recomputed under remat is time, not work). Nothing under the name: reads
nothing."""
from bench.lib import flops, spans as S, trace as T

PRODUCTS = {"fwd": 2.0 / 7.0, "bwd": 5.0 / 7.0}


def read(ctx, name):
    spec = ctx["cell"].metric_file(name)
    t = S.of_run(ctx)
    if not t:
        return None
    names = spec.get("scope") or spec["kernel"]
    names = [names] if isinstance(names, str) else names
    us = S.time_in(t, names)
    if us <= 0:
        return None
    if spec.get("inferred"):
        return 100.0 * S.time_in(t, names, True) / T.busy_us(t["ops"])
    if "flops" not in spec:
        return 100.0 * us / T.busy_us(t["ops"])
    cell, steps = ctx["cell"], len(S.named(t, "train.step"))
    g = ctx["arch"].attention_geometry(cell.config)
    need = steps * g["layers"] * PRODUCTS[spec["flops"]] * flops.flash_flops(
        cell.traffic["batch"], g["heads"], cell.traffic["seq"],
        g["head_dim"]) / cell.chips
    return 100.0 * need / ctx["peaks"]["bf16_flops_per_s"] / (us / 1e6)
