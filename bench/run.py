"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: checks that JAX holds the chips the cell asks for (and exits
without a result if not), builds the system under test for the cell's
configuration with weights from the seed, warms the cell's shapes, measures
for ``--seconds``, compares what the timed path produced with the plain
reference, and prints one JSON object as the last line of standard output.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
profiler over a slice of the window and reports its per-layer metrics.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()        # set-up is counted from here

import argparse        # noqa: E402
import json            # noqa: E402
import os              # noqa: E402
import sys             # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Run:
    """Per-run state the kinds share with the harness."""

    def __init__(self, cell):
        from bench.lib.window import CompileCounter
        self.trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
        self.compiles = CompileCounter()
        self.setup_s = None
        self.notes = {}

    def note(self, **kw):
        self.notes.update(kw)

    def open_window(self):
        """Set-up ends here: everything before is ``setup_s``."""
        self.setup_s = time.perf_counter() - T_PROCESS
        self.compiles.seen.clear()
        self.compiles.armed = True

    def close_window(self):
        self.compiles.armed = False
        if self.compiles.seen:
            raise RuntimeError(
                f"{len(self.compiles.seen)} compilation events inside the "
                f"measured window: {sorted(set(self.compiles.seen))}")


def say(*a):
    print("[bench]", *a, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_device(cell):
    """The device as JAX reports it; SystemExit(2) with no result where it
    is not the accelerator, or holds fewer chips than the cell asks for."""
    from paddle_tpu.utils import chip
    from bench.lib import spec
    try:
        device = chip.require_tpu()
    except RuntimeError as e:
        say(str(e))
        raise SystemExit(2)
    if device["count"] < cell.chips:
        say(f"{cell.name} asks for {cell.chips} chips, JAX holds "
            f"{device['count']}")
        raise SystemExit(2)
    spec.peaks(device["kind"])          # an unknown chip is an error
    return device


def collect(cell, args, run, out, device):
    """The result object from what the kind measured."""
    from bench.lib import compare, spec, trace as tracelib
    measured = dict(out["measured"], setup_s=run.setup_s)
    correct, compared = compare.judge(out["numbers"], cell)
    metrics = {}
    result = {"correct": bool(correct and out["failed"] == 0),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": dict(
                  device, memory_peak_bytes=out["memory_peak_bytes"])}
    if not args.trace:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    else:
        sl = out["trace"]
        reduced = tracelib.reduce_dir(sl.dir, sl.t1 - sl.t0, sl.last_step - sl.first_step)
        ctx = {"cell": cell, "arch": cell.arch(), "measured": measured,
               "trace": reduced, "notes": run.notes,
               "peaks": spec.peaks(device["kind"])}
        for m in cell.per_layer():
            value = cell.reader(m["name"])(ctx, m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        for key in ("busy_s", "window_s", "trace_cut", "steps_kept"):
            result["device"][key] = reduced[key]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared       # last: each number beside its limit
    return result


def drive(cell, args):
    """One run of the cell's kind: (per-run state, what the kind returns)."""
    run = Run(cell)
    out = cell.kind().run(cell, args, run)
    say("set-up", round(run.setup_s, 1), "s; notes", json.dumps(run.notes))
    return run, out


def execute(cell, args, device):
    """Everything after the look for a chip: one run, its result object."""
    run, out = drive(cell, args)
    return collect(cell, args, run, out, device)


def start(cell):
    """What every process that runs a cell does first: the look for the
    chip, then the compile cache at its one place. Returns the device."""
    device = check_device(cell)
    import jax
    from paddle_tpu.utils import chip
    # every program, however quick to compile, is found again by the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say("device", json.dumps(device), "compile cache at",
        chip.enable_compile_cache())
    return device


def main(argv=None) -> int:
    args = parse(argv)
    from bench.lib import spec
    cell = spec.Cell(args.workload)
    device = start(cell)
    result = execute(cell, args, device)
    say("whole run", round(time.perf_counter() - T_PROCESS, 1), "s")
    say("correct", result["correct"])
    for name, c in result["compared"].items():
        say(f"compared {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
