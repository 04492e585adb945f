"""Structured per-rank run log: JSONL step records for trajectory capture.

Each rank writes one ``.jsonl`` file: a ``meta`` header line followed by
one ``step`` record per training step. The schema is stable (bench.py and
BENCH_* trajectory tooling parse it):

  {"kind": "meta", "rank": 0, "world": 1, "unix_time": ...,
   "flops_per_step": ..., "peak_flops": ..., ...user meta}
  {"kind": "step", "step": 0, "step_time_ms": 12.3, "loss": 2.71,
   "tokens": 8192, "tokens_per_s": 665k, "mfu": 0.41, "unix_time": ...}

``mfu`` is a FLOPs-based model-flops-utilization estimate:
``flops_per_step / step_time_s / peak_flops`` — ``flops_per_step`` comes
from :func:`model_flops_per_step` (a jaxpr walk via hapi.dynamic_flops,
x3 for forward+backward) and ``peak_flops`` from the constructor. Missing
either leaves ``mfu: null`` rather than inventing a number.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

__all__ = ["RunLog", "read_runlog", "model_flops_per_step"]


def model_flops_per_step(net, input_size, dtypes=None) -> int:
    """FLOPs of one training step of ``net`` at ``input_size``: the traced
    forward cost x3 (backward ~= 2x forward, the standard estimate)."""
    from ..hapi.dynamic_flops import flops
    return 3 * int(flops(net, input_size, dtypes=dtypes))


class RunLog:
    """Append-only JSONL step log for one rank.

    path: file or directory (directory => ``<path>/runlog_rank<r>.jsonl``).
    """

    def __init__(self, path: str, rank: Optional[int] = None,
                 world: Optional[int] = None,
                 flops_per_step: Optional[float] = None,
                 peak_flops: Optional[float] = None,
                 meta: Optional[Dict] = None):
        if rank is None or world is None:
            from ..distributed.host_collectives import world_info
            r, w = world_info()
            rank = r if rank is None else rank
            world = w if world is None else world
        self.rank = int(rank)
        self.world = int(world)
        if os.path.isdir(path) or path.endswith(os.sep):
            os.makedirs(path, exist_ok=True)
            path = os.path.join(path, f"runlog_rank{self.rank}.jsonl")
        else:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
        self.path = path
        self.flops_per_step = flops_per_step
        self.peak_flops = peak_flops
        self._f = open(path, "w")
        self._step = 0
        self._last_t: Optional[float] = None
        header = {"kind": "meta", "rank": self.rank, "world": self.world,
                  "unix_time": time.time(),
                  "flops_per_step": flops_per_step,
                  "peak_flops": peak_flops}
        header.update(meta or {})
        self._write(header)
        # live perf-evidence stream: when PADDLE_PERF_EVIDENCE names a
        # ledger (tools/supervise.py threads one per generation), every
        # step record is appended as a normalized evidence row so the
        # crash report / resolver read measurements without re-parsing
        # rank logs. Best-effort: evidence must never break training.
        self._evidence = None
        self._device_kind = (meta or {}).get("device_kind") or \
            (meta or {}).get("device")
        ev_path = os.environ.get("PADDLE_PERF_EVIDENCE", "").strip()
        if ev_path:
            try:
                from . import evidence as _ev
                self._evidence = _ev.Ledger(ev_path)
                self._evidence.append_line(_ev.make_row(
                    "runlog", "runlog_meta",
                    {"rank": self.rank, "world": self.world,
                     "flops_per_step": flops_per_step,
                     "peak_flops": peak_flops},
                    file=os.path.basename(self.path),
                    device_kind=self._device_kind))
            except Exception:  # noqa: BLE001 — advisory stream only
                self._evidence = None

    def _write(self, rec: Dict) -> None:
        self._f.write(json.dumps(rec) + "\n")
        self._f.flush()

    def _mfu(self, step_time_ms: float) -> Optional[float]:
        if not self.flops_per_step or not self.peak_flops or \
                step_time_ms <= 0:
            return None
        achieved = self.flops_per_step / (step_time_ms / 1000.0)
        return achieved / self.peak_flops

    def log_step(self, step: Optional[int] = None,
                 step_time_ms: Optional[float] = None,
                 loss: Optional[float] = None,
                 tokens: Optional[int] = None, **extra) -> Dict:
        """Record one step. With ``step_time_ms=None`` the wall time since
        the previous ``log_step`` (or ``mark``) is used."""
        now = time.perf_counter()
        if step_time_ms is None and self._last_t is not None:
            step_time_ms = (now - self._last_t) * 1000.0
        self._last_t = now
        if step is None:
            step = self._step
        self._step = step + 1
        tokens_per_s = None
        if tokens is not None and step_time_ms:
            tokens_per_s = tokens / (step_time_ms / 1000.0)
        rec = {"kind": "step", "step": int(step),
               "step_time_ms": step_time_ms,
               "loss": None if loss is None else float(loss),
               "tokens": tokens, "tokens_per_s": tokens_per_s,
               "mfu": None if step_time_ms is None
               else self._mfu(step_time_ms),
               "unix_time": time.time()}
        rec.update(extra)
        self._write(rec)
        if self._evidence is not None:
            try:
                from . import evidence as _ev
                self._evidence.append_line(_ev.make_row(
                    "runlog", "train_step",
                    {k: rec.get(k) for k in
                     ("step", "step_time_ms", "loss", "tokens",
                      "tokens_per_s", "mfu")},
                    file=os.path.basename(self.path),
                    device_kind=self._device_kind))
            except Exception:  # noqa: BLE001 — advisory stream only
                self._evidence = None
        return rec

    def mark(self) -> None:
        """Start the wall-clock for the next ``log_step`` (call right
        before the first step so step 0 gets a time)."""
        self._last_t = time.perf_counter()

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def read_runlog(path: str) -> List[Dict]:
    """Parse a runlog JSONL file back into a list of record dicts."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
