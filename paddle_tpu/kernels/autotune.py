"""Kernel autotune: cached block-size selection for Pallas kernels.

Reference parity: paddle/phi/kernels/autotune/ (AutoTuneBase — time each
candidate kernel config once per input signature, cache the winner;
switch_autotune.h gates it behind a flag). TPU-native: the tunable is the
Pallas grid blocking (block_q/block_k for flash attention); timing ends in
a host fetch of the result and winners are cached
in-process and optionally on disk keyed by (kernel, device kind, shape
signature).
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..framework import flags

flags.define_flag("use_autotune", False,
                  "Time Pallas kernel block-size candidates on first use "
                  "(reference FLAGS_use_autotune).")

_cache: Dict[tuple, tuple] = {}
_cache_path: List[Optional[str]] = [
    os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE")]


def set_cache_path(path: Optional[str]):
    _cache_path[0] = path


def _load_disk() -> Dict[str, list]:
    p = _cache_path[0]
    if p and os.path.exists(p):
        try:
            with open(p) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}
    return {}


def _store_disk(disk: Dict[str, list]):
    p = _cache_path[0]
    if p:
        try:
            tmp = f"{p}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(disk, f)
            os.replace(tmp, p)  # atomic: a killed writer can't poison it
        except OSError:
            pass  # the disk cache is an optimization, never a failure


def _default_timer(fn: Callable[[], object]) -> float:
    np.asarray(fn()).ravel()[:1]  # compile + warm, SYNCHRONIZED (host fetch)
    t0 = time.perf_counter()
    out = fn()
    np.asarray(out).ravel()[:1]  # host fetch = true barrier
    return time.perf_counter() - t0


def pick(kernel: str, signature: Sequence, candidates: Sequence[tuple],
         run: Callable[[tuple], object],
         timer: Optional[Callable] = None) -> tuple:
    """Return the fastest candidate config for (kernel, signature).

    run(config) executes the kernel with that config; results are cached so
    each signature is tuned once per process (and per disk cache if set).
    When FLAGS_use_autotune is off, candidates[0] (the static default) wins
    without timing — reference switch_autotune behavior.
    """
    key = (kernel,) + tuple(signature)
    hit = _cache.get(key)
    if hit is not None and hit is not _MISS:
        return hit
    if not flags.flag("use_autotune"):
        # do NOT cache the untimed default: enabling the flag later must
        # still be able to tune this signature
        return tuple(candidates[0])
    disk = _load_disk()
    dkey = json.dumps(key)
    if dkey in disk:
        _cache[key] = tuple(disk[dkey])
        return _cache[key]
    t = timer or _default_timer
    best, best_dt = None, float("inf")
    for cand in candidates:
        try:
            dt = t(lambda c=cand: run(c))
        except Exception:  # noqa: BLE001 — invalid tiling: skip candidate
            continue
        if dt < best_dt:
            best, best_dt = tuple(cand), dt
    if best is None:
        best = tuple(candidates[0])
    _cache[key] = best
    # merge-on-write: concurrent ranks sharing the cache file must not drop
    # each other's winners (os.replace only prevents torn files)
    disk = {**_load_disk(), dkey: list(best)}
    _store_disk(disk)
    return best


_MISS = ("__miss__",)


def cached(kernel: str, signature: Sequence) -> Optional[tuple]:
    """Public cache lookup (used by traced call sites that cannot tune).
    Falls back to the disk cache so a decision tuned in one process
    reaches others (a later training job). Misses are
    memoized: the disk file is read at most once per signature, keeping
    the eager attention hot path free of file I/O. record() overwrites
    the sentinel, so an in-process tune is still picked up."""
    key = (kernel,) + tuple(signature)
    hit = _cache.get(key)
    if hit is _MISS:
        return None
    if hit is not None:
        return hit
    disk = _load_disk()
    dkey = json.dumps(key)
    if dkey in disk:
        _cache[key] = tuple(disk[dkey])
        return _cache[key]
    _cache[key] = _MISS
    return None


def record(kernel: str, signature: Sequence, config: Sequence):
    """Store an externally-measured winner (a caller that timed the
    candidates itself, or ``flags.apply_perf_config``), here and on disk
    for other processes."""
    key = (kernel,) + tuple(signature)
    _cache[key] = tuple(config)
    disk = {**_load_disk(), json.dumps(key): list(config)}
    _store_disk(disk)


def clear():
    _cache.clear()


def flash_block_candidates(sq: int, sk: int, head_dim: int,
                           itemsize: int = 2) -> List[tuple]:
    """(block_q, block_k) candidates for the flash kernels: 128-multiples
    that divide the sequence lengths (Mosaic tiling constraint) and whose
    forward working set fits the kernels' VMEM budget, by the one formula
    the kernels size themselves with (flash_pallas.tile_vmem_bytes). The
    untimed default comes first: what flash_pallas.choose_tiles gives
    these shapes, so switching autotune off changes nothing."""
    from . import flash_pallas as fp
    qs = [b for b in (128, 256, 512, 1024) if sq % b == 0] or [sq]
    ks = [b for b in (128, 256, 512, 1024) if sk % b == 0] or [sk]
    default = fp.choose_tiles("fwd", sq, sk, head_dim, itemsize)[:2]
    out = [(q, k) for q in qs for k in ks
           if fp.tile_vmem_bytes("fwd", q, k, head_dim, itemsize)
           <= fp.VMEM_BUDGET_BYTES]
    return [default] + sorted(c for c in out if c != default)


__all__ = ["pick", "cached", "record", "clear", "set_cache_path",
           "flash_block_candidates"]
