"""ArtifactStore: checkpoint-grade persistence for exported programs.

The stock persistent XLA compile cache is unsafe for the CPU tests in
this sandbox (tests/conftest.py), and concurrent generations sharing one
directory can tear each other's entries. This store is the safe replacement, built on
the same integrity discipline as ``distributed/checkpoint.py``:

  * **atomic writes** — payload and meta land as ``.tmp-<pid>`` files,
    fsync'd, then renamed; a kill mid-write leaves only tmp garbage,
    swept by a later put once the writer pid is gone (``*.corrupt``
    quarantine postmortems are likewise capped at the newest few).
  * **commit point = the ledger** — an artifact exists only once its
    entry is in ``_GOOD.json`` (itself rewritten atomically). A payload
    file without a ledger entry is invisible to ``get`` — so a process
    killed between payload rename and ledger update never publishes a
    half-written artifact.
  * **per-artifact crc32 + nbytes** — recorded in the ledger at put
    time, verified on every get; a mismatch quarantines the entry
    (``*.corrupt`` rename + ledger removal) and raises
    ``ArtifactCorrupt`` so the caller falls back to a fresh compile.
  * **keep-N GC** — oldest entries (by a ledger-held monotonic sequence
    number, not wall time) evicted under the lock.
  * **cross-process lockfile** — ``_LOCK`` held via ``flock(2)``: the
    kernel releases it the instant the holder dies (no stale-pid
    heuristics, no break-the-lock races — a waiter can never unlink a
    peer's freshly acquired lock), and a live-but-hung holder simply
    times the waiter out into ``LockTimeout``, which the cache layer's
    fallback ladder absorbs. The holder's pid is written into the file
    for postmortems only. Single-host by construction, like the
    supervisor it serves.

Chaos probes: ``aot.export`` (control faults between tmp write and
commit — the killed-mid-write drill), ``aot.load`` (control faults on
the read path), ``aot.artifact_bytes`` (byte corruption/truncation of
the payload as it hits disk; the crc is computed over the TRUE bytes
first, so the corruption is detected at load like a real bad sector).

Stdlib-only on purpose: tools and subprocess drills can import this
module through the jax-free package bootstrap (see tools/supervise.py).
"""
from __future__ import annotations

import contextlib
import fcntl
import json
import logging
import os
import time
import zlib
from typing import Dict, Iterator, Optional, Tuple

from ..resilience import chaos

__all__ = ["ArtifactStore", "ArtifactError", "ArtifactMiss",
           "ArtifactCorrupt", "LockTimeout"]

LEDGER = "_GOOD.json"
LOCKFILE = "_LOCK"


class ArtifactError(RuntimeError):
    """Base class for store failures."""


class ArtifactMiss(ArtifactError):
    """Key absent from the last-good ledger."""


class ArtifactCorrupt(ArtifactError):
    """Ledger entry failed integrity verification (now quarantined)."""


class LockTimeout(ArtifactError):
    """Could not acquire the cross-process lock in time."""


def _wall_now() -> float:
    """Wall timestamp for ledger metadata (human postmortems only —
    ordering decisions use the ledger's seq counter, never this)."""
    return time.time()


class ArtifactStore:
    """One directory of exported-program artifacts with a last-good
    ledger. All mutation happens under the cross-process lock; reads go
    lock-free (every file they touch is rename-atomic)."""

    def __init__(self, root: str, keep: int = 16,
                 lock_timeout: float = 20.0):
        self.root = os.path.abspath(root)
        self.keep = int(keep)
        self.lock_timeout = float(lock_timeout)
        os.makedirs(self.root, exist_ok=True)

    # -- paths ----------------------------------------------------------------
    def _payload_path(self, key: str) -> str:
        return os.path.join(self.root, f"{key}.hlo")

    def _meta_path(self, key: str) -> str:
        return os.path.join(self.root, f"{key}.meta.json")

    def _ledger_path(self) -> str:
        return os.path.join(self.root, LEDGER)

    # -- cross-process lock ---------------------------------------------------
    @contextlib.contextmanager
    def _lock(self) -> Iterator[None]:
        """flock-held writer lock. The lockfile is created once and never
        unlinked (unlink+flock mixes reintroduce the break-a-fresh-lock
        race); the kernel drops the lock on release OR holder death, so
        a generation hard-killed mid-put cannot wedge the next one."""
        path = os.path.join(self.root, LOCKFILE)
        deadline = time.monotonic() + self.lock_timeout
        fd = os.open(path, os.O_CREAT | os.O_WRONLY, 0o644)
        try:
            while True:
                try:
                    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    break
                except OSError:
                    if time.monotonic() >= deadline:
                        raise LockTimeout(
                            f"aot store lock {path} held past "
                            f"{self.lock_timeout}s") from None
                    time.sleep(0.02)
            try:
                os.truncate(fd, 0)
                os.write(fd, str(os.getpid()).encode())  # postmortems only
            except OSError:
                pass
            try:
                yield
            finally:
                try:
                    fcntl.flock(fd, fcntl.LOCK_UN)
                except OSError:
                    pass
        finally:
            os.close(fd)

    # -- ledger ---------------------------------------------------------------
    def _read_ledger(self) -> Dict:
        try:
            with open(self._ledger_path()) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            return {"seq": 0, "entries": {}}
        if not isinstance(data, dict) or "entries" not in data:
            return {"seq": 0, "entries": {}}
        return data

    def _write_ledger(self, ledger: Dict) -> None:
        self._atomic_write(self._ledger_path(),
                           json.dumps(ledger, indent=1).encode())

    def _atomic_write(self, path: str, data: bytes) -> None:
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    # -- write path -----------------------------------------------------------
    def put(self, key: str, payload: bytes, meta: Optional[Dict] = None,
            name: str = "") -> str:
        """Publish one artifact under `key`. Returns the payload path.

        Commit order: payload tmp -> (chaos window) -> payload rename ->
        meta rename -> ledger update (the commit point) -> GC. A death
        anywhere before the ledger write leaves the key unpublished."""
        crc = zlib.crc32(payload) & 0xFFFFFFFF
        nbytes = len(payload)
        data = chaos.mangle("aot.artifact_bytes", payload)
        ppath = self._payload_path(key)
        mpath = self._meta_path(key)
        with self._lock():
            tmp = f"{ppath}.tmp-{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            # the killed-mid-write drill window: a `die` here leaves the
            # tmp file only; an `error` aborts before anything published
            chaos.site("aot.export")
            os.replace(tmp, ppath)
            self._atomic_write(
                mpath, json.dumps(meta or {}, indent=1,
                                  default=str).encode())
            ledger = self._read_ledger()
            seq = int(ledger.get("seq", 0)) + 1
            ledger["seq"] = seq
            ledger["entries"][key] = {
                "file": os.path.basename(ppath),
                "meta_file": os.path.basename(mpath),
                "crc32": crc,
                "nbytes": nbytes,
                "seq": seq,
                "name": name,
                "created_unix": _wall_now(),
            }
            doomed = self._gc(ledger)
            self._sweep_orphans(ledger)
            # ledger FIRST, then evicted files: the ledger is the commit
            # point, so a kill between the two leaves unreferenced files
            # (swept later) — never a ledger entry pointing at nothing,
            # which the next get() would mislabel a corruption.
            self._write_ledger(ledger)
            for path in doomed:
                try:
                    os.unlink(path)
                except OSError:
                    pass
        return ppath

    def _gc(self, ledger: Dict) -> list:
        """Keep the newest ``keep`` entries by seq; drop the rest from
        the ledger and return their file paths for the caller to unlink
        AFTER the ledger lands (runs under the lock)."""
        entries = ledger["entries"]
        doomed: list = []
        if self.keep <= 0 or len(entries) <= self.keep:
            return doomed
        by_age = sorted(entries.items(), key=lambda kv: kv[1].get("seq", 0))
        for key, ent in by_age[:len(entries) - self.keep]:
            del entries[key]
            for base in (ent.get("file"), ent.get("meta_file")):
                if base:
                    doomed.append(os.path.join(self.root, base))
        return doomed

    def _sweep_orphans(self, ledger: Optional[Dict] = None,
                       keep_corrupt: int = 4) -> None:
        """Bound the directory's non-ledger litter (under the lock, on
        every put): ``*.tmp-<pid>`` left by a generation killed
        mid-write — the headline preemption scenario leaves one per
        kill — is removed once that pid is gone (single-host store, so
        a local liveness probe is authoritative); quarantined
        ``*.corrupt`` postmortem files are capped at the newest few by
        mtime; and payload/meta files no ledger entry references (a
        kill between ledger write and eviction unlink) are removed.
        Without this a long-lived shared cache dir grows without bound;
        with it, litter is bounded by (live writers + keep_corrupt)."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return
        referenced = {LEDGER, LOCKFILE}
        for ent in (ledger or {}).get("entries", {}).values():
            referenced.add(ent.get("file"))
            referenced.add(ent.get("meta_file"))
        corrupt = []
        for n in names:
            path = os.path.join(self.root, n)
            if ".tmp-" in n:
                pid_s = n.rsplit(".tmp-", 1)[1]
                if not pid_s.isdigit() or int(pid_s) == os.getpid():
                    continue
                try:
                    os.kill(int(pid_s), 0)
                except ProcessLookupError:
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
                except OSError:
                    pass  # e.g. EPERM: pid alive under another uid
            elif n.endswith(".corrupt"):
                try:
                    corrupt.append((os.path.getmtime(path), path))
                except OSError:
                    pass
            elif ledger is not None and n not in referenced and \
                    (n.endswith(".hlo") or n.endswith(".meta.json")):
                try:
                    os.unlink(path)
                except OSError:
                    pass
        if len(corrupt) > keep_corrupt:
            for _, path in sorted(corrupt)[:len(corrupt) - keep_corrupt]:
                try:
                    os.unlink(path)
                except OSError:
                    pass

    # -- read path ------------------------------------------------------------
    def get(self, key: str) -> Tuple[bytes, Dict]:
        """Return ``(payload, meta)`` for a ledger-good artifact.
        Raises ArtifactMiss when unpublished, ArtifactCorrupt (after
        quarantining) when integrity verification fails."""
        chaos.site("aot.load")
        ledger = self._read_ledger()
        ent = ledger["entries"].get(key)
        if ent is None:
            raise ArtifactMiss(f"aot artifact {key!r} not in ledger")
        ppath = os.path.join(self.root, ent["file"])
        try:
            with open(ppath, "rb") as f:
                payload = f.read()
        except OSError as e:
            self.quarantine(key)
            raise ArtifactCorrupt(
                f"aot artifact {key!r}: payload unreadable ({e})") from e
        if len(payload) != int(ent["nbytes"]) or \
                (zlib.crc32(payload) & 0xFFFFFFFF) != int(ent["crc32"]):
            self.quarantine(key)
            raise ArtifactCorrupt(
                f"aot artifact {key!r}: crc/nbytes mismatch "
                f"(got {len(payload)}B) — quarantined")
        try:
            with open(os.path.join(self.root, ent["meta_file"])) as f:
                meta = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            self.quarantine(key)
            raise ArtifactCorrupt(
                f"aot artifact {key!r}: meta unreadable ({e})") from e
        return payload, meta

    def contains(self, key: str) -> bool:
        return key in self._read_ledger()["entries"]

    def quarantine(self, key: str) -> None:
        """Remove `key` from the ledger and park its files as
        ``*.corrupt`` for postmortems. Never raises: it runs inside the
        cache layer's never-fatal fallback ladder, where a disk-full or
        read-only filesystem during the quarantine itself must still
        degrade to a fresh compile, not an I/O crash."""
        try:
            with self._lock():
                ledger = self._read_ledger()
                ent = ledger["entries"].pop(key, None)
                if ent is not None:
                    self._write_ledger(ledger)
                for base in ((ent or {}).get("file"),
                             (ent or {}).get("meta_file")):
                    if not base:
                        continue
                    src = os.path.join(self.root, base)
                    try:
                        os.replace(src, src + ".corrupt")
                    except OSError:
                        pass
        except Exception:  # noqa: BLE001 — see docstring
            logging.getLogger(__name__).warning(
                "aot store: quarantine of %r failed", key, exc_info=True)

    # -- introspection --------------------------------------------------------
    def keys(self) -> Dict[str, Dict]:
        """{key: ledger entry} snapshot of the published artifacts."""
        return dict(self._read_ledger()["entries"])

    def stats(self) -> Dict:
        entries = self._read_ledger()["entries"]
        return {
            "root": self.root,
            "artifacts": len(entries),
            "bytes": sum(int(e.get("nbytes", 0)) for e in entries.values()),
            "names": sorted({e.get("name", "") for e in entries.values()}),
        }
