"""PerfEvidence ledger: every perf measurement the repo produces, one store.

Perf artifacts are scattered across formats that each grew for one
consumer: probe ladders (``PROBE_*.json``, including ``ok:false`` rows
from a probe that died), bench rounds (``BENCH_*.json`` /
``BENCH_SERVE_*.json`` / ``BENCH_SESSION_*.json``), ``MFU_LAB_*.json``
tables, the kernel-autotune disk cache, the AOT
cache's per-program XLA ``cost_analysis`` stats (``PADDLE_AOT_STATS``),
per-rank runlogs, the serving flight recorder's step plans, and the
memory watcher's ring dumps (``profiler/memwatch.py``). This
module normalizes all of them into ONE schema-versioned JSONL ledger so
the profile-guided resolver (``tools/perf_resolve.py``) reads evidence
instead of re-profiling, and every flag decision can cite the row ids
that justify it.

Design rules:

  * **stdlib-only** — importable through the lint.py-style jax-free
    package bootstrap (``tools/`` consumers never pay a framework
    import). The only intra-package imports are ``profiler.instrument``
    (metrics, itself stdlib) and a *lazy, best-effort*
    ``aot.fingerprint.package_digest`` for the config fingerprint.
  * **rows are content-addressed** — ``id = <source>:<round>:<digest>``
    where the digest covers the normalized payload but NOT file mtimes,
    so rebuilding the ledger from the same committed artifacts in a
    fresh clone yields byte-identical ids (resolver determinism).
  * **malformed input is quarantined, never raised** — a torn JSONL
    line, a truncated artifact, or a wrong-schema row lands in
    ``Ledger.quarantined`` with its error; readers keep going.
  * **failure is first-class evidence** — a probe ``ok:false`` row
    ingests as a ``probe_failed`` row so the resolver knows the last
    hardware window died rather than silently trusting an older one
    forever.

The probe, bench, bench-session, bench-serve and mfu_lab formats have no
writer in the tree since ``bench.py`` and the CPU serving bench were
removed (ROADMAP D2 decides what the resolver still reads); their
ingestors keep recorded artifacts readable, and the repo root holds
none: the three the tests ingest are under ``tests/data/evidence/``.

Row shape (schema 1)::

    {"schema": 1, "id": "probe:r04:ab12...", "source": "probe",
     "kind": "probe_step", "round": "r04", "ok": true,
     "device_kind": "TPU v5 lite", "topology": {...} | null,
     "config": {"flags": {...} | null, "package_digest": "..."|null},
     "file": "PROBE_r04.json", "mtime_utc": "...", "data": {...}}

The attribution half (:func:`roofline`, :func:`attribute_step`) joins
runlog wall times with per-program flops/bytes_accessed to decompose a
step into compute/collective/data/host fractions and place each program
on the roofline (compute- vs memory-bound) — the Ragged Paged Attention
paper's kernel-efficiency accounting applied to whole steps.
"""
from __future__ import annotations

import glob
import hashlib
import json
import os
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import instrument as _instr

__all__ = [
    "SCHEMA_VERSION", "SOURCES", "Ledger", "read_rows", "row_id",
    "make_row", "ingest_probe", "ingest_bench", "ingest_bench_serve",
    "ingest_bench_session", "ingest_mfu_lab", "ingest_autotune",
    "ingest_aot_stats", "ingest_runlog", "ingest_flight", "ingest_mem",
    "ingest_path",
    "scan_repo", "build_ledger", "round_order", "roofline",
    "attribute_step", "PEAK_BYTES_PER_S", "peak_flops_for_kind",
    "device_identity",
]

SCHEMA_VERSION = 1

#: every source tag a row may carry (perf_evidence_rows_total{source})
SOURCES = ("probe", "bench", "bench_serve", "bench_session", "mfu_lab",
           "autotune", "aot_stats", "runlog", "flight", "mem")

# -- peak tables (published figures; a device that is not listed has no peak:
#    the lookups answer None, never a default) ----------------------------------
#: bf16 peak FLOP/s by device-kind substring.
PEAK_FLOPS = (
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v5", 459e12), ("v4", 275e12),
    ("v6", 918e12), ("trillium", 918e12),
)

#: HBM bandwidth (bytes/s) by device-kind substring — the roofline's
#: memory ceiling. Public figures: v5e 819 GB/s, v5p 2765 GB/s,
#: v4 1228 GB/s, v6e 1640 GB/s.
PEAK_BYTES_PER_S = (
    ("v5 lite", 8.19e11), ("v5litepod", 8.19e11), ("v5e", 8.19e11),
    ("v5p", 2.765e12), ("v5", 2.765e12), ("v4", 1.228e12),
    ("v6", 1.64e12), ("trillium", 1.64e12),
)


def _lookup_peak(table, device_kind: Optional[str]) -> Optional[float]:
    kind = (device_kind or "").lower()
    for sub, v in table:
        if sub in kind:
            return v
    return None


def peak_flops_for_kind(device_kind: Optional[str]) -> Optional[float]:
    return _lookup_peak(PEAK_FLOPS, device_kind)


def peak_bytes_for_kind(device_kind: Optional[str]) -> Optional[float]:
    return _lookup_peak(PEAK_BYTES_PER_S, device_kind)


def device_identity() -> Tuple[Optional[str], Optional[str]]:
    """(device_kind, platform) of the local backend, or (None, None) —
    the one best-effort jax probe shared by every perf-config consumer
    (flags.apply_perf_config, aot stats). Lazy and never raising: a
    perf layer must not make startup wait on (or die with) hardware."""
    try:
        import jax
        devices = jax.devices()
        if devices:
            return (getattr(devices[0], "device_kind", None),
                    devices[0].platform)
    except Exception:  # noqa: BLE001 — identity is metadata, not data
        pass
    return (None, None)


# -- row construction ---------------------------------------------------------
def _digest(payload) -> str:
    blob = json.dumps(payload, sort_keys=True, default=str).encode()
    return hashlib.blake2b(blob, digest_size=8).hexdigest()


def row_id(source: str, rnd: Optional[str], kind: str, file: str,
           data: Dict[str, Any]) -> str:
    """Content-addressed row id. Mtimes and ingest timestamps stay OUT of
    the digest: the same committed artifact must produce the same id in
    every clone (the resolver's byte-identical-output contract)."""
    return (f"{source}:{rnd or 'x'}:"
            f"{_digest({'kind': kind, 'file': file, 'data': data})}")


def _mtime_utc(path: str) -> Optional[str]:
    try:
        return time.strftime("%Y-%m-%dT%H:%M:%SZ",
                             time.gmtime(os.path.getmtime(path)))
    except OSError:
        return None


def _config_fingerprint(flags_map: Optional[Dict[str, Any]]
                        ) -> Dict[str, Any]:
    """Config-identity component for a row: the flag map the measurement
    ran under (when the artifact recorded one) plus the package source
    digest — reusing ``aot/fingerprint.py``'s component so evidence and
    AOT artifacts agree on what "same code" means. Best-effort: under
    the bare-package bootstrap the digest import can fail; evidence
    carries null rather than refusing to ingest."""
    pkg = None
    try:
        from ..aot.fingerprint import package_digest
        pkg = package_digest()
    except Exception:  # noqa: BLE001 — fingerprint is identity, not data
        pkg = None
    return {"flags": dict(sorted(flags_map.items())) if flags_map else None,
            "package_digest": pkg}


def make_row(source: str, kind: str, data: Dict[str, Any], *,
             file: str = "", rnd: Optional[str] = None, ok: bool = True,
             device_kind: Optional[str] = None,
             topology: Optional[Dict[str, Any]] = None,
             flags_map: Optional[Dict[str, Any]] = None,
             mtime_utc: Optional[str] = None) -> Dict[str, Any]:
    if source not in SOURCES:
        raise ValueError(f"unknown evidence source {source!r} "
                         f"(want one of {SOURCES})")
    return {
        "schema": SCHEMA_VERSION,
        "id": row_id(source, rnd, kind, file, data),
        "source": source,
        "kind": kind,
        "round": rnd,
        "ok": bool(ok),
        "device_kind": device_kind,
        "topology": topology,
        "config": _config_fingerprint(flags_map),
        "file": file,
        "mtime_utc": mtime_utc,
        "data": data,
    }


def round_order(rnd: Optional[str]) -> Tuple[int, str]:
    """Sort key for round tags: r01 < r04 < ... < 'latest'; unknown tags
    sort below every numbered round (deterministic, string-tiebroken)."""
    if rnd is None:
        return (-1, "")
    if rnd == "latest":
        return (1 << 30, rnd)
    if rnd.startswith("r"):
        try:
            return (int(rnd[1:]), rnd)
        except ValueError:
            pass
    return (-1, rnd)


def _round_from_name(path: str) -> Optional[str]:
    base = os.path.basename(path)
    stem = base.rsplit(".", 1)[0]
    for part in reversed(stem.split("_")):
        low = part.lower()
        if low == "latest":
            return "latest"
        if len(low) >= 2 and low[0] == "r" and low[1:].isdigit():
            return low
    return None


# -- the ledger ---------------------------------------------------------------
class _WriterLock:
    """Cross-process writer lock (``<ledger>.lock``, flock). Readers
    never take it (reads tolerate torn tails); writers serialize so a
    ``merge`` rewrite can never drop a concurrently appended line. On
    platforms without fcntl the lock degrades to a no-op."""

    def __init__(self, path: str):
        self._path = path + ".lock"
        self._f = None

    def __enter__(self):
        try:
            import fcntl
            self._f = open(self._path, "a")
            fcntl.flock(self._f.fileno(), fcntl.LOCK_EX)
        except Exception:  # noqa: BLE001 — locking is best-effort
            self._f = None
        return self

    def __exit__(self, *exc):
        if self._f is not None:
            try:
                self._f.close()
            except OSError:
                pass
        return False


class Ledger:
    """Atomic JSONL evidence store.

    ``merge()`` is the bulk path: under the writer lock, the file's
    existing CONTENT is preserved verbatim (lines that failed to parse
    stay on disk for postmortems — quarantine is a read-side judgment,
    not destruction) and only new rows are appended, via tmp+rename so
    a killed writer can never truncate the committed file.
    ``append_line()`` is the hot path (one locked ``write()`` of one
    line in append mode — what ``RunLog`` uses per step). Reading never
    raises on bad input: malformed lines and wrong-schema rows land in
    ``self.quarantined`` as ``{"line": n, "error": ..., "text": ...}``.
    """

    def __init__(self, path: str):
        self.path = path
        self.quarantined: List[Dict[str, Any]] = []

    # -- read ----------------------------------------------------------------
    def rows(self) -> List[Dict[str, Any]]:
        rows, self.quarantined = read_rows(self.path)
        return rows

    def ids(self) -> set:
        return {r["id"] for r in self.rows()}

    # -- write ---------------------------------------------------------------
    def merge(self, new_rows: Iterable[Dict[str, Any]]) -> int:
        """Dedupe-by-id merge with the tmp+rename discipline (same as
        the bench artifact writes). Returns rows actually added."""
        with _WriterLock(self.path):
            existing = self.rows()
            try:
                with open(self.path) as f:
                    content = f.read()
            except OSError:
                content = ""
            if content and not content.endswith("\n"):
                content += "\n"
            seen = {r["id"] for r in existing}
            added = []
            for row in new_rows:
                if row.get("id") not in seen:
                    seen.add(row["id"])
                    added.append(row)
            if not added:
                return 0
            tmp = f"{self.path}.tmp-{os.getpid()}"
            try:
                with open(tmp, "w") as f:
                    f.write(content)
                    for row in added:
                        f.write(json.dumps(row, sort_keys=True) + "\n")
                os.replace(tmp, self.path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        by_source: Dict[str, int] = {}
        for row in added:
            by_source[row["source"]] = by_source.get(row["source"], 0) + 1
        for source, n in sorted(by_source.items()):
            _instr.record_perf_evidence_rows(source, n)
        return len(added)

    def append_line(self, row: Dict[str, Any]) -> None:
        """Single-line append for per-step writers (RunLog): one write
        call per line, flushed — a concurrent reader sees whole lines or
        nothing, and a torn final line is quarantined by read_rows."""
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with _WriterLock(self.path):
            with open(self.path, "a") as f:
                f.write(json.dumps(row, sort_keys=True) + "\n")
                f.flush()
        _instr.record_perf_evidence_rows(row.get("source", "runlog"), 1)


def read_rows(path: str) -> Tuple[List[Dict[str, Any]],
                                  List[Dict[str, Any]]]:
    """Parse a ledger file -> (rows, quarantined). Missing file -> both
    empty. Never raises on content: unparseable lines, non-dict rows,
    wrong/missing schema versions, and rows without an id are
    quarantined with their line number and error."""
    rows: List[Dict[str, Any]] = []
    quarantined: List[Dict[str, Any]] = []
    try:
        with open(path) as f:
            lines = f.read().splitlines()
    except OSError:
        return [], []
    for n, line in enumerate(lines, 1):
        if not line.strip():
            continue
        try:
            row = json.loads(line)
        except ValueError as e:
            quarantined.append({"line": n, "error": f"json: {e}",
                                "text": line[:200]})
            continue
        if not isinstance(row, dict):
            quarantined.append({"line": n, "error": "row is not an object",
                                "text": line[:200]})
        elif row.get("schema") != SCHEMA_VERSION:
            quarantined.append({"line": n,
                                "error": f"schema {row.get('schema')!r} != "
                                         f"{SCHEMA_VERSION}",
                                "text": line[:200]})
        elif not isinstance(row.get("id"), str) or not row["id"]:
            quarantined.append({"line": n, "error": "missing row id",
                                "text": line[:200]})
        else:
            rows.append(row)
    return rows, quarantined


# -- ingestors (one per artifact format; each returns normalized rows) --------
def _load_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _num(v) -> Optional[float]:
    """Tolerant numeric coercion for artifact payloads: a hand-edited
    or future-format value that is not a number must degrade the field,
    never raise out of an ingestor (module contract)."""
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def ingest_probe(path: str) -> List[Dict[str, Any]]:
    """PROBE_*.json — the hardware probe ladder. An ``ok:false`` payload
    (the probe died before its first tier) is a first-class
    ``probe_failed`` row:
    the resolver uses it to mark decisions as carried-from-an-older-
    window instead of silently fresh."""
    doc = _load_json(path)
    if not isinstance(doc, dict):
        return []
    rnd = _round_from_name(path)
    base = os.path.basename(path)
    mt = _mtime_utc(path)
    if not doc.get("ok"):
        data = {"error": str(doc.get("error", "unknown"))[:500]}
        return [make_row("probe", "probe_failed", data, file=base, rnd=rnd,
                         ok=False, device_kind=doc.get("device_kind"),
                         mtime_utc=mt)]
    dk = doc.get("device_kind")
    topo = {"platform": doc.get("platform"), "device_kind": dk}
    rows = []
    for tier, step in sorted((doc.get("steps") or {}).items()):
        if not isinstance(step, dict):
            continue
        data = {"tier": tier}
        for k, v in sorted(step.items()):
            if k == "ok":
                continue
            data[k] = str(v)[:500] if k == "error" else v
        rows.append(make_row("probe", "probe_step", data, file=base,
                             rnd=rnd, ok=bool(step.get("ok")),
                             device_kind=dk, topology=topo, mtime_utc=mt))
    return rows


def _bench_parsed_rows(parsed: Dict[str, Any], base: str,
                       rnd: Optional[str], mt: Optional[str]
                       ) -> List[Dict[str, Any]]:
    extra = parsed.get("extra") or {}
    src = extra.get("value_source") or {}
    dk = extra.get("device") or src.get("device")
    live = "error" not in extra and (_num(parsed.get("value")) or 0) > 0
    data = {
        "metric": parsed.get("metric"),
        "value": parsed.get("value"),
        "unit": parsed.get("unit"),
        "vs_baseline": parsed.get("vs_baseline"),
        "mfu": extra.get("mfu") or src.get("mfu"),
        "config": extra.get("config") or src.get("config"),
        "error": str(extra.get("error"))[:500] if extra.get("error")
        else None,
        "carried_from": src.get("file"),
    }
    rows = [make_row("bench", "train_throughput", data, file=base, rnd=rnd,
                     ok=live, device_kind=dk, mtime_utc=mt)]
    for tag, att in sorted((extra.get("attempts") or {}).items()):
        if not isinstance(att, dict):
            continue
        adata = {"tag": tag, "tps": att.get("tps"), "mfu": att.get("mfu"),
                 "error": str(att.get("error"))[:500]
                 if att.get("error") else None}
        rows.append(make_row("bench", "bench_attempt", adata, file=base,
                             rnd=rnd, ok=att.get("error") is None,
                             device_kind=dk, mtime_utc=mt))
    return rows


def ingest_bench(path: str) -> List[Dict[str, Any]]:
    """BENCH_rNN.json — the driver wrapper ({"n","cmd","rc","tail",
    "parsed"}) around one bench line. The parsed payload is the
    evidence; a value carried forward from an older session ingests
    ok:false with the carried-from file recorded."""
    doc = _load_json(path)
    if not isinstance(doc, dict):
        return []
    parsed = doc.get("parsed")
    if not isinstance(parsed, dict):
        # a crashed round left only the traceback tail: that is still
        # evidence (the round produced no number)
        data = {"rc": doc.get("rc"),
                "tail": str(doc.get("tail", ""))[-500:]}
        return [make_row("bench", "bench_crashed", data,
                         file=os.path.basename(path),
                         rnd=_round_from_name(path), ok=False,
                         mtime_utc=_mtime_utc(path))]
    return _bench_parsed_rows(parsed, os.path.basename(path),
                              _round_from_name(path), _mtime_utc(path))


def ingest_bench_session(path: str) -> List[Dict[str, Any]]:
    """BENCH_SESSION_rNN.json — a successful hardware training session.
    The train_session row is the MFU anchor perf_report diffs against."""
    doc = _load_json(path)
    if not isinstance(doc, dict) or "metric" not in doc:
        return []
    rows = _bench_parsed_rows(doc, os.path.basename(path),
                              _round_from_name(path), _mtime_utc(path))
    for row in rows:
        row_data = dict(row["data"])
        row["source"] = "bench_session"
        row["kind"] = ("train_session" if row["kind"] == "train_throughput"
                       else row["kind"])
        row["id"] = row_id("bench_session", row["round"], row["kind"],
                           row["file"], row_data)
    return rows


def ingest_bench_serve(path: str) -> List[Dict[str, Any]]:
    """BENCH_SERVE_*.json — serving bench (static vs continuous,
    spec vs nonspec). These run on CPU in CI, so device_kind stays
    null unless the artifact says otherwise — the resolver only emits
    decisions for rows with a known device."""
    doc = _load_json(path)
    if not isinstance(doc, dict) or doc.get("bench") != "serve":
        return []
    rnd = doc.get("tag") or _round_from_name(path)
    base = os.path.basename(path)
    mt = _mtime_utc(path)
    dk = doc.get("device_kind")
    common = {"model": doc.get("model"), "workload": doc.get("workload"),
              "engine": doc.get("engine"), "fast": doc.get("fast")}
    rows = []
    for mode in ("static", "continuous", "nonspec", "spec"):
        res = doc.get(mode)
        if not isinstance(res, dict):
            continue
        data = dict(common)
        data["mode"] = mode
        for k, v in sorted(res.items()):
            if isinstance(v, (int, float, str, bool, type(None))):
                data[k] = v
        rows.append(make_row("bench_serve", "serve_bench", data, file=base,
                             rnd=rnd, ok=True, device_kind=dk,
                             mtime_utc=mt))
    summary = {k: doc.get(k) for k in ("vs_static", "vs_nonspec")
               if doc.get(k) is not None}
    if summary:
        rows.append(make_row("bench_serve", "serve_summary", summary,
                             file=base, rnd=rnd, ok=True, device_kind=dk,
                             mtime_utc=mt))
    return rows


def rows_from_mfu_lab(results: Dict[str, Any], rnd: Optional[str],
                      base: str, mtime_utc: Optional[str] = None,
                      device_kind: Optional[str] = None
                      ) -> List[Dict[str, Any]]:
    """Normalize an in-memory mfu_lab results table (tag -> bench row).
    Shared by ingest_mfu_lab (MFU_LAB_*.json) and the tests."""
    rows = []
    for tag, res in sorted((results or {}).items()):
        if not isinstance(res, dict):
            continue
        extra = res.get("extra") or {}
        err = res.get("error") or extra.get("error")
        data = {"tag": tag, "tps": res.get("value"),
                "mfu": extra.get("mfu"),
                "pallas_fused": bool(extra.get("pallas_fused")),
                "from": res.get("from"),
                "wall_s": res.get("wall_s"),
                "error": str(err)[:500] if err else None}
        rows.append(make_row(
            "mfu_lab", "lab_rung", data, file=base, rnd=rnd,
            ok=err is None and bool(res.get("value")),
            device_kind=device_kind or extra.get("device"),
            mtime_utc=mtime_utc))
    return rows


def ingest_mfu_lab(path: str) -> List[Dict[str, Any]]:
    doc = _load_json(path)
    if not isinstance(doc, dict):
        return []
    return rows_from_mfu_lab(doc, _round_from_name(path),
                             os.path.basename(path), _mtime_utc(path))


def ingest_autotune(path: str, device_kind: Optional[str] = None
                    ) -> List[Dict[str, Any]]:
    """AUTOTUNE_CACHE.json — kernels/autotune.py's disk cache:
    {json[(kernel, *signature)]: [block_q, block_k]}. Real signatures
    ((sq, sk, head_dim, dtype, causal) — flash_attention._tune_signature)
    carry NO device element, so the caller supplies ``device_kind``:
    ``build_ledger`` passes the device of the newest successful probe in
    the same root (the probe is what wrote the cache). A device-kind-
    looking signature element still wins when present."""
    doc = _load_json(path)
    if not isinstance(doc, dict):
        return []
    base = os.path.basename(path)
    mt = _mtime_utc(path)
    rows = []
    for dkey, config in sorted(doc.items()):
        try:
            key = json.loads(dkey)
        except ValueError:
            continue
        if not isinstance(key, list) or not key:
            continue
        kernel, sig = str(key[0]), key[1:]
        dk = next((s for s in sig if isinstance(s, str) and
                   any(t in s.lower() for t in ("tpu", "cpu", "gpu", "v5",
                                                "v4", "v6"))), None) \
            or device_kind
        data = {"kernel": kernel, "signature": sig,
                "block": list(config) if isinstance(config, (list, tuple))
                else config}
        rows.append(make_row("autotune", "autotune_winner", data,
                             file=base, rnd=_round_from_name(path),
                             device_kind=dk, mtime_utc=mt))
    return rows


def ingest_aot_stats(path: str, device_kind: Optional[str] = None
                     ) -> List[Dict[str, Any]]:
    """PADDLE_AOT_STATS files — per-program hit/miss/fallback counts and
    the XLA cost_analysis (flops / bytes_accessed) aot/cache.py records
    at export. The cost rows are the attribution side's program table."""
    doc = _load_json(path)
    if not isinstance(doc, dict) or "programs" not in doc:
        return []
    base = os.path.basename(path)
    rnd = _round_from_name(path)
    mt = _mtime_utc(path)
    dk = doc.get("device_kind") or device_kind  # own stamp beats the hint
    rows = []
    for name, prog in sorted((doc.get("programs") or {}).items()):
        if not isinstance(prog, dict):
            continue
        data = {"program": name,
                "hits": prog.get("hits"), "misses": prog.get("misses"),
                "fallbacks": prog.get("fallbacks"),
                "cost": dict(prog["cost"]) if isinstance(prog.get("cost"),
                                                         dict) else None}
        if isinstance(prog.get("mem"), dict):
            # static memory footprint (aot/cache.py memory_analysis) —
            # added ONLY when present so pre-mem artifacts keep their
            # content-addressed row ids (ledger stability across rebuilds)
            data["mem"] = dict(prog["mem"])
        rows.append(make_row("aot_stats", "program_cost", data, file=base,
                             rnd=rnd, ok=data["cost"] is not None,
                             device_kind=dk, mtime_utc=mt))
    return rows


def ingest_runlog(path: str) -> List[Dict[str, Any]]:
    """runlog_rank*.jsonl — one runlog_meta row (flops/peak) plus ONE
    runlog_summary row (count, mean/last step time, last mfu): a 10k-step
    log must not become 10k ledger rows. Live per-step evidence goes
    through RunLog's own PADDLE_PERF_EVIDENCE append, not this."""
    base = os.path.basename(path)
    rnd = _round_from_name(path)
    mt = _mtime_utc(path)
    meta: Optional[Dict[str, Any]] = None
    steps: List[Dict[str, Any]] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail line: the summary still lands
                if rec.get("kind") == "meta":
                    meta = rec
                elif rec.get("kind") == "step":
                    steps.append(rec)
    except OSError:
        return []
    rows = []
    dk = (meta or {}).get("device_kind")
    if meta is not None:
        data = {"rank": meta.get("rank"), "world": meta.get("world"),
                "flops_per_step": meta.get("flops_per_step"),
                "peak_flops": meta.get("peak_flops")}
        rows.append(make_row("runlog", "runlog_meta", data, file=base,
                             rnd=rnd, device_kind=dk, mtime_utc=mt))
    if steps:
        times = [s["step_time_ms"] for s in steps
                 if isinstance(s.get("step_time_ms"), (int, float))]
        last = steps[-1]
        data = {"steps": len(steps),
                "mean_step_time_ms": (round(sum(times) / len(times), 3)
                                      if times else None),
                "last_step": {k: last.get(k) for k in
                              ("step", "step_time_ms", "loss", "tokens",
                               "tokens_per_s", "mfu")}}
        rows.append(make_row("runlog", "runlog_summary", data, file=base,
                             rnd=rnd, device_kind=dk, mtime_utc=mt))
    return rows


def ingest_flight(path: str) -> List[Dict[str, Any]]:
    """Serving flight-recorder dumps (serving/obs.py): one step_plan row
    summarizing the ring — why the dump fired, the last step's plan
    (budget split / admission / pool / spec outcome), and the SLO
    snapshot at dump time."""
    doc = _load_json(path)
    if not isinstance(doc, dict) or "steps" not in doc or \
            "reason" not in doc:
        return []
    steps = doc.get("steps") or []
    tel = doc.get("telemetry") or {}
    # best-effort ingest of foreign-generation dumps: the evidence
    # plane reports whatever a partial/older record carries and must
    # never crash on it — the blessed exception to "required keys are
    # read with []" (WIR103), scoped to exactly these two reads
    reason = doc.get("reason")  # tpu-lint: disable=WIR103
    detail = doc.get("detail")  # tpu-lint: disable=WIR103
    data = {"reason": reason,
            "detail": detail,
            "buffered_steps": len(steps),
            "last_step": steps[-1] if steps else None,
            "slo": tel.get("slo"),
            "requests": tel.get("requests")}
    return [make_row("flight", "step_plan", data,
                     file=os.path.basename(path),
                     rnd=_round_from_name(path),
                     ok=reason == "manual",
                     mtime_utc=_mtime_utc(path))]


def ingest_mem(path: str) -> List[Dict[str, Any]]:
    """Memory-watcher dumps (profiler/memwatch.py): one ``mem_snapshot``
    row summarizing the ring — why the dump fired, the last snapshot's
    pool split, and the high watermarks. ``tools/mem_report.py`` joins
    these with the AOT ``memory_analysis`` rows into the per-chip
    budget breakdown. An anomaly-triggered dump (near_oom) ingests
    ``ok: false`` — pressure is failure evidence, same convention as
    the serving flight recorder's rows."""
    doc = _load_json(path)
    if not isinstance(doc, dict) or doc.get("kind") != "memwatch" or \
            "steps" not in doc:
        return []
    steps = doc.get("steps") or []
    last = steps[-1] if steps else None
    data = {"reason": doc.get("reason"),
            "detail": doc.get("detail"),
            "buffered_steps": len(steps),
            "last": last,
            "watermarks": doc.get("watermarks"),
            "counters": doc.get("counters")}
    return [make_row("mem", "mem_snapshot", data,
                     file=os.path.basename(path),
                     rnd=_round_from_name(path),
                     ok=doc.get("reason") == "manual",
                     device_kind=doc.get("device_kind"),
                     mtime_utc=_mtime_utc(path))]


#: (glob pattern, ingestor) in scan order. BENCH_SESSION must come before
#: the BENCH_r* pattern would otherwise swallow it.
_SCAN = (
    ("PROBE_*.json", ingest_probe),
    ("BENCH_SESSION_*.json", ingest_bench_session),
    ("BENCH_SERVE_*.json", ingest_bench_serve),
    ("BENCH_r*.json", ingest_bench),
    ("MFU_LAB_*.json", ingest_mfu_lab),
    ("AUTOTUNE_CACHE.json", ingest_autotune),
    ("AOT_STATS_*.json", ingest_aot_stats),
    ("aot_stats_*.json", ingest_aot_stats),
    ("runlog_rank*.jsonl", ingest_runlog),
    ("flight_*.json", ingest_flight),
    ("FLIGHT_*.json", ingest_flight),
    ("memwatch_*.json", ingest_mem),
    ("MEM_WATCH_*.json", ingest_mem),
)


def ingest_path(path: str, device_hint: Optional[str] = None
                ) -> List[Dict[str, Any]]:
    """Dispatch one artifact file to its ingestor by filename pattern.
    ``device_hint`` flows to the ingestors whose artifacts carry no
    device identity of their own (the autotune cache; AOT stats files
    predating the device_kind stamp)."""
    import fnmatch
    base = os.path.basename(path)
    for pattern, fn in _SCAN:
        if fnmatch.fnmatchcase(base, pattern):
            if fn in (ingest_autotune, ingest_aot_stats):
                return fn(path, device_hint)
            return fn(path)
    return []


def scan_repo(root: str) -> List[str]:
    """Committed perf artifacts at the repo root, in deterministic order."""
    out = []
    for pattern, _ in _SCAN:
        out.extend(sorted(glob.glob(os.path.join(root, pattern))))
    seen = set()
    uniq = []
    for p in out:
        if p not in seen:
            seen.add(p)
            uniq.append(p)
    return uniq


def build_ledger(root: str, out_path: str,
                 extra_paths: Iterable[str] = ()
                 ) -> Tuple["Ledger", Dict[str, int]]:
    """Ingest every committed artifact under ``root`` (plus any
    ``extra_paths``) into the ledger at ``out_path`` (atomic merge).
    Returns (ledger, {basename: rows_ingested})."""
    ledger = Ledger(out_path)
    report: Dict[str, int] = {}
    rows: List[Dict[str, Any]] = []
    paths = list(scan_repo(root)) + [p for p in extra_paths if p]
    # device hint for device-less artifacts (the autotune cache): the
    # newest successful probe in this root is what wrote them
    hint = None
    hint_key = (-1, "")
    for path in paths:
        if os.path.basename(path).startswith("PROBE_"):
            doc = _load_json(path)
            if isinstance(doc, dict) and doc.get("ok") and \
                    doc.get("device_kind"):
                key = round_order(_round_from_name(path))
                if key > hint_key:
                    hint, hint_key = doc["device_kind"], key
    for path in paths:
        got = ingest_path(path, device_hint=hint)
        report[os.path.basename(path)] = len(got)
        rows.extend(got)
    ledger.merge(rows)
    return ledger, report


# -- step-time anatomy / roofline attribution ---------------------------------
def roofline(cost: Dict[str, Any], peak_flops: float,
             peak_bytes_per_s: Optional[float] = None) -> Dict[str, Any]:
    """Place one program's XLA cost_analysis on the roofline.

    intensity = flops / bytes_accessed; machine_balance = peak_flops /
    peak_bandwidth. ratio = intensity / machine_balance: >= 1 means the
    program has enough arithmetic per byte to be compute-bound on this
    device; < 1 means the memory system is the ceiling. Without a
    bandwidth figure only the modeled compute time is returned."""
    flops = float(cost.get("flops") or 0.0)
    nbytes = float(cost.get("bytes_accessed") or 0.0)
    out: Dict[str, Any] = {
        "flops": flops,
        "bytes_accessed": nbytes,
        "compute_s": flops / peak_flops if peak_flops else None,
        "memory_s": (nbytes / peak_bytes_per_s
                     if peak_bytes_per_s and nbytes else None),
        "intensity": flops / nbytes if nbytes else None,
        "machine_balance": (peak_flops / peak_bytes_per_s
                            if peak_bytes_per_s and peak_flops else None),
        "ratio": None,
        "bound": None,
    }
    if out["intensity"] is not None and out["machine_balance"]:
        out["ratio"] = out["intensity"] / out["machine_balance"]
        out["bound"] = "compute" if out["ratio"] >= 1.0 else "memory"
    modeled = [t for t in (out["compute_s"], out["memory_s"])
               if t is not None]
    out["modeled_s"] = max(modeled) if modeled else None
    return out


def attribute_step(wall_s: float, costs: Dict[str, Dict[str, Any]],
                   peak_flops: float,
                   peak_bytes_per_s: Optional[float] = None,
                   collective_s: float = 0.0, data_s: float = 0.0,
                   emit_metrics: bool = False) -> Dict[str, Any]:
    """Decompose one step's wall time into compute/collective/data/host.

    ``costs`` maps program name -> cost_analysis dict ({"flops",
    "bytes_accessed"}). The device (compute) component is the roofline
    envelope max(flops/peak_flops, bytes/peak_bw) summed over programs;
    collective_s and data_s are caller-measured (step-plan records /
    dataloader spans); host is the unmodeled remainder, floored at 0.
    Fractions are normalized over the component SUM (not wall) so they
    always total 1.0 even when the model overcommits a short wall time.

    With ``emit_metrics`` the fractions and per-program roofline ratios
    are published through ``instrument.record_perf_*`` (no-ops while the
    metrics plane is disabled)."""
    wall_s = float(wall_s)
    programs = {name: roofline(cost, peak_flops, peak_bytes_per_s)
                for name, cost in sorted((costs or {}).items())}
    device_s = sum(p["modeled_s"] or 0.0 for p in programs.values())
    flops = sum(p["flops"] for p in programs.values())
    collective_s = max(float(collective_s), 0.0)
    data_s = max(float(data_s), 0.0)
    host_s = max(wall_s - device_s - collective_s - data_s, 0.0)
    total = device_s + collective_s + data_s + host_s
    fractions = {
        "compute": device_s / total if total else 0.0,
        "collective": collective_s / total if total else 0.0,
        "data": data_s / total if total else 0.0,
        "host": host_s / total if total else 0.0,
    }
    out = {
        "wall_s": wall_s,
        "device_s": device_s,
        "collective_s": collective_s,
        "data_s": data_s,
        "host_s": host_s,
        "fractions": fractions,
        "programs": programs,
        "mfu": (flops / (wall_s * peak_flops)
                if wall_s > 0 and peak_flops else None),
    }
    if emit_metrics:
        for component, frac in sorted(fractions.items()):
            _instr.record_perf_step_fraction(component, frac)
        for name, p in programs.items():
            if p["ratio"] is not None:
                _instr.record_perf_roofline(name, p["ratio"])
    return out
