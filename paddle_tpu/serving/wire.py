"""The wire-contract registry + runtime sealing twin (wirecheck's ground truth).

Every record that crosses (or will cross) a process/host boundary —
KV-page hand-off exports, drain/replay manifests, fleet telemetry,
autoscale ledger events, flight dumps, checkpoint metadata — is declared
here, in ``WIRE_SCHEMAS``: one entry per record family with its version,
required/optional keys and per-key JSON-pure type specs. ROADMAP item
2's multi-host rungs (KV transport over ICI/DCN, the fleet prefix
directory) put these records on an actual wire, where "hash-chain keys
are ints/tuples" and "signals() is version-1 pinned" stop being folklore
and start being compatibility: the reference PaddlePaddle stack
delegates this to its ProcessGroup/TCPStore serialization layer; here
the contract is a literal both halves of wirecheck read.

The registry is a PURE LITERAL (``ast.literal_eval``-readable): the
static rules (``analysis/wire_rules.py``, WIR101..WIR106) parse it out
of this file's source without importing jax or the package, and the
runtime twin below loads it live — ``analysis/wirecheck.py`` (WIR520)
pins the two views byte-identical, so they cannot drift.

Runtime twin: ``seal(record, family)`` at every producing seam
(``KVBlockPool.export_pages``/``import_pages``, ``build_manifest``/
``replay_manifest``, ``FleetObserver.signals``, the autoscaler ledger,
``save_state_dict``'s metadata). Disarmed (the default) it is a single
list-index check and returns the record untouched (microbench-pinned in
``tests/test_wirecheck.py``). Armed — ``PADDLE_WIRECHECK=1`` or
``wire.arm()`` — it validates the record against ``WIRE_SCHEMAS`` and
raises ``WireContractViolation`` AT THE SEAM THAT PRODUCED the bad
record, not three hops later in a consumer that can only report a
mangled file. Violation messages are byte-stable (sorted key lists, no
addresses/timestamps): the chaos drill pins them.

Schema evolution: each family pins a hash of its key-set + type specs
per version in ``key_hashes``. Editing a schema without bumping the
version (and appending a new pin) trips WIR511 in ``wirecheck.py`` and
the version-bump test — the same discipline a cross-host peer holds you
to, enforced before the peer exists.

Stdlib-only on purpose: the lint driver and the jax-free bootstrap load
this module standalone (by file path), exactly like ``locking.py``.
"""
from __future__ import annotations

import os
import zlib
from typing import Any, Dict, Optional

__all__ = ["WIRE_SCHEMAS", "NON_WIRE_SINKS", "WireContractViolation",
           "seal", "validate", "arm", "armed", "key_hash"]


# -- the wire-record contract registry ----------------------------------------
# One entry per record family. Pure literal — ast.literal_eval-readable
# (the static rules parse it; no computed values, no interpolation).
#
# Per-key type specs (the wire-pure vocabulary):
#   int / float / str / bool / none   exact scalar types (bool is NOT an
#                                     int here; numpy scalars are NOT
#                                     floats — strict type(), the drift
#                                     WIR101 exists for)
#   number                            int or float
#   dict / list                       JSON-pure container, deep-checked
#   json                              any JSON-pure value (opaque field)
#   list[X]                           list/tuple of X
#   prefix_keys                       hash-chain/affinity keys: a list of
#                                     int tuples (lists after a JSON
#                                     round-trip) — ints ONLY, the
#                                     WIR105 position
#   device                            device-array payload riding NEXT TO
#                                     the record (the ICI plane half of a
#                                     KV hand-off); exempt from JSON
#                                     purity, stripped before any dump
#   a|b                               union of the above
#
# Static binding (how the WIR1xx rules find the code that owns a
# family, spelled as the last two path components :: function name):
#   builders        functions that CONSTRUCT the record
#   consumers       (function, variable) pairs that READ it by key
#   item_consumers  same, for the per-row variable of item_key families
#   sinks           functions that WRITE it (json.dump/_atomic_json) —
#                   the registry-drift test walks the serving tier and
#                   asserts every dump call site maps to one of these
WIRE_SCHEMAS = {
    "kv_export_record": {
        "family": "kv_export_record",
        "version": 1,
        "version_key": "version",
        "required": {
            "version": "int",
            "num_pages": "int",
            "n_tokens": "int",
            "block_size": "int",
            "keys": "prefix_keys",
            "tokens": "list[int]",
        },
        "optional": {
            # the device half of the hand-off (ServingEngine.
            # _export_request): page contents, collective-sent on a
            # real topology — never JSON-dumped with the record
            "k": "device",
            "v": "device",
        },
        "item_key": None,
        "item_required": {},
        "item_optional": {},
        "key_hashes": {1: "128afd40"},
        "byte_stable": False,
        "builders": ("serving/kv_pool.py::export_pages",
                     "serving/engine.py::_export_request"),
        "consumers": (("serving/kv_pool.py::import_pages", "record"),
                      ("serving/engine.py::import_handoff", "record")),
        "item_consumers": (),
        "sinks": (),
    },
    "drain_manifest": {
        "family": "drain_manifest",
        "version": 1,
        "version_key": "version",
        "required": {
            "version": "int",
            "requests": "list[dict]",
        },
        "optional": {
            # builder-side provenance: written by build_manifest, read
            # by no consumer — a hand-rolled replay manifest (version +
            # requests) is a valid hand-off
            "unix_time": "number",
            "drain_seconds": "number",
        },
        "item_key": "requests",
        "item_required": {
            "order": "int",
            "rid": "int",
            "prompt": "list[int]",
            "max_new_tokens": "int",
        },
        "item_optional": {
            # absent in older-generation manifests; replay .get()s them
            # by design — WIR103 only polices .get() on REQUIRED keys
            "tag": "json",
            "generated": "list[int]",
            "eos_id": "int|none",
            "ttft_deadline": "float|none",
            "tpot_deadline": "float|none",
            "stream": "bool",
        },
        "key_hashes": {1: "93332558"},
        "byte_stable": False,
        "builders": ("serving/resilience.py::build_manifest",),
        "consumers": (("serving/resilience.py::load_manifest", "manifest"),
                      ("serving/resilience.py::replay_manifest", "manifest"),
                      ("serving/router.py::_hand_off", "manifest")),
        "item_consumers": (("serving/resilience.py::replay_manifest",
                            "entry"),
                           ("serving/resilience.py::replay_manifest", "e"),
                           ("serving/router.py::_hand_off", "entry"),
                           ("serving/router.py::_hand_off", "e")),
        "sinks": ("serving/resilience.py::write_manifest",),
    },
    "fleet_signals": {
        "family": "fleet_signals",
        "version": 1,
        "version_key": "version",
        "required": {
            "version": "int",
            "schema": "str",
            "unix_time": "number",
            "passes": "int",
            "samples": "int",
            "window": "int",
            "replicas": "list[dict]",
            "fleet": "dict",
            "autoscale": "list[dict]",
            "dumps": "list[dict]",
        },
        "optional": {},
        "item_key": None,
        "item_required": {},
        "item_optional": {},
        "key_hashes": {1: "be29c41d"},
        # serve_top --watch diffs consecutive snapshots; construction
        # order must be deterministic (the WIR106 position)
        "byte_stable": True,
        "builders": ("serving/fleet_obs.py::signals",),
        "consumers": (("serving/autoscaler.py::_control_inner", "sig"),
                      ("serving/autoscaler.py::_decide", "sig"),
                      ("serving/autoscaler.py::_snapshot", "sig")),
        "item_consumers": (),
        "sinks": ("serving/fleet_obs.py::write_telemetry",),
    },
    "autoscale_event": {
        "family": "autoscale_event",
        "version": 1,
        "version_key": "version",
        "required": {
            "version": "int",
            "tick": "int",
            "passes": "int",
            "rule": "str",
            "action": "str",
            "role": "str|none",
            "replica": "int|none",
            "outcome": "str",
            "reason": "str",
            "signal": "dict",
            "detail": "dict",
        },
        "optional": {},
        "item_key": None,
        "item_required": {},
        "item_optional": {},
        "key_hashes": {1: "c12c9d71"},
        "byte_stable": False,
        "builders": ("serving/autoscaler.py::to_dict",),
        "consumers": (),
        "item_consumers": (),
        "sinks": (),
    },
    "flight_dump": {
        "family": "flight_dump",
        "version": 1,
        "version_key": "version",
        "required": {
            "version": "int",
            "reason": "str",
            "detail": "dict|none",
            "unix_time": "number",
        },
        "optional": {
            # per-engine arm (ServingObserver._flight_record)
            "ring": "dict",
            "steps": "list[dict]",
            "requests": "list[dict]",
            "live_requests": "list[dict]",
            "telemetry": "dict",
            # correlated fleet arm (FleetObserver._fleet_record)
            "origin_replica": "int|none",
            "passes": "int",
            "window": "int",
            "router": "dict",
            "replicas": "dict",
            "autoscale": "list[dict]",
        },
        "item_key": None,
        "item_required": {},
        "item_optional": {},
        "key_hashes": {1: "2273bf8d"},
        "byte_stable": False,
        "builders": ("serving/obs.py::_flight_record",
                     "serving/fleet_obs.py::_fleet_record"),
        "consumers": (("profiler/evidence.py::ingest_flight", "doc"),),
        "item_consumers": (),
        "sinks": ("serving/obs.py::dump", "serving/fleet_obs.py::dump"),
    },
    "checkpoint_meta": {
        "family": "checkpoint_meta",
        "version": 2,
        "version_key": "format",
        "required": {
            "format": "int",
            "world_size": "int",
            "state": "dict",
            "storage": "dict",
        },
        "optional": {},
        "item_key": None,
        "item_required": {},
        "item_optional": {},
        "key_hashes": {2: "28297e11"},
        "byte_stable": False,
        "builders": ("distributed/checkpoint.py::_do_save",),
        "consumers": (("distributed/checkpoint.py::load_state_dict",
                       "meta"),
                      ("distributed/checkpoint.py::verify_checkpoint",
                       "meta")),
        "item_consumers": (),
        "sinks": (),
    },
    "kv_transfer_ack": {
        "family": "kv_transfer_ack",
        "version": 1,
        "version_key": "version",
        "required": {
            "version": "int",
            # idempotency key of the message being acknowledged — the
            # exporter resolves its pending retransmit table by this,
            # and a deduped duplicate prepare re-sends the SAME ack
            "ref": "str",
            # which transport channel the ack closes: "kv" (two-phase
            # KV-page hand-off) or "manifest" (drain-manifest replay)
            "channel": "str",
            "rid": "int|none",
            "status": "str",            # ok | abort
            "reason": "str|none",       # abort cause (PoolExhausted, ...)
            "num_pages": "int",
        },
        "optional": {},
        "item_key": None,
        "item_required": {},
        "item_optional": {},
        "key_hashes": {1: "c947c98a"},
        "byte_stable": False,
        "builders": ("serving/transport.py::build_ack",),
        "consumers": (("serving/router.py::_on_transfer_ack", "ack"),),
        "item_consumers": (),
        "sinks": (),
    },
    "membership_lease": {
        "family": "membership_lease",
        "version": 1,
        "version_key": "version",
        "required": {
            "version": "int",
            "replica": "int",
            # sender-side transport tick the heartbeat was minted at;
            # the lease extends lease_ticks past the RECEIVER's tick at
            # delivery (clocks are per-process on a real wire)
            "tick": "int",
            "role": "str|none",
            "lease_ticks": "int",
            # the fleet-signal payload riding the lease ring: enough
            # for membership telemetry to answer "what was this replica
            # doing when we last heard from it"
            "queue_depth": "int",
            "tokens_generated": "int",
        },
        "optional": {},
        "item_key": None,
        "item_required": {},
        "item_optional": {},
        "key_hashes": {1: "30e15e76"},
        "byte_stable": False,
        "builders": ("serving/membership.py::build_heartbeat",),
        "consumers": (("serving/membership.py::heartbeat", "record"),),
        "item_consumers": (),
        "sinks": (),
    },
    "telemetry_line": {
        "family": "telemetry_line",
        # 2: "attention" (PR 27); 3: "model"; 4: "attention_tiles" (PR 35);
        # 5: "overlap" (PR 38); 6: "host" (PR 39)
        "version": 6,
        "version_key": "version",
        "required": {
            "version": "int",
            "steps": "int",
            "tokens_generated": "int",
            "queue_depth": "int",
            "running": "int",
            "pool": "dict",
            "spec": "dict",
            "unix_time": "number",
            "requests": "dict",
            "slo": "dict",
            "latency": "dict",
            "flight": "dict",
        },
        "optional": {
            "mesh": "dict",
            "role": "str",
            "handoff": "dict",
            "mem": "dict",
            "resilience": "dict",
            "attention": "str",
            "attention_tiles": "dict",
            "overlap": "dict",
            "host": "dict",
            "model": "dict",
        },
        "item_key": None,
        "item_required": {},
        "item_optional": {},
        "key_hashes": {1: "f2b55577", 2: "a5410fb5", 3: "3a9c8544",
                       4: "4b58fb5c", 5: "f2e21e83", 6: "9e293f84"},
        "byte_stable": False,
        "builders": ("serving/engine.py::telemetry",),
        "consumers": (),
        "item_consumers": (),
        "sinks": ("serving/obs.py::write_telemetry",),
    },
}

# Serving-tier JSON writers that are deliberately NOT wire records:
# render-only artifacts a human (or chrome://tracing) consumes, never a
# peer process with compatibility expectations. The registry-drift test
# walks every json.dump/_atomic_json call site in the serving tier and
# requires it to appear either in a family's builders/sinks or here —
# a NEW dump site that is in neither fails the gate until declared.
NON_WIRE_SINKS = (
    "serving/obs.py::_atomic_json",            # the shared writer itself
    "serving/obs.py::export_chrome_trace",     # trace render, not a peer
    "serving/fleet_obs.py::export_chrome_trace",
)


class WireContractViolation(RuntimeError):
    """A record violated its declared WIRE_SCHEMAS contract at a
    producing/consuming seam (armed mode only)."""


# -- arming -------------------------------------------------------------------
_TRUTHY = ("1", "true", "on", "yes")
# one mutable cell so the disarmed fast path is a single list index
_armed = [os.environ.get("PADDLE_WIRECHECK", "").strip().lower()
          in _TRUTHY]


def arm(on: bool = True) -> None:
    """Arm/disarm wire-contract validation process-wide (the env knob
    ``PADDLE_WIRECHECK=1`` arms it at import)."""
    _armed[0] = bool(on)


def armed() -> bool:
    return _armed[0]


# -- schema-evolution pin -----------------------------------------------------
def key_hash(spec: Dict[str, Any]) -> str:
    """Deterministic 8-hex-digit pin of a family's key-set + type specs
    (+ item schema). ``key_hashes[version]`` in the registry must equal
    this — editing a schema without bumping the version and appending a
    fresh pin trips WIR511 and the version-bump test. crc32 of the
    canonical repr: stable across processes and PYTHONHASHSEED."""
    basis = repr((spec["version_key"],
                  tuple(sorted(spec["required"].items())),
                  tuple(sorted(spec["optional"].items())),
                  spec.get("item_key"),
                  tuple(sorted(spec.get("item_required", {}).items())),
                  tuple(sorted(spec.get("item_optional", {}).items()))))
    return f"{zlib.crc32(basis.encode('utf-8')) & 0xFFFFFFFF:08x}"


# -- the validating half ------------------------------------------------------
def _is_pure(v: Any) -> bool:
    """Deep JSON purity. Strict scalar types on purpose: numpy scalars
    (np.float64 subclasses float!), bool-as-int, bytes, sets and
    datetimes are exactly the drift WIR101 polices — a record that only
    round-trips on THIS host is not a wire record. Tuples are allowed
    (json serializes them as arrays); NaN/inf are not (stdlib json
    emits them, but no JSON peer parses them)."""
    t = type(v)
    if v is None or t is bool or t is str or t is int:
        return True
    if t is float:
        return v == v and v not in (float("inf"), float("-inf"))
    if isinstance(v, (list, tuple)):
        return all(_is_pure(x) for x in v)
    if isinstance(v, dict):
        return all(type(k) is str and _is_pure(x) for k, x in v.items())
    return False


def _type_ok(spec: str, v: Any) -> bool:
    for part in spec.split("|"):
        if part == "none" and v is None:
            return True
        if part in ("int", "crc") and type(v) is int:
            return True
        if part == "float" and type(v) is float:
            return True
        if part == "number" and type(v) in (int, float):
            return True
        if part == "str" and type(v) is str:
            return True
        if part == "bool" and type(v) is bool:
            return True
        if part == "dict" and isinstance(v, dict) and _is_pure(v):
            return True
        if part == "list" and isinstance(v, (list, tuple)) \
                and _is_pure(v):
            return True
        if part == "json" and _is_pure(v):
            return True
        if part == "device":        # opaque payload plane: anything goes
            return True
        if part == "prefix_keys" and isinstance(v, (list, tuple)) \
                and all(isinstance(k, (list, tuple))
                        and all(type(x) is int for x in k) for k in v):
            return True
        if part.startswith("list[") and part.endswith("]") \
                and isinstance(v, (list, tuple)):
            inner = part[5:-1]
            if all(_type_ok(inner, x) for x in v):
                return True
    return False


def _violate(family: str, problem: str) -> None:
    raise WireContractViolation(f"wire[{family}] {problem}")


def _check_keys(family: str, record: Dict[str, Any],
                required: Dict[str, str], optional: Dict[str, str],
                where: str) -> None:
    missing = sorted(k for k in required if k not in record)
    if missing:
        _violate(family, f"{where}missing required keys {missing}")
    undeclared = sorted(k for k in record
                        if k not in required and k not in optional)
    if undeclared:
        _violate(family,
                 f"{where}undeclared keys {undeclared} "
                 f"(declare them in WIRE_SCHEMAS and bump the version)")
    for key in sorted(record):
        spec = required.get(key) or optional[key]
        if not _type_ok(spec, record[key]):
            _violate(family,
                     f"{where}key '{key}' is {type(record[key]).__name__}"
                     f", schema wants {spec}")


def validate(record: Any, family: str) -> Dict[str, Any]:
    """Validate ``record`` against its declared family; raises
    ``WireContractViolation`` (byte-stable message) on any drift.
    Returns the record. Runs regardless of arming — ``seal`` is the
    armed-gated wrapper the hot seams call."""
    spec = WIRE_SCHEMAS.get(family)
    if spec is None:
        _violate(family, f"undeclared family (declared: "
                         f"{sorted(WIRE_SCHEMAS)})")
    if not isinstance(record, dict):
        _violate(family,
                 f"record is {type(record).__name__}, not a dict")
    vkey = spec["version_key"]
    got = record.get(vkey)
    if got != spec["version"]:
        _violate(family, f"version key '{vkey}' is {got!r}, registry "
                         f"pins {spec['version']}")
    _check_keys(family, record, spec["required"], spec["optional"], "")
    ikey = spec["item_key"]
    if ikey and isinstance(record.get(ikey), (list, tuple)):
        for i, row in enumerate(record[ikey]):
            if not isinstance(row, dict):
                _violate(family, f"{ikey}[{i}] is "
                                 f"{type(row).__name__}, not a dict")
            _check_keys(family, row, spec["item_required"],
                        spec["item_optional"], f"{ikey}[{i}] ")
    return record


def seal(record: Dict[str, Any], family: str) -> Dict[str, Any]:
    """The producing-seam hook: disarmed, a single list-index check and
    the record straight back (microbench-pinned); armed, a full
    ``validate`` that raises WHERE the record was built."""
    if _armed[0]:
        validate(record, family)
    return record


def self_check() -> Optional[str]:
    """Cheap runtime coherence probe (the deep version is
    ``analysis/wirecheck.py``): every family's current version must
    have a key_hashes pin matching ``key_hash``. Returns a problem
    string or None."""
    for fam, spec in sorted(WIRE_SCHEMAS.items()):
        pin = spec["key_hashes"].get(spec["version"])
        want = key_hash(spec)
        if pin != want:
            return (f"wire[{fam}] key_hashes[{spec['version']}] is "
                    f"{pin!r} but the declared keys hash to {want!r} — "
                    f"schema edited without a version bump?")
    return None
