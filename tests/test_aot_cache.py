"""AOT program-artifact cache: fingerprint, store, cached_jit, and the
trainer / serving-engine / to_static integrations.

The contract under test is the one the stock XLA cache lacked on the CPU
(tests/conftest.py): any mismatch is a miss, never a wrong hit; a corrupted,
truncated, killed-mid-write, or chaos-poisoned artifact NEVER enters (or
survives in) the ``_GOOD.json`` ledger and always degrades to a fresh
compile with bit-identical numerics — tagged and metered, never fatal.

All tests are fast, CPU-only, and seeded. The full supervised
kill→restart drill (two jax-importing generations) is RUN_SLOW-gated;
its canonical form is ``tools/chaos_drill.py --preempt``.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.aot import fingerprint as fp
from paddle_tpu.aot.cache import CachedProgram, aot_stats, cached_jit, \
    reset_stats, resolve_store
from paddle_tpu.aot.store import (ArtifactCorrupt, ArtifactMiss,
                                  ArtifactStore, LockTimeout)
from paddle_tpu.profiler import metrics as _metrics
from paddle_tpu.resilience import FaultPlan, chaos

pytestmark = pytest.mark.aot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated_env(monkeypatch):
    """No ambient cache/stats env leaks into (or out of) a test."""
    monkeypatch.delenv("PADDLE_AOT_CACHE", raising=False)
    monkeypatch.delenv("PADDLE_AOT_STATS", raising=False)
    chaos.clear_plan()
    reset_stats()
    yield
    chaos.clear_plan()
    reset_stats()


@pytest.fixture
def metrics_on():
    _metrics.reset_registry()
    _metrics.enable_metrics()
    try:
        yield _metrics.get_registry()
    finally:
        _metrics.disable_metrics()
        _metrics.reset_registry()


def _sig(*shapes, dtype="float32"):
    return ";".join(f"{dtype}[{','.join(map(str, s))}]" for s in shapes)


# -- fingerprint: any mismatch is a miss, never a wrong hit -------------------

class TestFingerprint:
    def test_same_inputs_same_key(self):
        k1, c1 = fp.fingerprint("p", _sig((4, 4)), fn=None, extras=(1, "a"))
        k2, c2 = fp.fingerprint("p", _sig((4, 4)), fn=None, extras=(1, "a"))
        assert k1 == k2 and not fp.explain_miss(c1, c2)

    def test_avals_change_is_a_miss(self):
        k1, _ = fp.fingerprint("p", _sig((4, 4)))
        k2, _ = fp.fingerprint("p", _sig((4, 8)))
        k3, _ = fp.fingerprint("p", _sig((4, 4), dtype="bfloat16"))
        assert len({k1, k2, k3}) == 3

    def test_name_extras_shardings_change_is_a_miss(self):
        base, _ = fp.fingerprint("p", _sig((2,)))
        assert fp.fingerprint("q", _sig((2,)))[0] != base
        assert fp.fingerprint("p", _sig((2,)), extras=(1,))[0] != base
        assert fp.fingerprint("p", _sig((2,)),
                              shardings="P('dp')")[0] != base

    def test_flag_change_is_a_miss(self):
        from paddle_tpu.framework import flags
        name = sorted(flags._FLAGS)[0]
        old = flags._FLAGS[name]
        k1, _ = fp.fingerprint("p", _sig((2,)))
        try:
            flags._FLAGS[name] = ("__aot_test__", old)
            k2, _ = fp.fingerprint("p", _sig((2,)))
        finally:
            flags._FLAGS[name] = old
        assert k1 != k2

    def test_topology_change_is_a_miss(self, monkeypatch):
        k1, _ = fp.fingerprint("p", _sig((2,)))
        real = fp.topology()
        fake = dict(real, device_count=real["device_count"] + 8)
        monkeypatch.setattr(fp, "topology", lambda: fake)
        k2, c2 = fp.fingerprint("p", _sig((2,)))
        assert k1 != k2
        monkeypatch.undo()
        _, c1 = fp.fingerprint("p", _sig((2,)))
        assert "topology" in fp.explain_miss(c1, c2)

    def test_source_fn_change_is_a_miss(self):
        k1, _ = fp.fingerprint("p", _sig((2,)), fn=lambda x: x * 2.0)
        k2, _ = fp.fingerprint("p", _sig((2,)), fn=lambda x: x * 3.0)
        assert k1 != k2

    def test_code_digest_covers_value_bindings(self):
        """The values bound OUTSIDE the bytecode — keyword defaults,
        functools.partial bindings, closed-over scalars — are exactly
        where user hyperparameters live (``def loss(p, y, weight=0.5)``);
        each must fork the digest or a restart after editing one is a
        silently-wrong hit."""
        import functools

        def mk_default(w):
            ns = {}
            exec(f"def f(x, weight={w}):\n    return x * weight", ns)
            return ns["f"]

        assert fp.code_digest(mk_default(0.5)) != \
            fp.code_digest(mk_default(0.9))
        assert fp.code_digest(mk_default(0.5)) == \
            fp.code_digest(mk_default(0.5))

        def g(x, *, weight):
            return x * weight

        assert fp.code_digest(functools.partial(g, weight=0.5)) != \
            fp.code_digest(functools.partial(g, weight=0.9))

        def mk_kwonly(w):
            ns = {}
            exec(f"def f(x, *, weight={w}):\n    return x * weight", ns)
            return ns["f"]

        assert fp.code_digest(mk_kwonly(0.5)) != \
            fp.code_digest(mk_kwonly(0.9))

        def mk_closure(w):
            def f(x):
                return x * w
            return f

        assert fp.code_digest(mk_closure(0.5)) != \
            fp.code_digest(mk_closure(0.9))
        assert fp.code_digest(mk_closure(0.5)) == \
            fp.code_digest(mk_closure(0.5))

    def test_code_digest_covers_referenced_globals(self):
        """A constant read from the enclosing MODULE (``LR = 0.5`` above
        the cached fn) is traced into the program like a default or
        closure value — and lives outside both the bytecode and
        package_digest's reach. Editing it must fork the digest."""
        def mk(lr):
            ns = {"LR": lr}
            exec("def f(x):\n    return x * LR", ns)
            return ns["f"]

        assert fp.code_digest(mk(0.5)) != fp.code_digest(mk(0.9))
        assert fp.code_digest(mk(0.5)) == fp.code_digest(mk(0.5))
        # numpy scalars (0-d array-likes) fork by VALUE, not just dtype
        assert fp.code_digest(mk(np.float32(0.5))) != \
            fp.code_digest(mk(np.float32(0.9)))

    def test_stable_repr_is_address_free_for_functions(self):
        """MoE decoder static keys embed live function objects; raw
        repr() would bake a per-process 0x address into the cache key —
        a permanent spurious miss on every restart/replica. stable_repr
        must digest callables by code: equal across distinct
        equal-bodied function objects, forked by a body edit."""
        def mk(body):
            ns = {}
            exec(f"def act(x):\n    return {body}", ns)
            return ns["act"]

        key_a = (1, 2, mk("x * 2.0"), True)
        key_b = (1, 2, mk("x * 2.0"), True)
        key_c = (1, 2, mk("x * 3.0"), True)
        assert "0x" not in fp.stable_repr(key_a)
        assert fp.stable_repr(key_a) == fp.stable_repr(key_b)
        assert fp.stable_repr(key_a) != fp.stable_repr(key_c)

    def test_code_digest_is_instance_stable(self):
        """Callable instances (to_static's StaticFunction closes over
        itself) must digest by class identity, never object repr — a
        memory address in the digest would make every process a miss."""
        class C:
            def __call__(self, x):
                return x

        assert fp.code_digest(C()) == fp.code_digest(C())

    def test_code_digest_frozenset_const_is_hashseed_stable(self):
        """Set-literal membership tests compile to frozenset consts,
        which iterate in hash order — the digest must sort them or every
        process (PYTHONHASHSEED randomized) becomes a spurious miss.
        jax-free subprocesses, so this costs milliseconds."""
        script = textwrap.dedent(f"""
            import sys, types, os
            pkg = types.ModuleType("paddle_tpu")
            pkg.__path__ = [os.path.join({REPO!r}, "paddle_tpu")]
            sys.modules["paddle_tpu"] = pkg
            sys.path.insert(0, {REPO!r})
            from paddle_tpu.aot.fingerprint import code_digest
            def f(x):
                return x in {{"mean", "sum", "none", "batchmean"}}
            print(code_digest(f))
        """)
        digests = set()
        for seed in ("1", "7"):
            env = dict(os.environ, PYTHONHASHSEED=seed)
            r = subprocess.run([sys.executable, "-c", script],
                               capture_output=True, timeout=60, env=env)
            assert r.returncode == 0, r.stderr.decode()
            digests.add(r.stdout.strip())
        assert len(digests) == 1, digests

    def test_module_digest_separates_structure_and_scalars(self):
        """Param names/shapes and the container's forward code are
        identical for ReLU-vs-GELU Sequentials and for two LayerNorms
        differing only in eps — the module digest must still fork, and
        must be stable across equally-constructed instances."""
        paddle.seed(0)
        a = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
        paddle.seed(0)
        b = nn.Sequential(nn.Linear(4, 8), nn.GELU(), nn.Linear(8, 1))
        paddle.seed(0)
        c = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
        assert fp.module_digest(a) == fp.module_digest(c)
        assert fp.module_digest(a) != fp.module_digest(b)
        n1 = nn.LayerNorm(8, epsilon=1e-5)
        n2 = nn.LayerNorm(8, epsilon=1e-3)
        assert fp.module_digest(n1) != fp.module_digest(n2)

    def test_avals_signature_covers_tree_structure(self):
        a = jax.ShapeDtypeStruct((2, 3), jnp.float32)
        assert fp.avals_signature({"x": a}) != fp.avals_signature([a])
        assert fp.avals_signature((a, a)) != fp.avals_signature((a,))


# -- store: checkpoint-grade integrity ----------------------------------------

class TestArtifactStore:
    def test_put_get_roundtrip_and_meta(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        st.put("k1", b"payload-bytes", {"m": 1}, name="prog")
        data, meta = st.get("k1")
        assert data == b"payload-bytes" and meta == {"m": 1}
        assert st.contains("k1") and st.stats()["artifacts"] == 1

    def test_miss_raises(self, tmp_path):
        with pytest.raises(ArtifactMiss):
            ArtifactStore(str(tmp_path)).get("nope")

    def test_corrupt_payload_quarantined(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        path = st.put("k1", b"A" * 64)
        with open(path, "r+b") as f:
            f.seek(10)
            f.write(b"Z")
        with pytest.raises(ArtifactCorrupt):
            st.get("k1")
        assert not st.contains("k1")  # removed from the ledger
        assert os.path.exists(path + ".corrupt")  # parked for postmortem

    def test_truncated_payload_quarantined(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        path = st.put("k1", b"A" * 64)
        with open(path, "wb") as f:
            f.write(b"A" * 10)
        with pytest.raises(ArtifactCorrupt):
            st.get("k1")
        assert not st.contains("k1")

    def test_chaos_byte_mangle_detected_at_load(self, tmp_path):
        """aot.artifact_bytes corrupts what hits the DISK; the crc is of
        the true bytes, so the bad sector is caught at get."""
        chaos.install_plan(
            FaultPlan().add("aot.artifact_bytes", "corrupt", at=(1,)))
        st = ArtifactStore(str(tmp_path))
        st.put("k1", b"B" * 128)
        chaos.clear_plan()
        with pytest.raises(ArtifactCorrupt):
            st.get("k1")
        assert not st.contains("k1")

    def test_chaos_export_error_publishes_nothing(self, tmp_path):
        """The fault window sits between the tmp write and the rename:
        an aborted put leaves the ledger (and the key) untouched."""
        chaos.install_plan(FaultPlan().add("aot.export", "error", at=(1,)))
        st = ArtifactStore(str(tmp_path))
        with pytest.raises(chaos.FaultInjected):
            st.put("k1", b"C" * 32)
        chaos.clear_plan()
        assert not st.contains("k1")
        names = os.listdir(str(tmp_path))
        assert not any(n.endswith(".hlo") for n in names), names
        # the aborted attempt's tmp garbage is visible but invisible to get
        assert any(".tmp-" in n for n in names), names
        st.put("k1", b"C" * 32)  # the key is reusable afterwards
        assert st.get("k1")[0] == b"C" * 32

    def test_killed_mid_write_never_enters_ledger(self, tmp_path):
        """The drill the stock XLA cache could not survive: a process
        hard-killed between the payload tmp write and the commit leaves
        NO ledger entry, and the next generation — despite the dead
        holder's leftover lockfile — publishes cleanly. Runs through the
        jax-free bootstrap, so the subprocess costs milliseconds."""
        script = textwrap.dedent(f"""
            import sys, types, os
            pkg = types.ModuleType("paddle_tpu")
            pkg.__path__ = [os.path.join({REPO!r}, "paddle_tpu")]
            sys.modules["paddle_tpu"] = pkg
            sys.path.insert(0, {REPO!r})
            from paddle_tpu.resilience import chaos
            from paddle_tpu.resilience.chaos import FaultPlan
            from paddle_tpu.aot.store import ArtifactStore
            chaos.install_plan(FaultPlan().add("aot.export", "die", at=(1,)))
            ArtifactStore(sys.argv[1]).put("k1", b"payload")
        """)
        r = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                           capture_output=True, timeout=60)
        assert r.returncode == 43, r.stderr.decode()  # chaos die default
        st = ArtifactStore(str(tmp_path))
        assert not st.contains("k1")
        names = os.listdir(str(tmp_path))
        assert any(".tmp-" in n for n in names), names  # the torn write
        assert "_LOCK" in names  # died holding the lock...
        st.put("k1", b"payload")  # ...which died with it (flock)
        assert st.get("k1")[0] == b"payload"

    def test_orphan_tmp_and_corrupt_files_swept_on_put(self, tmp_path):
        """A generation killed mid-write leaves a ``.tmp-<pid>`` file and
        every quarantine parks ``.corrupt`` postmortems; neither is ever
        in the ledger, so keep-N GC alone lets a long-lived shared dir
        grow without bound. put() sweeps dead writers' tmp litter and
        caps corrupt files at the newest few — while a LIVE writer's
        in-flight tmp file is never touched."""
        store = ArtifactStore(str(tmp_path), keep=16)
        r = subprocess.run([sys.executable, "-c",
                            "import os; print(os.getpid())"],
                           capture_output=True, timeout=30)
        dead_pid = int(r.stdout)
        dead_tmp = tmp_path / f"aaaa.hlo.tmp-{dead_pid}"
        dead_tmp.write_bytes(b"partial")
        live_tmp = tmp_path / f"bbbb.hlo.tmp-{os.getpid()}"
        live_tmp.write_bytes(b"inflight")
        for i in range(6):
            c = tmp_path / f"old{i}.hlo.corrupt"
            c.write_bytes(b"x")
            os.utime(c, (i + 1, i + 1))
        store.put("k1", b"payload", {})
        assert not dead_tmp.exists()
        assert live_tmp.exists()
        left = sorted(p.name for p in tmp_path.iterdir()
                      if p.name.endswith(".corrupt"))
        assert left == [f"old{i}.hlo.corrupt" for i in (2, 3, 4, 5)]

    def test_keep_n_gc_evicts_oldest_by_seq(self, tmp_path):
        st = ArtifactStore(str(tmp_path), keep=2)
        p1 = st.put("k1", b"1")
        st.put("k2", b"2")
        st.put("k3", b"3")
        assert sorted(st.keys()) == ["k2", "k3"]
        assert not os.path.exists(p1)
        assert st.get("k3")[0] == b"3"

    def test_lock_of_live_holder_times_out_then_releases(self, tmp_path):
        """A hung-but-alive writer holds the flock: waiters time out into
        LockTimeout (which the cache ladder absorbs as a fallback) and
        can NEVER steal the lock; release unblocks them."""
        import fcntl
        st = ArtifactStore(str(tmp_path), lock_timeout=0.2)
        lock = os.path.join(str(tmp_path), "_LOCK")
        fd = os.open(lock, os.O_CREAT | os.O_WRONLY)
        fcntl.flock(fd, fcntl.LOCK_EX)
        try:
            with pytest.raises(LockTimeout):
                st.put("k1", b"x")
            assert not st.contains("k1")
        finally:
            fcntl.flock(fd, fcntl.LOCK_UN)
            os.close(fd)
        st.put("k1", b"x")  # released: same store object proceeds
        assert st.contains("k1")

    def test_dead_holder_lock_released_by_kernel(self, tmp_path):
        """flock dies with its holder: a subprocess that takes the lock
        and exits without releasing cannot wedge the next writer (no
        stale-pid heuristics, no break-the-lock races)."""
        script = textwrap.dedent("""
            import fcntl, os, sys
            fd = os.open(os.path.join(sys.argv[1], "_LOCK"),
                         os.O_CREAT | os.O_WRONLY)
            fcntl.flock(fd, fcntl.LOCK_EX)
            os._exit(0)  # no unlock, no close — the kernel cleans up
        """)
        r = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                           capture_output=True, timeout=60)
        assert r.returncode == 0, r.stderr.decode()
        st = ArtifactStore(str(tmp_path), lock_timeout=2.0)
        st.put("k1", b"x")
        assert st.contains("k1")


# -- cached_jit: load-or-compile with the fallback ladder ---------------------

def _f(x):
    return x * 2.0 + 1.0


class TestCachedJit:
    def test_no_cache_is_plain_jit(self):
        prog = cached_jit(_f, name="toy", cache=False)
        assert not isinstance(prog, CachedProgram)
        assert float(np.asarray(prog(jnp.float32(2.0)))) == 5.0

    def test_env_resolution(self, tmp_path, monkeypatch):
        assert resolve_store(None) is None
        monkeypatch.setenv("PADDLE_AOT_CACHE", str(tmp_path))
        prog = cached_jit(_f, name="toy")
        assert isinstance(prog, CachedProgram)

    def test_miss_then_hit_bit_identical(self, tmp_path):
        x = jnp.asarray(np.linspace(-3, 7, 16, dtype=np.float32))
        p1 = cached_jit(_f, name="toy", cache=str(tmp_path))
        out1 = np.asarray(p1(x))
        assert p1.stats == {"hits": 0, "misses": 1, "fallbacks": 0}
        p2 = cached_jit(_f, name="toy", cache=str(tmp_path))
        out2 = np.asarray(p2(x))
        assert p2.stats == {"hits": 1, "misses": 0, "fallbacks": 0}
        assert np.array_equal(out1, out2)
        assert np.array_equal(out1, np.asarray(_f(x)))

    def test_new_signature_is_a_new_program(self, tmp_path):
        p = cached_jit(_f, name="toy", cache=str(tmp_path))
        p(jnp.zeros(4))
        p(jnp.zeros(8))
        assert p.stats["misses"] == 2
        p2 = cached_jit(_f, name="toy", cache=str(tmp_path))
        p2(jnp.zeros(4))
        p2(jnp.zeros(8))
        assert p2.stats == {"hits": 2, "misses": 0, "fallbacks": 0}

    def test_warm_materializes_without_executing(self, tmp_path):
        aval = jax.ShapeDtypeStruct((4,), jnp.float32)
        p = cached_jit(_f, name="toy", cache=str(tmp_path))
        assert p.warm(aval) == "miss"
        assert p.warm(aval) == "warm"  # already materialized
        p2 = cached_jit(_f, name="toy", cache=str(tmp_path))
        assert p2.warm(aval) == "hit"
        out = p2(jnp.ones(4))
        assert np.array_equal(np.asarray(out), np.asarray(_f(jnp.ones(4))))

    def test_corrupt_artifact_falls_back_and_heals(self, tmp_path,
                                                   metrics_on):
        x = jnp.asarray(np.arange(6, dtype=np.float32))
        p1 = cached_jit(_f, name="toy", cache=str(tmp_path))
        ref = np.asarray(p1(x))
        (hlo,) = [n for n in os.listdir(str(tmp_path))
                  if n.endswith(".hlo")]
        with open(os.path.join(str(tmp_path), hlo), "r+b") as f:
            f.seek(20)
            f.write(b"\xff\xff\xff\xff")
        p2 = cached_jit(_f, name="toy", cache=str(tmp_path))
        out = np.asarray(p2(x))
        assert np.array_equal(out, ref)  # identical numerics, no crash
        assert p2.stats["fallbacks"] == 1 and p2.stats["misses"] == 1
        snap = metrics_on.snapshot()
        assert snap["aot_cache_fallbacks_total"]["reason=corrupt"] == 1
        assert snap["aot_cache_misses_total"]["program=toy"] >= 1
        # the fallback re-exported: a third program hits the HEALED entry
        p3 = cached_jit(_f, name="toy", cache=str(tmp_path))
        assert np.array_equal(np.asarray(p3(x)), ref)
        assert p3.stats == {"hits": 1, "misses": 0, "fallbacks": 0}

    def test_undeserializable_artifact_falls_back(self, tmp_path):
        """crc-valid garbage (a torn writer that happened to commit, a
        foreign file) fails DESERIALIZE, not crc — still never fatal."""
        store = ArtifactStore(str(tmp_path))
        x = jnp.ones((3,), jnp.float32)
        p = cached_jit(_f, name="toy", cache=store)
        store.put(p.key_for(x), b"definitely not stablehlo")
        out = np.asarray(p(x))
        assert np.array_equal(out, np.asarray(_f(x)))
        assert p.stats["fallbacks"] == 1
        assert p.stats["misses"] == 1  # healed by re-export

    def test_chaos_load_fault_falls_back(self, tmp_path):
        x = jnp.ones((3,), jnp.float32)
        cached_jit(_f, name="toy", cache=str(tmp_path))(x)  # publish
        chaos.install_plan(FaultPlan().add("aot.load", "error", at=(1,)))
        p = cached_jit(_f, name="toy", cache=str(tmp_path))
        out = np.asarray(p(x))
        assert np.array_equal(out, np.asarray(_f(x)))
        assert p.stats["fallbacks"] == 1

    def test_unexportable_runs_uncached(self, tmp_path, monkeypatch):
        """Ladder rung 2: export machinery failing leaves a plain jit —
        the call still succeeds, nothing is published."""
        def boom(*a, **k):
            raise RuntimeError("not exportable")

        monkeypatch.setattr(jax.export, "export", boom)
        p = cached_jit(_f, name="toy", cache=str(tmp_path))
        x = jnp.ones((3,), jnp.float32)
        assert np.array_equal(np.asarray(p(x)), np.asarray(_f(x)))
        assert p.stats["fallbacks"] == 1
        assert ArtifactStore(str(tmp_path)).stats()["artifacts"] == 0

    def test_loaded_but_unrunnable_artifact_recompiles(self, tmp_path):
        """Ladder rung 3: an artifact that deserializes but fails its
        first call (here: exported from a different-arity program under
        the right key) is quarantined and the call re-runs fresh."""
        from jax import export as jexport
        store = ArtifactStore(str(tmp_path))
        x = jnp.ones((3,), jnp.float32)
        p = cached_jit(_f, name="toy", cache=store)
        key = p.key_for(x)
        aval = jax.ShapeDtypeStruct((3,), jnp.float32)
        alien = jexport.export(jax.jit(lambda a, b: a + b))(aval, aval)
        store.put(key, bytes(alien.serialize()))
        out = np.asarray(p(x))
        assert np.array_equal(out, np.asarray(_f(x)))
        assert p.stats["fallbacks"] == 1
        assert not store.contains(key)  # quarantined
        # second call uses the validated fresh program, no re-ladder
        assert np.array_equal(np.asarray(p(x)), np.asarray(_f(x)))
        assert p.stats["fallbacks"] == 1

    def test_stats_file_written(self, tmp_path, monkeypatch):
        stats_path = str(tmp_path / "stats.json")
        monkeypatch.setenv("PADDLE_AOT_STATS", stats_path)
        cached_jit(_f, name="toy", cache=str(tmp_path / "c"))(jnp.ones(2))
        with open(stats_path) as f:
            stats = json.load(f)
        assert stats["programs"]["toy"]["misses"] == 1
        assert stats["first_program_ready_unix"] is not None
        assert aot_stats()["programs"]["toy"]["misses"] == 1
        reset_stats()
        assert aot_stats()["programs"] == {}

    @pytest.mark.slow
    def test_cross_process_hit(self, tmp_path):
        """The fingerprint holds across PROCESSES (fresh module state,
        fresh code objects): run the same tiny program twice in two
        interpreters against one store — second run must hit. Slow-gated
        (two jax-importing interpreters); the tier-1 in-process hit tests
        cover deserialization and the supervised drill covers the
        cross-process loop."""
        script = textwrap.dedent(f"""
            import sys, json
            sys.path.insert(0, {REPO!r})
            import numpy as np, jax.numpy as jnp
            from paddle_tpu.aot.cache import cached_jit
            def f(x):
                return x * 2.0 + 1.0
            p = cached_jit(f, name="xproc", cache=sys.argv[1])
            out = p(jnp.asarray(np.arange(5, dtype=np.float32)))
            print(json.dumps({{"stats": p.stats,
                               "out": np.asarray(out).tolist()}}))
        """)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        runs = []
        for _ in range(2):
            r = subprocess.run([sys.executable, "-c", script,
                                str(tmp_path)], capture_output=True,
                               timeout=180, env=env, cwd=REPO)
            assert r.returncode == 0, r.stderr.decode()
            runs.append(json.loads(r.stdout.splitlines()[-1]))
        assert runs[0]["stats"] == {"hits": 0, "misses": 1, "fallbacks": 0}
        assert runs[1]["stats"] == {"hits": 1, "misses": 0, "fallbacks": 0}
        assert runs[0]["out"] == runs[1]["out"]


# -- trainer integration: the compiled training step --------------------------

def _toy_trainer(cache, seed=7, lr=0.05, hidden=8):
    from paddle_tpu.parallel import SpmdTrainer
    paddle.seed(seed)
    np.random.seed(seed)
    x = np.random.randn(16, 4).astype(np.float32)
    y = (x @ np.random.randn(4, 1)).astype(np.float32)
    net = nn.Sequential(nn.Linear(4, hidden), nn.ReLU(),
                        nn.Linear(hidden, 1))
    mse = nn.MSELoss()

    def loss_fn(model, xb, yb):
        return mse(model(xb), yb)

    tr = SpmdTrainer(net, optimizer.SGD(learning_rate=lr,
                                        parameters=net.parameters()),
                     loss_fn, aot_cache=cache)
    return tr, net, paddle.to_tensor(x), paddle.to_tensor(y)


def _params_of(net):
    return {n: np.asarray(p._data) for n, p in net.named_parameters()}


class TestTrainerAot:
    def test_export_load_bit_identical_training(self, tmp_path):
        """Generation 0 (miss: trace+export), generation 1 (hit:
        deserialize), and the uncached baseline all step to bitwise-equal
        weights — hit and miss run the identical StableHLO."""
        tr1, net1, x, y = _toy_trainer(str(tmp_path))
        for _ in range(3):
            tr1.train_step(x, y)
        tr1.block()
        assert tr1._step_fn.stats == {"hits": 0, "misses": 1,
                                      "fallbacks": 0}
        tr2, net2, x2, y2 = _toy_trainer(str(tmp_path))
        for _ in range(3):
            tr2.train_step(x2, y2)
        tr2.block()
        assert tr2._step_fn.stats == {"hits": 1, "misses": 0,
                                      "fallbacks": 0}
        tr3, net3, x3, y3 = _toy_trainer(False)
        for _ in range(3):
            tr3.train_step(x3, y3)
        tr3.block()
        p1, p2, p3 = _params_of(net1), _params_of(net2), _params_of(net3)
        for n in p1:
            assert np.array_equal(p1[n], p2[n]), n
            assert np.array_equal(p1[n], p3[n]), n

    def test_hyperparameter_change_is_a_miss(self, tmp_path):
        tr1, _, x, y = _toy_trainer(str(tmp_path), lr=0.05)
        tr1.train_step(x, y)
        tr1.block()
        # lr rides as an ARGUMENT (same program), but optimizer scalar
        # config is committed via key_extras: a different momentum-free
        # SGD lr alone must NOT fork the key...
        tr2, _, x2, y2 = _toy_trainer(str(tmp_path), lr=0.05)
        tr2.train_step(x2, y2)
        assert tr2._step_fn.stats["hits"] == 1
        # ...but a different model geometry (shapes) must.
        tr3, _, x3, y3 = _toy_trainer(str(tmp_path), hidden=16)
        tr3.train_step(x3, y3)
        assert tr3._step_fn.stats["hits"] == 0
        assert tr3._step_fn.stats["misses"] == 1

    def test_activation_swap_is_a_miss(self, tmp_path):
        """Sequential(Linear, ReLU, Linear) vs Sequential(Linear, GELU,
        Linear): identical param names/shapes, identical container
        forward code — only the module-structure digest separates them.
        A shared cache dir must fork the key, never hit."""
        from paddle_tpu.parallel import SpmdTrainer

        def build(act):
            paddle.seed(7)
            np.random.seed(7)
            x = np.random.randn(16, 4).astype(np.float32)
            y = (x @ np.random.randn(4, 1)).astype(np.float32)
            net = nn.Sequential(nn.Linear(4, 8), act(), nn.Linear(8, 1))
            mse = nn.MSELoss()
            tr = SpmdTrainer(
                net, optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters()),
                lambda model, xb, yb: mse(model(xb), yb),
                aot_cache=str(tmp_path))
            return tr, paddle.to_tensor(x), paddle.to_tensor(y)

        tr1, x1, y1 = build(nn.ReLU)
        tr1.train_step(x1, y1)
        tr1.block()
        assert tr1._step_fn.stats["misses"] == 1
        tr2, x2, y2 = build(nn.GELU)
        tr2.train_step(x2, y2)
        tr2.block()
        assert tr2._step_fn.stats["hits"] == 0
        assert tr2._step_fn.stats["misses"] == 1

    def test_corrupt_step_artifact_never_crashes_training(self, tmp_path):
        tr1, net1, x, y = _toy_trainer(str(tmp_path))
        tr1.train_step(x, y)
        tr1.block()
        for n in os.listdir(str(tmp_path)):
            if n.endswith(".hlo"):
                with open(os.path.join(str(tmp_path), n), "r+b") as f:
                    f.seek(30)
                    f.write(b"\x00" * 16)
        tr2, net2, x2, y2 = _toy_trainer(str(tmp_path))
        tr2.train_step(x2, y2)
        tr2.block()
        assert tr2._step_fn.stats["fallbacks"] == 1
        for n, a in _params_of(net1).items():
            assert np.array_equal(a, _params_of(net2)[n]), n


# -- serving-engine integration: the step_ragged program ----------------------

def _serve_engine(cache, seed=3, rms_eps=None):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import EngineConfig, ServingEngine
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab_size=61, hidden_size=32, layers=2,
                           heads=4, kv_heads=2, seq=64)
    cfg.use_flash_attention = False
    if rms_eps is not None:
        cfg.rms_norm_eps = rms_eps
    model = LlamaForCausalLM(cfg)
    return ServingEngine(model, EngineConfig(max_seqs=4, token_budget=32,
                                             aot_cache=cache))


def _serve_prompts(n=3, vocab=61, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (ln,)).tolist()
            for ln in (7, 4, 11, 5)[:n]]


class TestEngineAot:
    def test_warm_start_hit_parity_and_corrupt_fallback(self, tmp_path):
        """One story, four engines on one store: uncached baseline,
        construction-export (miss), construction-deserialize (hit), and
        the corrupted-artifact fallback — greedy outputs identical in
        all four (the step_ragged program's export→load bit-parity)."""
        prompts = _serve_prompts()
        e0 = _serve_engine(False)
        assert e0.aot_warm_result is None  # no cache: plain jit path
        out0 = e0.generate_batch(prompts, max_new_tokens=8)
        e1 = _serve_engine(str(tmp_path))
        assert e1.aot_warm_result == "miss"  # construction exported it
        out1 = e1.generate_batch(prompts, max_new_tokens=8)
        e2 = _serve_engine(str(tmp_path))
        assert e2.aot_warm_result == "hit"  # deserialized, no re-trace
        out2 = e2.generate_batch(prompts, max_new_tokens=8)
        assert out0 == out1 == out2
        for n in os.listdir(str(tmp_path)):
            if n.endswith(".hlo"):
                with open(os.path.join(str(tmp_path), n), "r+b") as f:
                    f.seek(100)
                    f.write(b"\xde\xad\xbe\xef")
        e3 = _serve_engine(str(tmp_path))
        assert e3.aot_warm_result == "fallback"  # degraded, not crashed
        out3 = e3.generate_batch(prompts, max_new_tokens=8)
        assert out3 == out0

    def test_decoder_eps_change_is_a_miss(self, tmp_path):
        """Two models with identical weight SHAPES but different
        rms_norm_eps trace different programs (eps is a baked-in
        constant): sharing one cache dir must miss, never warm-start
        the other model's artifact. The decoder's _static_key — what
        the uncached jit dispatch keyed on — is committed via extras."""
        e1 = _serve_engine(str(tmp_path), rms_eps=1e-5)
        assert e1.aot_warm_result == "miss"
        e2 = _serve_engine(str(tmp_path), rms_eps=1e-4)
        assert e2.aot_warm_result == "miss"  # NOT a wrong hit
        e3 = _serve_engine(str(tmp_path), rms_eps=1e-5)
        assert e3.aot_warm_result == "hit"  # same eps still hits


# -- to_static integration ----------------------------------------------------

class _StructNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(4, 3)

    def forward(self, x):
        h = self.fc(x)
        return {"out": h, "pair": (h * 2.0, h + 1.0)}


class TestToStaticAot:
    def test_hit_across_instances_bit_identical(self, tmp_path):
        from paddle_tpu import jit
        x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
            (2, 4)).astype(np.float32))
        paddle.seed(11)
        n1 = nn.Linear(4, 3)
        jit.to_static(n1, aot_cache=str(tmp_path))
        with paddle.no_grad():
            y1 = n1(x)
        paddle.seed(11)
        n2 = nn.Linear(4, 3)
        jit.to_static(n2, aot_cache=str(tmp_path))
        with paddle.no_grad():
            y2 = n2(x)
        (p2,) = n2.forward._aot_programs.values()
        assert p2.stats == {"hits": 1, "misses": 0, "fallbacks": 0}
        assert np.array_equal(np.asarray(y1._data), np.asarray(y2._data))

    def test_out_spec_restored_from_meta_on_hit(self, tmp_path):
        """A hit never traces, so the output TREE (Python metadata) must
        ride in the artifact meta and rebuild exactly."""
        from paddle_tpu import jit
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        paddle.seed(5)
        n1 = _StructNet()
        jit.to_static(n1, aot_cache=str(tmp_path))
        with paddle.no_grad():
            r1 = n1(x)
        paddle.seed(5)
        n2 = _StructNet()
        jit.to_static(n2, aot_cache=str(tmp_path))
        with paddle.no_grad():
            r2 = n2(x)
        (p2,) = n2.forward._aot_programs.values()
        assert p2.stats["hits"] == 1
        assert sorted(r2) == ["out", "pair"]
        assert isinstance(r2["pair"], tuple) and len(r2["pair"]) == 2
        assert np.array_equal(np.asarray(r1["out"]._data),
                              np.asarray(r2["out"]._data))
        assert np.array_equal(np.asarray(r1["pair"][1]._data),
                              np.asarray(r2["pair"][1]._data))

    def test_function_body_change_is_a_miss(self, tmp_path):
        """Editing the wrapped function's math (same name, same input
        shapes) must fork the key: the user's forward is reached only
        via runtime attribute access, so it is committed to the key
        explicitly — a stale program deserializing here would be a
        silently-wrong hit."""
        from paddle_tpu import jit

        def make(variant):
            if variant == 1:
                def fwd(t):
                    return t * 2.0
            else:
                def fwd(t):
                    return t * 3.0
            return jit.to_static(fwd, aot_cache=str(tmp_path))

        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        s1 = make(1)
        y1 = s1(x)
        (p1,) = s1._aot_programs.values()
        assert p1.stats == {"hits": 0, "misses": 1, "fallbacks": 0}
        s2 = make(2)
        y2 = s2(x)
        (p2,) = s2._aot_programs.values()
        assert p2.stats == {"hits": 0, "misses": 1, "fallbacks": 0}
        assert np.allclose(np.asarray(y2._data),
                           np.asarray(y1._data) * 1.5)
        s3 = make(1)  # unchanged body still hits
        s3(x)
        (p3,) = s3._aot_programs.values()
        assert p3.stats == {"hits": 1, "misses": 0, "fallbacks": 0}

    def test_grad_calls_bypass_the_cache(self, tmp_path):
        """Training calls need jax.vjp THROUGH the program; the exported
        primal cannot provide it, so they stay on the fresh-trace path
        — and backward still works."""
        from paddle_tpu import jit
        paddle.seed(2)
        net = nn.Linear(4, 3)
        jit.to_static(net, aot_cache=str(tmp_path))
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        x.stop_gradient = False
        y = net(x)
        y.sum().backward()
        assert x.grad is not None
        for prog in net.forward._aot_programs.values():
            assert prog.stats["hits"] == prog.stats["misses"] == 0


# -- supervisor drill ---------------------------------------------------------

class TestSupervisedDrill:
    @pytest.mark.slow
    def test_preempt_drill_with_aot_cache(self, tmp_path):
        """The acceptance loop: kill→restart resumes stepping from a
        deserialized program (>= 1 hit, no fresh export) with a lower
        cold start than generation 0 — asserted inside the drill."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import chaos_drill
        finally:
            sys.path.pop(0)
        report = chaos_drill.run_preempt_drill(
            seed=1234, verbose=False, work_dir=str(tmp_path), aot=True)
        assert report["ok"]
        assert report["aot"]["gen1"]["hits"] >= 1
        assert report["aot"]["cold_start_gen1_s"] < \
            report["aot"]["cold_start_gen0_s"]
