"""chip_smoke.py off the chip: it refuses a CPU, its tiny mode passes, and the
two rules it shares with every chip script (platform check, compile-cache
placement) hold. The real run is on the chip: `python chip_smoke.py`."""
import json
import os
import re
import subprocess
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from paddle_tpu.utils import chip  # noqa: E402


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)           # one device: the one-chip phases
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)


def test_refuses_to_run_without_a_tpu():
    r = _run()
    assert r.returncode != 0
    assert "default backend is 'cpu'" in r.stderr
    lines = r.stdout.strip().splitlines()
    assert not lines[-1].startswith("{"), "a result was printed"
    assert not any("phase" in ln for ln in lines), "work ran on the CPU"


def test_tiny_mode_passes_every_phase():
    r = _run("--tiny-cpu")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    # the result line carries exactly these keys; the detail is the line
    # before it
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    tag = "[chip_smoke] summary "
    assert lines[-2].startswith(tag)
    summary = json.loads(lines[-2][len(tag):])
    assert summary["ok"] is True and summary["tiny_cpu"] is True
    assert summary["device"] == device
    assert summary["phases_run"] == ["kernels", "train", "serve"]
    assert all(p["ok"] for p in summary["phases"].values())
    assert summary["compile_cache"] is None     # the CPU stays cold
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert "tokens_per_s" not in r.stdout and "mfu" not in r.stdout


def test_flash_partition_parser():
    """The four-chip phase reads the flash call's per-chip operand out of
    compiled HLO; pin the reader on the two shapes it must tell apart."""
    line = ('  %custom-call.3 = (bf16[{n},2048,128]{{2,1,0}}, '
            'f32[{n},2048,8]{{2,1,0}}) custom-call(%a, %b, %c), '
            'custom_call_target="tpu_custom_call", operand_layout=...\n')
    ok = chip_smoke.parse_flash_calls(line.format(n=16) * 3,
                                      whole=64, shard=16)
    assert ok["flash_partitioned"] is True
    with pytest.raises(chip_smoke.SmokeFailure, match="whole problem is 64"):
        chip_smoke.parse_flash_calls(line.format(n=64), whole=64, shard=16)
    with pytest.raises(chip_smoke.SmokeFailure, match="no tpu_custom_call"):
        chip_smoke.parse_flash_calls("ROOT %dot = f32[4]", 64, 16)


@pytest.fixture
def config_updates(monkeypatch):
    """Record what the helper asks of jax.config without doing it: the
    test process must stay on a cold cache."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


METADATA_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)


def test_compile_cache_yields_to_the_environment(monkeypatch, tmp_path,
                                                 config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip.enable_compile_cache() == str(tmp_path)
    # the place is the environment's; the key takes the metadata in, so a
    # profile names this commit's scopes and lines, not a cached program's
    assert config_updates == [METADATA_IN_KEY]


def test_compile_cache_default_is_one_fixed_path(monkeypatch,
                                                 config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert chip.enable_compile_cache() is None      # a CPU stays cold
    assert config_updates == []
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")])
    fixed = os.path.join(REPO, ".jax_cache")
    assert chip.enable_compile_cache() == fixed
    assert chip.enable_compile_cache() == fixed
    assert config_updates == [METADATA_IN_KEY,
                              ("jax_compilation_cache_dir", fixed)] * 2


def test_kernel_gates_let_a_backend_error_out(monkeypatch):
    """A chip held by another process must stop the program, not select
    the XLA path or the interpreter."""
    import jax.numpy as jnp
    from paddle_tpu import kernels
    from paddle_tpu.kernels import flash_attention, fused_pallas, gmm_pallas
    from paddle_tpu.framework import flags
    from paddle_tpu.serving import ragged

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="initialize backend"):
        kernels.on_tpu()
    q = jnp.zeros((1, 128, 2, 64), jnp.bfloat16)
    with pytest.raises(RuntimeError, match="initialize backend"):
        flash_attention.is_available(q, q, causal=True)
    with pytest.raises(RuntimeError, match="initialize backend"):
        gmm_pallas._interpret()
    monkeypatch.setitem(flags._FLAGS, "use_pallas_fused", True)
    with pytest.raises(RuntimeError, match="initialize backend"):
        fused_pallas.enabled()
    # no flag stands before the serving kernel: the backend alone is asked
    assert "use_ragged_pallas" not in flags._FLAGS
    with pytest.raises(RuntimeError, match="initialize backend"):
        ragged.attention_path(None, (256, 32, 16, 128), jnp.bfloat16)


def test_require_tpu_names_what_it_found():
    with pytest.raises(RuntimeError, match=r"'cpu' \(8 x cpu\)"):
        chip.require_tpu()


def test_set_device_refuses_what_it_cannot_select():
    import paddle_tpu as paddle
    assert paddle.device.set_device("cpu") == "cpu:0"
    assert paddle.device.set_device("cpu:0") == "cpu:0"
    with pytest.raises(ValueError, match="no 'tpu' device"):
        paddle.device.set_device("tpu")
    with pytest.raises(ValueError, match="use a mesh"):
        paddle.device.set_device("cpu:3")
    paddle.device.synchronize()


# -- the serving kernel, compiled for the chip that is described, not attached --
@pytest.fixture(scope="module")
def one_chip():
    """One v5e chip as a sharding: the TPU's compiler is installed here and
    compiles for a chip it is told about (on-chip-measurement guide, section
    2). Only inside a fixture: the library is loaded by the worker that runs
    this file, after collection."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("pages,kvh,rep,rows,slots,table", [
    # every cell's cache entries joined into one pool, as the step threads them
    (16 * 256, 32, 1, 64, 16, 128),     # cgpt67-serve-decode
    (20 * 1280, 8, 4, 128, 32, 64),     # mistral7b-serve-chat
    (192 * 256, 16, 1, 64, 16, 128),    # ouro26-serve-decode
], ids=["decode", "chat", "looped"])
def test_paged_attention_compiles_at_the_serving_cells_geometries(
        one_chip, pages, kvh, rep, rows, slots, table):
    """Mosaic accepts the kernel at both cells' pools, budgets and tables
    (what interpret mode cannot show); ``tools/kernel_check.py`` runs the
    same two against the reference on the chip."""
    import jax.numpy as jnp
    from paddle_tpu.kernels import ragged_pallas as rp

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q = shape((rows, kvh * rep, 128), jnp.bfloat16)
    pool = shape((pages, kvh, 16, 128), jnp.bfloat16)
    assert rp.tiles(pool.shape, pool.dtype)
    per_slot = shape((slots,), jnp.int32)
    compiled = jax.jit(
        lambda q, kp, vp, tables, *meta: rp.paged_attention(
            q, kp, vp, tables, *meta, rep=rep)).lower(
        q, pool, pool, shape((slots, table), jnp.int32),
        per_slot, per_slot, per_slot,
        shape((slots + 1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text
    assert compiled.memory_analysis().output_size_in_bytes == rows * kvh * rep * 256


def _page_writes(text):
    """Each ``kv_write`` kernel call of a lowered program: (the operand
    types, the result types, {result: the operand it aliases})."""
    calls = []
    for line in text.splitlines():
        if 'kernel_name = "kv_write"' not in line:
            continue
        ins, outs = re.search(r"\} : \((.*)\) -> \(?(.*?)\)?$",
                              line).groups()
        # a single result is no tuple: its index list is empty
        aliases = {int(o or 0): int(i) for o, i in re.findall(
            r"output_tuple_indices = \[(\d*)\], operand_index = (\d+)",
            line)}
        calls.append((re.findall(r"tensor<[^>]*>", ins),
                      re.findall(r"tensor<[^>]*>", outs), aliases))
    return calls


def test_the_step_programs_page_write_aliases_both_pools(one_chip,
                                                         monkeypatch):
    """A Llama step program lowered for one v5e: the page write is one
    kernel call a layer visit (one function, called a layer), K and V
    both handed back aliased, and the compiled program holds no second
    pool (its temporaries are under one pool's bytes)."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import generation as G
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import engine as E
    monkeypatch.setattr(paddle.kernels, "on_tpu", lambda: True)
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=61, hidden_size=256, layers=2,
                           heads=2, kv_heads=2, seq=64)
    cfg.use_flash_attention = False
    dec = G._decoder_for(LlamaForCausalLM(cfg))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    w = {k: shape(v.shape, v.dtype) for k, v in
         dec.weights(LlamaForCausalLM(cfg)).items()}
    t, i32 = 16, jnp.int32
    pool = shape((2, 2048, 2, 16, 128), jnp.bfloat16)
    lowered = E._engine_step.lower(
        dec, None, E._argmax_rows, None, w, shape((t,), i32),
        shape((t + dec.beside_width(t),), i32), shape((t,), i32),
        shape((t,), i32), shape((t,), i32), shape((t,), jnp.bool_),
        shape((4, 8), i32), pool, pool)
    (ins, outs, aliases), = _page_writes(lowered.as_text())
    joined = "tensor<4096x2x16x128xbf16>"
    assert outs == [joined, joined]
    assert [ins[aliases[0]], ins[aliases[1]]] == [joined, joined]
    assert aliases[0] != aliases[1]
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp < 4096 * 2 * 16 * 128 * 2, temp


def test_the_latent_page_write_aliases_its_one_pool(one_chip):
    """The write of a latent pool (no V) is the same kernel with one pool,
    handed back aliased."""
    import jax.numpy as jnp
    from paddle_tpu import generation as G
    from paddle_tpu.kernels import kv_write_pallas as kw

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    t, pages = 320, 8 * 1024
    pool = shape((pages, 1, 16, 640), jnp.bfloat16)

    def write(kf, rows, at, offs):
        plan = kw.at_entry(kw.plan(at, offs, 1024), 7 * 1024)
        return G._kv_write_pages(kf, None, rows, None, plan)[0]

    lowered = jax.jit(write, donate_argnums=0).lower(
        pool, shape((t, 1, 1, 640), jnp.bfloat16), shape((t,), jnp.int32),
        shape((t,), jnp.int32))
    (ins, outs, aliases), = _page_writes(lowered.as_text())
    assert outs == ["tensor<8192x1x16x640xbf16>"] == [ins[aliases[0]]]
    assert lowered.compile().memory_analysis().temp_size_in_bytes \
        < pages * 16 * 640 * 2


@pytest.mark.parametrize("rows,kvh,d,pools,path", [
    (2048, 32, 128, 2, "page_kernel"),  # cgpt67's heads at a 2,048 budget
    (2048, 1, 640, 1, "page_kernel"),   # a latent pool at the same budget
    (6016, 32, 128, 2, "page_kernel"),  # just under ``VMEM_MOST``
    (6144, 32, 128, 2, "xla"),          # just over: XLA's write
], ids=["decode-2048", "latent-2048", "most", "over"])
def test_the_page_write_runs_where_its_vmem_holds_the_steps_rows(
        one_chip, monkeypatch, rows, kvh, d, pools, path):
    """The page kernel holds a step's rows in VMEM, so its VMEM grows with
    the budget: ``write_path`` takes it where ``vmem_bytes`` fits under
    ``VMEM_MOST``, and there Mosaic compiles it for one v5e at the limit the
    call asks for; past that the write stays XLA's."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import generation as G
    from paddle_tpu.kernels import kv_write_pallas as kw
    from paddle_tpu.serving import ragged
    monkeypatch.setattr(paddle.kernels, "on_tpu", lambda: True)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    entry = (512, kvh, 16, d)
    assert ragged.write_path(None, (2,) + entry, jnp.bfloat16, rows) == path
    if path == "xla":
        return
    pool = shape((2 * entry[0],) + entry[1:], jnp.bfloat16)
    new = shape((rows, 1, kvh, d), jnp.bfloat16)

    def write(kf, vf, k, v, at, offs):
        plan = kw.at_entry(kw.plan(at, offs, entry[0]), entry[0])
        two = pools == 2
        return G._kv_write_pages(kf, vf if two else None, k,
                                 v if two else None, plan)

    jax.jit(write, donate_argnums=(0, 1)).lower(
        pool, pool, new, new, shape((rows,), jnp.int32),
        shape((rows,), jnp.int32)).compile()


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_flash_kernels_compile_at_the_training_cells_shape(one_chip, kernel):
    """Mosaic accepts each flash kernel at cgpt13-train-2k's attention,
    [8 x 16, 2048, 128] bfloat16 causal, on the tiles the call sizes for
    itself and under the scoped VMEM limit it asks for; and the call keeps
    the name the benchmark's rooflines find it by."""
    import jax.numpy as jnp
    from paddle_tpu.kernels import flash_pallas as fp

    bh, s, d = 128, 2048, 128
    tiles = fp.choose_tiles(kernel[len("flash_"):], s, s, d, 2,
                            batch_heads=bh)
    assert tiles.grid_steps <= 2048

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rows, stats = shape((bh, s, d)), shape((bh, s, fp.LANES), jnp.float32)
    if kernel == "flash_fwd":
        fn = lambda q, k, v: fp._flash_forward(  # noqa: E731
            q, k, v, True, None, *tiles[:2])
        args = (shape((8, 16, s, d)),) * 3
    else:
        launch = fp._flash_dq if kernel == "flash_dq" else fp._flash_dkv
        fn = lambda *a: launch(*a, None, True, d ** -0.5,  # noqa: E731
                               None, *tiles[:2])
        args = (rows,) * 4 + (stats,) * 2
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and kernel in text
