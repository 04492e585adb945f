"""The Mamba-2 state update (``kernels/ssm_pallas``): the Pallas kernel in
interpret mode and the ``jnp`` path against a plain per-sequence recurrence
(``models.nemotron_h.ssm_row``), for decode rows alone, chunks alone, both
mixed, a chunk that ends a sequence, and an unscheduled slot left bit for
bit; the compile for a described v5e at the serving cell's shapes; and the
two-bank ``relu2`` grouped product at a width 512 does not divide."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import grouped_experts_pallas as ge
from paddle_tpu.kernels import ssm_pallas as ssm
from paddle_tpu.models.nemotron_h import NemotronHConfig, ssm_row

H, G, P, N, LAYERS, SLOTS = 8, 2, 8, 16, 2, 6
CFG = NemotronHConfig.tiny()
# (slot, rows, context after the step): the rows a step packs, in order
PLANS = {
    "decode rows alone": [(3, 1, 8), (0, 1, 21), (5, 1, 2)],
    "chunks alone": [(1, 5, 5), (4, 7, 19)],
    "mixed": [(3, 1, 8), (1, 5, 5), (4, 3, 12), (0, 1, 1)],
    "a chunk that ends a sequence": [(2, 9, 9), (3, 1, 30)],
    "one row, nothing else": [(5, 1, 1)],
}


def _step(plan, rows=24, seed=0):
    rng = np.random.default_rng(seed)
    slot_ids, positions = [], []
    for slot, count, ctx in plan:
        slot_ids += [slot] * count
        positions += list(range(ctx - count, ctx))
    valid = np.arange(rows) < len(slot_ids)
    pad = rows - len(slot_ids)
    f32 = jnp.float32
    return dict(
        slot_ids=np.asarray(slot_ids + [0] * pad, np.int32),
        positions=np.asarray(positions + [0] * pad, np.int32), valid=valid,
        pool=jnp.asarray(rng.normal(size=(LAYERS, SLOTS)
                                    + ssm.pool_shape(H, G, P, N)), f32),
        x=jnp.asarray(rng.normal(size=(rows, H, P)), f32),
        b=jnp.asarray(rng.normal(size=(rows, G, N)), f32),
        c=jnp.asarray(rng.normal(size=(rows, G, N)), f32),
        dt=jnp.asarray(rng.uniform(0.05, 1.5, size=(rows, H)), f32))


def _expected(s, layer):
    """Each scheduled sequence's rows through ``ssm_row`` in order, from its
    slot's state, or from nothing where its first row is at position 0."""
    pool = np.array(s["pool"])
    decay = jnp.exp(-s["dt"] * 2.0)
    y = np.zeros((len(s["valid"]), H * P), np.float32)
    for slot in sorted(set(s["slot_ids"][s["valid"]].tolist())):
        rows = [r for r in range(len(s["valid"]))
                if s["valid"][r] and s["slot_ids"][r] == slot]
        state = ssm.from_pool(s["pool"][layer, slot], P)
        if s["positions"][rows[0]] == 0:
            state = jnp.zeros_like(state)
        for r in rows:
            state, out = ssm_row(state, s["x"][r], s["b"][r], s["c"][r],
                                 s["dt"][r], decay[r], CFG)
            y[r] = np.asarray(out).reshape(-1)
        pool[layer, slot] = np.asarray(ssm.to_pool(state, G))
    return y, pool


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("plan", list(PLANS), ids=[p.replace(" ", "_")
                                                   for p in PLANS])
def test_the_update_is_the_recurrence_of_each_scheduled_sequence(
        plan, kernel, monkeypatch):
    if kernel:
        monkeypatch.setattr(ssm, "_INTERPRET", True)
    s = _step(PLANS[plan])
    meta = ssm.scan_meta(jnp.asarray(s["slot_ids"]),
                         jnp.asarray(s["positions"]),
                         jnp.asarray(s["valid"]), SLOTS)
    assert int(meta.n_live) == len(PLANS[plan])
    layer = 1
    y, pool = ssm.ssm_scan(s["pool"], layer, s["x"], s["b"], s["c"], s["dt"],
                           jnp.exp(-s["dt"] * 2.0), meta, kernel=kernel)
    want_y, want_pool = _expected(s, layer)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
    np.testing.assert_allclose(np.asarray(pool), want_pool, atol=2e-5)
    # what the step did not schedule is as it was, bit for bit
    idle = sorted(set(range(SLOTS)) - {slot for slot, _, _ in PLANS[plan]})
    assert np.array_equal(np.asarray(pool)[layer, idle],
                          np.asarray(s["pool"])[layer, idle])
    assert np.array_equal(np.asarray(pool)[0], np.asarray(s["pool"])[0])
    assert not np.asarray(y)[~s["valid"]].any()


def test_a_step_that_schedules_nothing_leaves_the_pool(monkeypatch):
    monkeypatch.setattr(ssm, "_INTERPRET", True)
    s = _step([])
    meta = ssm.scan_meta(jnp.asarray(s["slot_ids"]),
                         jnp.asarray(s["positions"]),
                         jnp.asarray(s["valid"]), SLOTS)
    y, pool = ssm.ssm_scan(s["pool"], 0, s["x"], s["b"], s["c"], s["dt"],
                           jnp.exp(-s["dt"]), meta)
    assert np.array_equal(np.asarray(pool), np.asarray(s["pool"]))
    assert not np.asarray(y).any()


def test_the_pools_layout_lays_a_groups_heads_side_by_side_in_the_lanes():
    assert ssm.lane_heads(128, 8, 64) == 2       # two heads of 64: 128 lanes
    assert ssm.pool_shape(128, 8, 64, 128) == (64, 128, 128)
    assert ssm.lane_heads(8, 2, 8) == 4 and ssm.pool_shape(8, 2, 8, 16) \
        == (2, 16, 32)
    assert ssm.lane_heads(4, 4, 256) == 1        # a head wider than the lanes
    state = jnp.arange(8 * 8 * 16, dtype=jnp.float32).reshape(8, 8, 16)
    tiles = ssm.to_pool(state, 2)
    assert tiles.shape == (2, 16, 32)
    assert float(tiles[1, 3, 8 + 5]) == float(state[5, 5, 3])
    assert np.array_equal(np.asarray(ssm.from_pool(tiles, 8)),
                          np.asarray(state))


# -- the grouped product's second expert shape -----------------------------------
@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("hidden,width,block", [(128, 384, 384),
                                                (128, 2688, 2688),
                                                (256, 640, 640)])
def test_two_bank_relu2_experts_at_a_width_512_does_not_divide(
        hidden, width, block, kernel, monkeypatch):
    if kernel:
        monkeypatch.setattr(ge, "_INTERPRET", True)
    assert width % 512 and ge.width_block(hidden, width, 2, 4) == block
    e, pairs, tm = 6, 40, 8
    rng = np.random.default_rng(7)
    keys = jnp.asarray(rng.choice([0, 0, 0, 2, 3, 5, e], pairs), jnp.int32)
    x = jnp.asarray(rng.normal(0, 1, (pairs, hidden)), jnp.float32)
    up = jnp.asarray(rng.normal(0, 0.1, (e, hidden, width)), jnp.float32)
    down = jnp.asarray(rng.normal(0, 0.1, (e, width, hidden)), jnp.float32)
    sizes, tile_group, n_live, row_pair, pair_row = ge.group_plan(keys, e, tm)
    assert int(sizes[1]) == int(sizes[4]) == 0       # experts nobody chose
    live = row_pair >= 0
    xs = jnp.where(live[:, None], x[jnp.maximum(row_pair, 0)], 0)
    ys = ge.grouped_experts(xs, tile_group, n_live, None, up, down,
                            kernel=kernel)
    held = np.asarray(keys) < e
    got = np.asarray(ys)[np.asarray(pair_row)[held]]
    k = np.asarray(keys)[held]
    want = np.stack([np.square(np.maximum(np.asarray(x)[held][i]
                                          @ np.asarray(up)[k[i]], 0))
                     @ np.asarray(down)[k[i]] for i in range(len(k))])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_width_block_keeps_the_three_bank_cells_512():
    assert ge.width_block(6144, 2048, 3, 2) == 512      # longcat560-serve-batch
    assert ge.width_block(1024, 2688, 2, 2) == 2688     # nemotron120-serve-batch
    assert ge.width_block(96, 32, 3, 4) == 32           # no lane tile divides


# -- Mosaic, without a chip --------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    """One v5e chip as a sharding (``tests/test_chip_smoke.py`` has the
    same): the TPU's compiler is installed here and compiles for a chip it
    is told about."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernels_compile_at_the_serving_cells_shapes(one_chip,
                                                          monkeypatch):
    """Mosaic accepts the state update at 5 layers x 192 slots of 128 heads
    x 64 x 128 and 320 rows, aliasing the pool in place, and the two-bank
    grouped product at 7,040 pairs over 128 experts of 1024 x 2688 (what
    interpret mode cannot show); ``tools/kernel_check.py`` runs both
    against their oracles on the chip."""
    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rows, slots, layers = 320, 192, 5
    i32, f32 = jnp.int32, jnp.float32
    pool = shape((layers, slots) + ssm.pool_shape(128, 8, 64, 128), f32)
    assert ssm.tiles(pool, rows)
    per_slot = shape((slots,), i32)
    compiled = jax.jit(
        lambda *a: ssm._call(*a, interpret=False,
                             block_bytes=ssm.BLOCK_BYTES),
        donate_argnums=(10,)).lower(
        per_slot, per_slot, per_slot, per_slot, shape((1,), i32),
        shape((1,), i32), shape((rows, 8192), f32), shape((rows, 8192), f32),
        shape((rows, 1024), f32), shape((rows, 1024), f32), pool).compile()
    assert "ssm_scan" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == layers * slots * 128 * 64 * 128 * 4
    assert mem.temp_size_in_bytes < 64 << 20          # no second pool
    pairs, experts, latent, width = rows * 22, 128, 1024, 2688
    tm = ge.TM
    n_tiles = -(-pairs // tm) + experts
    assert (tm, n_tiles) == (64, 238)
    assert ge.tiles(shape((n_tiles * tm, latent)),
                    shape((experts, latent, width)), tm)
    monkeypatch.setattr(paddle.kernels, "on_tpu", lambda: True)
    compiled = jax.jit(
        lambda xs, tg, nl, up, down: ge.grouped_experts(xs, tg, nl, None, up,
                                                        down)).lower(
        shape((n_tiles * tm, latent)), shape((n_tiles,), i32), shape((), i32),
        shape((experts, latent, width)),
        shape((experts, width, latent))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_experts" in text
