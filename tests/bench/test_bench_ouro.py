"""The Ouro adapter, its plain reference and its cell through the harness on
the CPU: the walk and the counts against a hand count, one compiled weight
program for all layers, the walked reference against the reference written
straight through, ``correct`` for the program and not for the float8 control
or an altered token, and the two new metric files on a trace recorded on the
chip."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as tiny
import ouro_tiny
from bench import run as R
from bench.archs import ouro
from bench.lib import flops, serving, spec, weights as W
from bench.reference import ouro_block
from bench.reference.common import fp8

SEED = 2900000123            # past 2**31, as the driver's are
CFG = ouro_tiny.OURO


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return ouro_tiny.make_root(tmp_path_factory.mktemp("bench_root"))


def real_config():
    with open(os.path.join(tiny.REPO, "bench", "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


def test_walk_is_passes_of_blocks_then_the_passes_end():
    stops = ouro.walk(CFG)
    assert stops == [("block", 0), ("block", 1), ("pass_end", None)] * 3
    real = ouro.walk(real_config())
    assert len(real) == 4 * 49 and real[48] == ("pass_end", None)
    assert real[49] == ("block", 0) and real[-1] == ("pass_end", None)
    assert {i for _, i in real} == set(range(48)) | {None}
    for name in {n for n, _ in real} | {"embed", "head"}:
        assert callable(getattr(ouro_block, name))


def test_counts_equal_a_hand_count_at_the_tiny_size():
    h, inter, v, passes, layers = 64, 128, 256, 3, 2
    layer = 4 * h * h + 3 * h * inter
    assert ouro.block_matmul_params(CFG) == passes * layers * layer
    assert ouro.head_params(CFG) == h * v
    assert ouro.n_params(CFG) == layers * (layer + 4 * h) + 2 * v * h + h + h + 1
    assert ouro.attention_flops(CFG, 10) == 4.0 * 10 * h * passes * layers
    assert ouro.kv_bytes_per_token(CFG) == 2 * passes * layers * h * 2
    assert ouro.attention_geometry(CFG) == {"layers": 6, "heads": 4,
                                            "head_dim": 16}
    # a serving step reads every layer's weights once a pass
    assert flops.serve_step_bytes(ouro, CFG, 0, 0) == 2 * (
        passes * layers * layer + h * v)


def test_counts_at_the_published_size():
    cfg = real_config()
    assert ouro.n_params(cfg) == 2_667_974_657                  # 5.34 GB
    assert ouro.block_matmul_params(cfg) == 4 * 48 * 51_380_224
    assert ouro.kv_bytes_per_token(cfg) == 192 * 8192           # 1.573 MB
    assert ouro.attention_flops(cfg, 7) == 4.0 * 7 * 2048 * 192
    assert ouro.attention_geometry(cfg)["layers"] == 192
    assert cfg["reduced"] == {} and cfg["published"]["num_hidden_layers"] == 48
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        (entry,) = [c for c in json.load(f)["configs"] if c["name"] == "ouro-2.6b"]
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]


def test_all_layers_share_one_weight_program_and_the_model_takes_them():
    cfg = dict(CFG, intermediate_size=136)    # lists no other test has made
    before = W._make._cache_size()
    made = W.all_weights(ouro, cfg, SEED)
    assert W._make._cache_size() - before == 2       # top, and one layer list
    from bench.lib import system
    model = system.build_model(ouro, cfg, SEED)
    assert {n for n, _ in model.named_parameters()} == set(made)
    assert made["model.early_exit_gate.weight"].shape == (64, 1)
    assert made["model.layers.1.input_layernorm_2.weight"].shape == (64,)


def test_the_walked_reference_is_the_forward_written_straight_through():
    rng = np.random.default_rng(29)
    seqs = [(rng.integers(0, 256, p).tolist(), rng.integers(0, 256, s).tolist())
            for p, s in ((20, 40), (70, 9))]
    got = serving.reference_logits(ouro, CFG, SEED, seqs)
    top = {k: v.astype(jnp.float32)
           for k, v in W.top_weights(ouro, CFG, SEED).items()}
    layers = [{k: v.astype(jnp.float32)
               for k, v in W.layer_weights(ouro, CFG, SEED, i).items()}
              for i in range(2)]
    for (prompt, served), lg in zip(seqs, got):
        fed = jnp.asarray(list(prompt) + list(served[:-1]), jnp.int32)
        want, passes = ouro_block.forward(top, layers, fed, CFG)
        assert (np.asarray(passes) == 3).all()       # the published threshold
        np.testing.assert_allclose(
            lg, np.asarray(want)[len(prompt) - 1:], rtol=2e-5, atol=2e-5)


def test_the_walked_reference_refuses_another_threshold():
    x = jnp.ones((4, 64), jnp.float32)
    top = {k: v.astype(jnp.float32)
           for k, v in W.top_weights(ouro, CFG, SEED).items()}
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ouro_block.pass_end(top, None, x, dict(CFG, early_exit_threshold=0.6))


def test_the_cell_is_correct_through_the_harness(root):
    res = R.execute(spec.Cell(ouro_tiny.CELL, root), tiny.args(seed=SEED),
                    tiny.DEVICE)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_s", "serve_tok_s", "setup_s"}
    for name, c in res["compared"].items():
        assert 0 <= c["value"] < c["limit"], name


def test_an_altered_token_is_not_correct(root, monkeypatch):
    from paddle_tpu.serving import engine as eng
    orig = eng._argmax_rows
    monkeypatch.setattr(eng, "_argmax_rows",
                        lambda logits: (orig(logits) + 1) % 256)
    res = R.execute(spec.Cell(ouro_tiny.CELL, root), tiny.args(), tiny.DEVICE)
    assert res["correct"] is False
    assert res["compared"]["token_gap_max"]["value"] > 1.0


def test_the_float8_control_is_not_correct_on_three_seeds(root):
    from bench.lib import compare
    cell = spec.Cell(ouro_tiny.CELL, root)
    for seed in (1, 2, 3):
        rng = np.random.default_rng(seed)
        seqs = [(rng.integers(0, 256, n).tolist(),
                 rng.integers(0, 256, 40).tolist()) for n in (20, 50, 70)]
        low = serving.control_numbers(ouro, CFG, seed, seqs, fp8)
        correct, compared = compare.judge(low, cell)
        assert not correct, (seed, compared)
        same = serving.control_numbers(ouro, CFG, seed, seqs, None)
        assert compare.judge(same, cell)[0]


# -- the new metric files on one step of a traced run on a v5e -------------------
ONE_STEP = os.path.join(tiny.DATA, "ouro_one_step.trace.json.gz")   # PR 29,
# seed 2900000103, step 20 of the slice (bench/tools/cut_trace.py --steps 1)
CHAT = os.path.join(tiny.DATA, "chat_two_steps.trace.json.gz")      # PR 26
NEW = ("loop_exit_time_share.serve", "loop_exit_inferred_share.serve",
       "exit_pass_mean.serve")


def metric_files():
    out = {}
    for name in NEW + ("kv_write_time_share.serve", "paged_attn_time_share.serve",
                       "dense_time_share.serve"):
        with open(os.path.join(tiny.REPO, "bench", "metrics", name + ".json")) as f:
            out[name] = json.load(f)
    return out


class RecordedCell:
    root, name = "/nowhere", "cell"

    def __init__(self):
        self.files = metric_files()

    def metric_file(self, name):
        return self.files[name]


def read(monkeypatch, path, name):
    import importlib
    from bench.lib import trace as T
    monkeypatch.setattr(T, "find", lambda _dir: path)
    cell = RecordedCell()
    reader = importlib.import_module(
        "bench.readers." + cell.files[name]["reader"])
    return reader.read({"cell": cell}, name)


def test_the_new_metric_files_read_a_recorded_step(monkeypatch):
    from bench.lib import spans as S
    t = S.load(ONE_STEP)
    (run,) = S.named(t, "serve.run")
    assert run[3]["layer_visits"] == "192"
    (emit,) = S.named(t, "serve.emit")
    rows = int(emit[3]["exit_rows"])
    assert rows == 16 and int(emit[3]["exit_pass_sum"]) == 4 * rows
    assert read(monkeypatch, ONE_STEP, "exit_pass_mean.serve") == 4.0
    share = read(monkeypatch, ONE_STEP, "loop_exit_time_share.serve")
    assert 0 < share < 0.1                       # four norms and a gate
    lent = read(monkeypatch, ONE_STEP, "loop_exit_inferred_share.serve")
    assert 0 <= lent <= share
    # the step is the dense work, the attention kernel and the pools' upkeep
    parts = {n: read(monkeypatch, ONE_STEP, n + "_time_share.serve")
             for n in ("dense", "paged_attn", "kv_write")}
    assert all(v > 15 for v in parts.values()) and sum(parts.values()) < 100
    kernel = [o for o in t["ops"] if S.has("paged_attention", o)
              and o[2].startswith("paged_attention")]
    assert len(kernel) == 192                    # one call a cache entry


def test_the_new_metric_files_read_nothing_from_a_program_without_them(
        monkeypatch):
    """The parent's program has no ``loop_exit`` scope and no exit counts on
    ``serve.emit``: each reader answers None, and the line leaves them out."""
    for name in NEW:
        assert read(monkeypatch, CHAT, name) is None
        assert read(monkeypatch, None, name) is None


def test_a_traced_runs_line_holds_the_new_metrics(root, monkeypatch):
    """``--trace 1`` end to end on the CPU with the recorded step in the
    run's place: the three metrics this PR adds are on the cell's line."""
    from bench.lib import trace as T
    monkeypatch.setattr(T, "find", lambda _dir: ONE_STEP)
    res = R.execute(spec.Cell(ouro_tiny.CELL, root),
                    tiny.args(seconds=1.5, trace=1),
                    {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    got = res["metrics"]
    assert set(NEW) <= set(got)
    assert got["exit_pass_mean.serve"] == {"value": 4.0, "unit": "passes"}
    assert got["loop_exit_time_share.serve"]["value"] > 0
    json.dumps(res)
