"""The comparison that decides ``correct`` has been shown to fail: the
control (the plain reference computed in float8, the nearest precision below
the configurations' bfloat16) and each fault a cell can have come out as not
correct, at a size a test run can hold. PERF.md has the same readings on the
chip at the cells' own sizes."""
import numpy as np
import pytest

import bench_tiny as tiny
from bench import run as R
from bench.kinds import train_batches as tb
from bench.lib import compare, serving, spec
from bench.reference import train_steps
from bench.reference.common import fp8


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.mark.parametrize("cell_name", ["tiny-gpt-train", "tiny-llama-train"])
def test_training_control_and_half_batch_fail_on_three_seeds(root, cell_name):
    cell = spec.Cell(cell_name, root)
    arch, cfg, t = cell.arch(), cell.config, cell.traffic
    for seed in (1, 2, 3):
        bs = tb.batches(t, cfg["vocab_size"], seed, tb.FOLLOWED)
        ref = train_steps.follow(arch, cfg, seed, bs, tb.hyper(t))
        same = compare.training_numbers(ref, ref)
        assert all(v == 0 for v in same.values())
        for kw in ({"q": fp8}, {"fault": "half_batch"}):
            got = train_steps.follow(arch, cfg, seed, bs, tb.hyper(t), **kw)
            ok, compared = compare.judge(compare.training_numbers(got, ref), cell)
            assert not ok, (seed, kw, compared)


def test_frozen_state_reads_one():
    ref = {"losses": [1.0], "grad_norm": {"a": 1.0, "b": 2.0, "c": 1e-9},
           "change_norm": {"a": 0.5, "b": 0.25, "c": 0.1}}
    frozen = dict(ref, change_norm={"a": 0.0, "b": 0.0, "c": 0.0})
    got = compare.training_numbers(frozen, ref)
    assert got["change_norm_gap"] == 1.0 and got["grad_norm_gap"] == 0.0
    # c's gradient is nought to rounding: however it moves, it is not compared
    wild = dict(ref, change_norm={"a": 0.5, "b": 0.25, "c": 9.0})
    assert compare.training_numbers(wild, ref)["change_norm_gap"] == 0.0
    assert compare.still_leaves(ref["grad_norm"]) == {"c"}


def _patched_trainer(monkeypatch, wrap):
    from paddle_tpu.parallel import trainer as tr
    orig = tr.SpmdTrainer.train_step
    monkeypatch.setattr(tr.SpmdTrainer, "train_step",
                        lambda self, *batch: wrap(orig, self, batch))


def test_fault_step_returns_its_state_unchanged(root, monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen(orig, self, batch):
        keep = {n: jnp.copy(p._data) for n, p in self._params.items()}
        state = jax.tree_util.tree_map(jnp.copy, self._opt_state)
        loss = orig(self, *batch)
        for n, a in keep.items():
            self._params[n]._data = a
        if state is not None:
            self._opt_state = state
        return loss

    _patched_trainer(monkeypatch, frozen)
    res = R.execute(spec.Cell("tiny-gpt-train", root), tiny.args(seconds=0.5),
                    tiny.DEVICE)
    assert res["correct"] is False
    assert res["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(root, monkeypatch):
    _patched_trainer(monkeypatch, lambda orig, self, batch: orig(
        self, *(b[: b.shape[0] // 2] for b in batch)))
    res = R.execute(spec.Cell("tiny-llama-train", root), tiny.args(seconds=0.5),
                    tiny.DEVICE)
    assert res["correct"] is False
    over = [n for n, c in res["compared"].items() if c["value"] > c["limit"]]
    assert "grad_norm_gap" in over


@pytest.mark.parametrize("cell_name", ["tiny-llama-chat", "tiny-gpt-closed"])
def test_fault_a_token_altered_where_it_is_produced(root, monkeypatch, cell_name):
    from paddle_tpu.serving import engine as eng
    orig = eng._argmax_rows
    vocab = tiny.LLAMA["vocab_size"]
    monkeypatch.setattr(eng, "_argmax_rows",
                        lambda logits: (orig(logits) + 1) % vocab)
    res = R.execute(spec.Cell(cell_name, root), tiny.args(), tiny.DEVICE)
    assert res["correct"] is False
    assert res["compared"]["token_gap_max"]["value"] > 1.0


@pytest.mark.parametrize("cell_name", ["tiny-llama-chat", "tiny-gpt-closed"])
def test_serving_control_fails_on_three_seeds(root, cell_name):
    cell = spec.Cell(cell_name, root)
    arch, cfg = cell.arch(), cell.config
    for seed in (1, 2, 3):
        rng = np.random.default_rng(seed)
        seqs = [(rng.integers(0, cfg["vocab_size"], n).tolist(),
                 rng.integers(0, cfg["vocab_size"], 40).tolist())
                for n in (20, 50, 70)]
        # the reference's own first tokens in the program's place: exact
        ref = serving.reference_logits(arch, cfg, seed, seqs)
        best = [(p, np.argmax(lg, -1).tolist()) for (p, _), lg in zip(seqs, ref)]
        exact = np.concatenate([serving.token_gaps(lg, b[1])
                                for lg, b in zip(ref, best)])
        assert exact.max() == 0.0
        numbers = serving.control_numbers(arch, cfg, seed, seqs, fp8)
        ok, compared = compare.judge(numbers, cell)
        assert not ok, (seed, compared)
