"""An adapter states each layer's leaves and the walk a forward pass makes,
and the harness follows both: toy architectures whose layers are visited
several times or are not all alike go through the weights, the serving
reference and the control as the two real adapters do, and the real
adapters' weights and reference logits are what they were before the seam
(golden values taken at commit 08052bc, PR 27)."""
import glob
import hashlib
import importlib
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as tiny
import toy_looped
import toy_mixed
from bench.kinds import train_batches as tb
from bench.lib import serving, weights as W
from bench.reference import train_steps
from bench.reference.common import fp8, mm

SEED = 2800000123            # past 2**31, as the driver's are


def sequences(vocab):
    rng = np.random.default_rng(28)
    return [(rng.integers(0, vocab, p).tolist(), rng.integers(0, vocab, s).tolist())
            for p, s in ((20, 40), (70, 9))]


def padded(prompt, served):
    """The rows the harness feeds: prompt and served tokens but the last, then
    token 0 up to a multiple of 256. The float8 stand-in scales a tensor by
    its largest magnitude, so the straight line has to see the same rows."""
    fed = list(prompt) + list(served[:-1])
    return jnp.asarray(fed + [0] * (256 - len(fed)), jnp.int32)


def f32_leaves(arch, cfg):
    return {k: v.astype(jnp.float32)
            for k, v in W.all_weights(arch, cfg, SEED).items()}


def upto_mean(x):
    return jnp.cumsum(x, axis=0) / jnp.arange(1, x.shape[0] + 1)[:, None]


def looped_straight_line(w, ids, q):
    """``toy_looped`` up to its head, written out: two layers, three times
    over."""
    x = w["embed.weight"][ids]
    for _ in range(3):
        for pre in ("layers.0.", "layers.1."):
            x = x + mm(upto_mean(x), w[pre + "mix.weight"], q)
            x = x + mm(jnp.tanh(mm(x, w[pre + "up.weight"], q)),
                       w[pre + "down.weight"], q)
        x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) \
            * w["norm.weight"]
    return x


def mixed_straight_line(w, ids, q):
    """``toy_mixed`` up to its head, written out: one dense layer, then two
    gated ones."""
    x = w["embed.weight"][ids]
    x = x + mm(upto_mean(x), w["layers.0.dense.weight"], q)
    for pre in ("layers.1.", "layers.2."):
        m = jax.nn.silu(mm(x, w[pre + "gate.weight"], q)) \
            * mm(x, w[pre + "up.weight"], q)
        x = x + mm(m, w[pre + "down.weight"], q)
    return x


def agrees(arch, straight_line, q, tol=2e-5):
    cfg = arch.CONFIG
    seqs = sequences(cfg["vocab_size"])
    got = serving.reference_logits(arch, cfg, SEED, seqs, q)
    w = f32_leaves(arch, cfg)
    # compiled as the harness's steps are: run operation by operation, a last
    # bit that differs flips a float8 rounding, and one flip moves every logit
    straight_line = jax.jit(straight_line, static_argnums=2)
    for (prompt, served), lg in zip(seqs, got):
        rows = straight_line(w, padded(prompt, served), q)[
            len(prompt) - 1: len(prompt) - 1 + len(served)]
        want = mm(rows, w["head.weight"], q)    # the served rows alone
        assert lg.shape == (len(served), cfg["vocab_size"])
        assert np.std(want) > 0.1
        np.testing.assert_allclose(lg, np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("q", [None, fp8], ids=["float32", "float8"])
def test_looped_toy_through_the_serving_reference(q):
    agrees(toy_looped, looped_straight_line, q)


@pytest.mark.parametrize("q", [None, fp8], ids=["float32", "float8"])
def test_mixed_toy_through_the_serving_reference(q):
    agrees(toy_mixed, mixed_straight_line, q)


def test_the_looped_walk_visits_each_layer_three_times():
    stops = toy_looped.walk(toy_looped.CONFIG)
    assert stops == [("block", 0), ("block", 1), ("pass_end", None)] * 3


def test_mixed_toy_each_layer_its_own_leaves_and_one_program_a_kind():
    cfg = dict(toy_mixed.CONFIG, hidden=40)    # lists no other test has made
    before = W._make._cache_size()
    w = W.all_weights(toy_mixed, cfg, SEED)
    # the leaves outside the layers, layer 0's list, layers 1-2's list
    assert W._make._cache_size() - before == 3
    assert {k: tuple(v.shape) for k, v in w.items()} == {
        "embed.weight": (48, 40), "head.weight": (40, 48),
        "layers.0.dense.weight": (40, 40),
        "layers.1.gate.weight": (40, 80), "layers.1.up.weight": (40, 80),
        "layers.1.down.weight": (80, 40),
        "layers.2.gate.weight": (40, 80), "layers.2.up.weight": (40, 80),
        "layers.2.down.weight": (80, 40)}
    assert all(v.dtype == jnp.bfloat16 for v in w.values())
    # one program, two layers: the index is traced and the values differ
    assert not np.array_equal(np.asarray(w["layers.1.up.weight"], np.float32),
                              np.asarray(w["layers.2.up.weight"], np.float32))
    again = W.layer_weights(toy_mixed, cfg, SEED, 2)
    assert W._make._cache_size() - before == 3
    assert np.array_equal(np.asarray(again["up.weight"], np.float32),
                          np.asarray(w["layers.2.up.weight"], np.float32))


def test_control_numbers_on_the_looped_toy():
    """The path ``bench/tools/control.py`` takes: float8 in the program's
    place reads a gap, the reference in its own place reads none."""
    cfg = toy_looped.CONFIG
    seqs = sequences(cfg["vocab_size"])
    low = serving.control_numbers(toy_looped, cfg, SEED, seqs, fp8)
    assert set(low) == {"token_gap_max", "token_gap_mean"}
    assert low["token_gap_max"] > low["token_gap_mean"] > 0
    same = serving.control_numbers(toy_looped, cfg, SEED, seqs, None)
    assert same == {"token_gap_max": 0.0, "token_gap_mean": 0.0}


def _with_walk(arch, stops):
    names = ("REFERENCE", "n_layers", "layer_prefix", "top_specs", "layer_specs")
    return types.SimpleNamespace(walk=lambda cfg: stops,
                                 **{n: getattr(arch, n) for n in names})


@pytest.mark.parametrize("arch,cfg,reason", [
    (toy_looped, toy_looped.CONFIG, "stops at ['pass_end']"),
    (toy_mixed, toy_mixed.CONFIG, "stops at ['dense', 'gated']"),
    (_with_walk(toy_looped, [("block", 0), ("block", 1), ("block", 0)]),
     toy_looped.CONFIG, "visits the 2 layers in 3 stops"),
    (_with_walk(toy_looped, [("block", 1)]), toy_looped.CONFIG,
     "visits the 2 layers in 1 stops"),
], ids=["looped", "mixed", "revisit", "layer-left-out"])
def test_the_training_reference_refuses_a_walk_it_cannot_follow(arch, cfg, reason):
    bs = tb.batches(tiny.TRAIN, cfg["vocab_size"], SEED, 1)
    with pytest.raises(ValueError) as e:
        train_steps.follow(arch, cfg, SEED, bs, tb.hyper(tiny.TRAIN))
    assert "the training reference" in str(e.value) and reason in str(e.value)
    assert "not written" in str(e.value)


def test_the_training_reference_follows_blocks_in_the_walks_order():
    """A one-visit walk in another order is another model, and is followed."""
    arch = importlib.import_module("bench.archs.gpt2")
    turned = _with_walk(arch, [("block", 1), ("block", 0)])
    turned.FUSED = arch.FUSED
    bs = tb.batches(tiny.TRAIN, tiny.GPT2["vocab_size"], SEED, 1)
    plain, other = (train_steps.follow(a, tiny.GPT2, SEED, bs, tb.hyper(tiny.TRAIN))
                    for a in (arch, turned))
    assert set(plain["grad_norm"]) == set(other["grad_norm"])
    assert plain["losses"] != other["losses"]


# -- the two real adapters are what they were ----------------------------------
def digest(named):
    h = hashlib.sha256()
    for name, a in named:
        a = np.asarray(a, np.float32)
        h.update(name.encode() + str(a.shape).encode() + a.tobytes())
    return h.hexdigest()


GOLDEN = {   # at commit 08052bc (PR 27), tests/bench/bench_tiny.py's sizes
    "gpt2": (tiny.GPT2, 28,
             "6c8e28bf2584c5815e6e022d4ddd8ec08c625f2d390f4179e8bdc56b65e74de2",
             "6fa03b8d740a07ec2f3554a7a442893b7c0e6eea25a9f5b3376cc0ba4aabde80",
             "2e40d576a0c0cd8cf9892af17aa2a7b4e04afa7457cb9657077947fbc6b9dc77"),
    "llama": (tiny.LLAMA, 21,
              "567b756183c1faef3a37b0b73bd951af95901ed8b371d9cffa9e3de0bd3f73e8",
              "152b9a462da7bed28adaf07a264d33fb87d89d1fd9615c13bffce996dd49a66f",
              "cb22b5634fb0dafa8b48ef979302649c06a21d62d938df33c9e1a202b9c71797"),
}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_golden_every_leaf_bit_for_bit(name):
    cfg, leaves, want, _, _ = GOLDEN[name]
    w = W.all_weights(importlib.import_module("bench.archs." + name), cfg, SEED)
    assert len(w) == leaves
    assert digest((k, w[k]) for k in sorted(w)) == want


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_golden_reference_logits_bit_for_bit(name):
    cfg, _, _, want, _ = GOLDEN[name]
    got = serving.reference_logits(
        importlib.import_module("bench.archs." + name), cfg, SEED,
        sequences(cfg["vocab_size"]))
    assert [a.shape for a in got] == [(40, 256), (9, 256)]
    assert digest(("", a) for a in got) == want


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_golden_two_followed_training_steps_digit_for_digit(name):
    cfg, _, _, _, want = GOLDEN[name]
    got = train_steps.follow(
        importlib.import_module("bench.archs." + name), cfg, SEED,
        tb.batches(tiny.TRAIN, cfg["vocab_size"], SEED, 2), tb.hyper(tiny.TRAIN))
    text = json.dumps(got, sort_keys=True)
    assert hashlib.sha256(text.encode()).hexdigest() == want


# -- every adapter and configuration the benchmark has -------------------------
BENCH = os.path.join(tiny.REPO, "bench")
ADAPTERS = sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(BENCH, "archs", "*.py")) if not p.endswith("__init__.py"))
CONFIGS = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(BENCH, "configs", "*.json")))


@pytest.mark.parametrize("name", ADAPTERS)
def test_an_adapter_has_what_every_cell_reads(name):
    arch = importlib.import_module("bench.archs." + name)
    for attr in ("n_layers", "layer_prefix", "top_specs", "layer_specs", "walk",
                 "build", "block_matmul_params", "head_params",
                 "attention_flops", "kv_bytes_per_token", "attention_geometry"):
        assert callable(getattr(arch, attr)), attr
    ref = importlib.import_module(arch.REFERENCE)
    assert callable(ref.embed) and callable(ref.head)


@pytest.mark.parametrize("name", CONFIGS)
def test_a_configurations_walk_names_steps_and_layers_that_exist(name):
    from bench.lib import spec
    cfg = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    arch = importlib.import_module(
        "bench.archs." + spec.module_name(cfg["architecture"]))
    ref = importlib.import_module(arch.REFERENCE)
    stops = list(arch.walk(cfg))
    n = arch.n_layers(cfg)
    assert {i for _, i in stops if i is not None} == set(range(n))
    for step, _ in stops:
        assert callable(getattr(ref, step)), step
    kinds = {W._hashable(arch.layer_specs(cfg, i)) for i in range(n)}
    assert 1 <= len(kinds) <= n
    for specs in kinds:
        assert len({leaf for leaf, _, _ in specs}) == len(specs)
