"""The benchmark's yardstick on the CPU: trace reduction on a recorded trace,
operation and byte counts against hand-worked numbers, the request sets, the
peaks table, and BENCHMARK.json against the limits of its contract."""
import json
import os

import pytest

import bench_tiny as tiny
import bench_contract as contract
from bench.archs import gpt2, llama
from bench.lib import flops, lengths, spec, trace as T

RECORDED = os.path.join(tiny.DATA, "train_step.trace.json.gz")
MISTRAL = json.load(open(os.path.join(
    tiny.REPO, "bench", "configs", "mistral-7b-v0.3-l20.json")))
CGPT = json.load(open(os.path.join(
    tiny.REPO, "bench", "configs", "cerebras-gpt-1.3b-l14.json")))


# -- trace reduction -----------------------------------------------------------
def op(start, dur, name="fusion.1", cat="loop fusion", long_name="", src=""):
    return (start, dur, name, cat, long_name, src)


def test_busy_is_the_union_of_overlapping_intervals():
    ops = [op(0, 10), op(5, 10), op(30, 5), op(31, 1)]
    assert T.busy_intervals(ops) == [[0, 15], [30, 35]]
    assert T.busy_us(ops) == 20


def test_idle_gaps_are_named_by_the_host_span_over_their_middle():
    ops = [op(0, 100), op(400, 100), op(1000, 50)]
    spans = [(90, 320, "bench.submit"), (480, 600, "bench.engine_step")]
    gaps = dict(T.idle_gaps(ops, spans, 0, 1200))
    assert gaps["bench.submit"] == pytest.approx(300e-6)
    assert gaps["bench.engine_step"] == pytest.approx(500e-6)
    assert gaps["outside bench spans"] == pytest.approx(150e-6)


def test_the_reduction_reads_one_device_and_says_so(monkeypatch, tmp_path):
    one = T.load(RECORDED)
    monkeypatch.setattr(T, "find", lambda _dir: RECORDED)
    got = T.reduce_dir(str(tmp_path), 0.5)
    assert got["busy_s"] == pytest.approx(0.29587056, rel=1e-6)
    assert got["window_s"] == 0.5 and len(got["top_ops"]) == 10
    two = dict(one, devices={0: one["devices"][0], 1: one["devices"][0]})
    monkeypatch.setattr(T, "load", lambda _path: two)
    with pytest.raises(RuntimeError, match="one device"):
        T.reduce_dir(str(tmp_path), 0.5)
    monkeypatch.setattr(T, "find", lambda _dir: None)
    with pytest.raises(RuntimeError, match="no trace"):
        T.reduce_dir(str(tmp_path), 0.5)


def test_recorded_trace_two_training_steps_on_a_v5e():
    t = T.load(RECORDED)
    assert sorted(t["devices"]) == [0]
    ops, mods = t["devices"][0]["ops"], t["devices"][0]["modules"]
    assert len(ops) == 1900
    steps = [m for m in mods if m[2].startswith("jit_step_fn")]
    assert len(steps) == 2
    # the device ran one operation at a time: the union is the sum
    assert T.busy_us(ops) == pytest.approx(sum(o[1] for o in ops))
    assert T.busy_us(ops) == pytest.approx(295870.56, rel=1e-6)
    # 2 steps x 2 layers x (fwd, recomputed fwd, dq, dkv) Pallas calls
    assert sum(1 for o in ops if T.is_pallas(o)) == 16
    assert T.time_where(ops, T.is_pallas) == pytest.approx(93483.83, rel=1e-6)
    adam = T.time_where(ops, lambda o: T.from_source(o, "paddle_tpu/optimizer/"))
    assert adam == pytest.approx(12269.69, rel=1e-6)
    assert [s[2] for s in t["spans"]] == ["bench.train_step", "bench.block"] * 2
    top = T.top_ops(ops, 3)
    assert top[0][0].startswith("custom-call bf16[64,2048,128]")
    assert top[0][1] > top[1][1] > top[2][1]


# -- the profiler's slice -----------------------------------------------------------
class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.fixture
def profiler(monkeypatch):
    """``bench.lib.window`` with the profiler's start and stop recorded and
    the clock in the test's hand."""
    from bench.lib import window
    calls, clock = [], Clock()
    monkeypatch.setattr(window, "start_profiler", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(window.jax.profiler, "stop_trace", lambda: calls.append(("stop",)))
    monkeypatch.setattr(window, "clock", clock)
    return window, calls, clock


def test_the_slice_ends_by_steps_as_well_as_by_seconds(profiler, tmp_path):
    window, calls, clock = profiler
    sl = window.TraceSlice(True, str(tmp_path / "t"), seconds=45.0)
    assert (sl.start_at, sl.length, sl.max_steps) == (27.0, 8.0, None)
    sl.max_steps = 45                 # 20,000 events a step: the looped cell
    sl.boundary(26.9, 530)
    assert calls == []
    sl.boundary(27.1, 534)
    assert calls == [("start", sl.dir)] and sl.first_step == 534
    for step in range(535, 579):      # 44 steps of 50 ms: 2.2 of the 8 s
        clock.now += 0.05
        sl.boundary(27.1 + clock.now - 100.0, step)
    assert len(calls) == 1
    clock.now += 0.05
    sl.boundary(29.4, 579)            # the 45th step has run
    assert calls[-1] == ("stop",) and (sl.first_step, sl.last_step) == (534, 579)
    sl.boundary(40.0, 800)
    sl.stop(900)
    assert len(calls) == 2 and sl.last_step == 579
    assert sl.quiet_end(window_end=150.0) == 100.0   # when the profiler started
    # no limit by steps (training, 4,000 events a step): the 8 seconds end it
    calls.clear()
    by_time = window.TraceSlice(True, str(tmp_path / "u"), seconds=45.0)
    by_time.boundary(27.0, 10)
    clock.now += 7.9
    by_time.boundary(34.9, 18)
    assert len(calls) == 1
    clock.now += 0.2
    by_time.boundary(35.1, 19)
    assert calls[-1] == ("stop",) and by_time.last_step == 19
    off = window.TraceSlice(False, str(tmp_path / "v"), seconds=45.0)
    off.calibrate(lambda: 1 / 0)      # never called
    off.boundary(30.0, 5)
    assert off.t0 is None and off.quiet_end(150.0) == 150.0 and len(calls) == 2


def test_the_slices_steps_come_from_the_events_a_step_leaves(profiler, monkeypatch,
                                                            tmp_path):
    """``calibrate`` traces a few steps in set-up and counts what the export
    holds of them, as its cap counts: every event but the metadata."""
    window, calls, _ = profiler
    chat = os.path.join(tiny.DATA, "chat_two_steps.trace.json.gz")
    monkeypatch.setattr(T, "find", lambda d: chat if d.endswith(".calibration") else None)
    ran = []
    sl = window.TraceSlice(True, str(tmp_path / "t"), seconds=45.0)
    sl.calibrate(lambda: ran.append(1), steps=2)
    assert calls == [("start", sl.dir + ".calibration"), ("stop",)] and len(ran) == 2
    events = T.count_events(chat)
    assert 4000 < events < 5000 and sl.events_a_step == events / 2
    assert sl.max_steps == int(window.SLICE_EVENTS / sl.events_a_step)
    assert sl.max_steps * sl.events_a_step <= 900_000 < (sl.max_steps + 1) * sl.events_a_step
    # no export to count (the CPU of the tests may leave none): no limit
    monkeypatch.setattr(T, "find", lambda d: None)
    blind = window.TraceSlice(True, str(tmp_path / "u"), seconds=45.0)
    blind.calibrate(lambda: None)
    assert blind.max_steps is None


# -- operations and bytes, by hand ----------------------------------------------
def test_mistral_counts():
    # a layer: q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert llama.block_matmul_params(MISTRAL) == 20 * layer
    assert llama.head_params(MISTRAL) == 4096 * 32768
    assert llama.n_params(MISTRAL) == 20 * (layer + 2 * 4096) \
        + 2 * 4096 * 32768 + 4096
    # K and V of a token: 20 layers x 8 heads x 128 x 2 tensors x 2 bytes
    assert llama.kv_bytes_per_token(MISTRAL) == 81_920
    # one query over 1,000 keys: QK^T and PV, 2*1000*4096 each, 20 layers
    assert llama.attention_flops(MISTRAL, 1000) == 4 * 1000 * 4096 * 20


def test_cerebras_counts_and_training_flops():
    layer = 4 * 2048 * 2048 + 2 * 2048 * 8192
    assert layer == 50_331_648
    assert gpt2.block_matmul_params(CGPT) == 14 * layer
    assert gpt2.kv_bytes_per_token(CGPT) == 2 * 14 * 2048 * 2
    # forward of a token at seq 2048: 2 x (blocks + tied head) + attention over
    # the mean causal context 1024; backward twice that again
    fwd = 2 * (14 * layer + 2048 * 50257) + 4 * 1024 * 2048 * 14
    assert flops.train_flops_per_token(gpt2, CGPT, 2048) == 3 * fwd
    assert 5.1e9 < 3 * fwd < 5.3e9


def test_flash_and_serving_step_counts():
    # 7 products of 2*s*s*d per head, causal half
    assert flops.flash_flops(1, 1, 2048, 128) == 7 * 2 * 2048 * 2048 * 128 / 2
    assert flops.flash_flops(8, 16, 2048, 128, causal=False) \
        == 8 * 16 * 7 * 2 * 2048 * 2048 * 128
    blocks, head = llama.block_matmul_params(MISTRAL), llama.head_params(MISTRAL)
    got = flops.serve_step_flops(llama, MISTRAL, 128, 32, [500, 1500])
    assert got == 2 * blocks * 128 + 2 * head * 32 + 4 * 2000 * 4096 * 20
    got = flops.serve_step_bytes(llama, MISTRAL, 128, 10_000)
    assert got == (blocks + head) * 2 + 81_920 * 10_000 + 81_920 * 128


# -- the request sets -------------------------------------------------------------
CHAT = json.load(open(os.path.join(tiny.REPO, "bench", "traffic",
                                   "chat-paced.json")))


def test_same_requests_in_the_same_order_for_every_seed():
    """A mix is a replayed trace: the multiset of lengths, their pairing and
    their order come from the traffic file (``order_seed``), never from the
    seed, which draws the token ids."""
    a = lengths.request_set(CHAT, 90, 32768, seed=3)
    b = lengths.request_set(CHAT, 90, 32768, seed=2**31 + 17)
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b]
    assert a[0][0] != b[0][0]                     # token ids are the seed's
    assert a == lengths.request_set(CHAT, 90, 32768, seed=3)
    c = lengths.request_set(dict(CHAT, order_seed=8), 90, 32768, seed=3)
    assert sorted(len(p) for p, _ in c) == sorted(len(p) for p, _ in a)
    assert sorted(o for _, o in c) == sorted(o for _, o in a)
    assert [len(p) for p, _ in c] != [len(p) for p, _ in a]
    lens = [len(p) for p, _ in a]
    assert min(lens) >= 32 and max(lens) <= 768
    assert 230 <= sorted(lens)[45] <= 280          # the stated median, 256
    assert all(16 <= o <= 192 for _, o in a)
    firsts = {tuple(p[:16]) for p, _ in a}
    assert len(firsts) == 90                       # no shared first page


@pytest.mark.parametrize("kind", ["closed-loop", "open-loop-paced", "train-batches"])
def test_token_ids_are_drawn_under_the_configurations_own_vocabulary(kind):
    """A sliced vocabulary is a smaller vocabulary: every kind draws its ids
    under the ``vocab_size`` the configuration's file holds (here a quarter
    of a published 131,072), so traffic, logits and comparison are over the
    slice alike."""
    from bench.kinds import closed_loop, open_loop_paced, train_batches
    held = 131072 // 4
    seed = 2**31 + 36
    if kind == "closed-loop":
        mix = json.load(open(os.path.join(tiny.REPO, "bench", "traffic",
                                          "decode-closed16.json")))
        ids = [i for p, _ in closed_loop.Clients(mix, held, seed).reqs for i in p]
    elif kind == "open-loop-paced":
        ids = [i for r in open_loop_paced.schedule(CHAT, held, 45.0, seed)
               for i in r.prompt]
    else:
        mix = {"batch": 2, "seq": 2048}
        ids = train_batches.Feed(mix, held, seed).next().ravel().tolist()
    assert len(ids) > 1000 and min(ids) >= 0
    assert 0.9 * held < max(ids) < held


@pytest.mark.parametrize("key", ["order_seed", "jitter"])
def test_a_mix_states_its_order_and_its_arrivals(key):
    """No default for either: a traffic file that leaves one out is refused."""
    from bench.kinds import open_loop_paced
    mix = {k: v for k, v in CHAT.items() if k != key}
    with pytest.raises(KeyError, match=key):
        open_loop_paced.schedule(mix, 32768, 45.0, seed=1)
    assert len(open_loop_paced.schedule(CHAT, 32768, 45.0, seed=1)) == 86


def test_paced_arrivals_keep_the_rate_and_jitter_by_half_a_gap():
    for seed in (1, 2, 2**31 + 5):
        due = lengths.paced_arrivals(2.0, 80, seed, jitter=0.5)
        assert len(due) == 80 and due == sorted(due)
        for i, d in enumerate(due):                # slot i is [i/2, (i+1)/2)
            assert i * 0.5 <= d < (i + 1) * 0.5
    assert lengths.paced_arrivals(2.0, 80, 1, 0.5) != lengths.paced_arrivals(2.0, 80, 2, 0.5)
    # no jitter: the offered load is exact, the same instants for every seed
    even = lengths.paced_arrivals(2.0, 80, 1, jitter=0.0)
    assert even == lengths.paced_arrivals(2.0, 80, 9, jitter=0.0)
    assert even == [(i + 0.5) * 0.5 for i in range(80)]


def test_stratified_uniform_covers_the_range_evenly():
    got = lengths.stratified({"shape": "uniform", "min": 64, "max": 160}, 96)
    assert got == sorted(got) and got[0] == 64 and got[-1] in (159, 160)
    assert abs(sum(got) / 96 - 112) < 1


# -- peaks and the contract's limits ---------------------------------------------
def test_peaks_by_exact_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        spec.peaks("TPU v5")
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_benchmark_json_keeps_to_its_contract():
    contract.benchmark_json(tiny.REPO)


HELD = {"num_hidden_layers": 11, "n_routed_experts": 128, "vocab_size": 32768,
        "hidden_size": 4096, "moe_topk": 22, "kv_lora_rank": 512,
        "published": {"num_hidden_layers": 88, "n_routed_experts": 512,
                      "vocab_size": 131072, "hidden_size": 8192,
                      "moe_topk": 44, "kv_lora_rank": 1024}}


@pytest.mark.parametrize("keys,change,refused", [
    (["num_hidden_layers", "n_routed_experts", "vocab_size"], {}, None),
    (["vocab_size"], {"vocab_size": 131072 // 8}, None),     # the floor itself
    (["vocab_size"], {"vocab_size": 131072 // 16}, "under an eighth"),
    (["vocab_size"], {"vocab_size": 131072}, "equals the published"),
    (["n_routed_experts"], {"n_routed_experts": 8}, None),
    (["n_routed_experts"], {"n_routed_experts": 4}, "under the floor of 8"),
    (["hidden_size"], {}, "a width may not be cut"),
    (["kv_lora_rank"], {}, "a width may not be cut"),
    (["moe_topk"], {}, "a width may not be cut"),
    (["n_embd"], {"n_embd": 1, "published": {"n_embd": 2}}, "a width may not be cut"),
])
def test_reduced_lists_depth_experts_held_and_vocabulary_never_a_width(
        keys, change, refused):
    """The ``model-configs`` guide's cut: a width may not be listed in
    ``reduced``; the vocabulary may, down to an eighth of the published rows,
    and a count of experts held down to 8."""
    held = dict(HELD, **change, reduced={k: "test" for k in keys})
    entry = {"reduced": keys}
    if refused is None:
        contract.reduced_keys(entry, held)
    else:
        with pytest.raises(AssertionError, match=refused):
            contract.reduced_keys(entry, held)
    with pytest.raises(AssertionError, match="list other reduced keys"):
        contract.reduced_keys({"reduced": keys + ["n_layer"]}, held)
