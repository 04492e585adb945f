"""The Nemotron-H adapter, its plain reference and its cell through the
harness on the CPU: the new cell's files load and name each other, the
configuration against the catalog's row, the walk and the counts against the
issue's arithmetic, three compiled weight programs for three kinds of layer,
``correct`` for the program and not for the float8 control, an altered token
or a part of the mathematics left out, and the new readers on two steps of a
traced run on the chip."""
import importlib
import json
import os

import numpy as np
import pytest

import bench_tiny as tiny
import bench_contract as contract
import bench_recorded
import nemotron_tiny
from bench import run as R
from bench.archs import nemotron_h as arch
from bench.lib import compare, serving, spans as S, spec, trace as T, weights as W
from bench.reference import nemotron_h_block as ref
from bench.reference.common import fp8
from bench.tools.longcat_faults import in_the_programs_place
from bench.tools.nemotron_faults import FAULTS, faulty

SEED = 3700000123            # past 2**31, as the driver's are
CFG = nemotron_tiny.NEMOTRON
CELL = "nemotron120-serve-batch"
NEW = ("ssm_scan_time_share.serve", "ssm_scan_inferred_share.serve",
       "ssm_proj_time_share.serve", "moe_latent_time_share.serve",
       "ssm_scan_roofline.serve", "state_live_share.serve",
       "state_prefill_row_share.serve", "step_mfu.state.serve",
       "step_hbm_roofline.state.serve")
SHARED = ("batch_step_s", "batch_host_s", "batch_gap_mean_s",
          "batch_gap_p95_s", "batch_pool_live_share",
          "device_idle_share.batch.serve", "moe_experts_time_share.serve",
          "moe_experts_inferred_share.serve", "moe_route_time_share.serve",
          "moe_route_inferred_share.serve", "moe_experts_roofline.serve",
          "expert_held_share.serve", "expert_load_peak.serve",
          "attn_tiles_ahead_share.batch.serve")
# the batch cells' share of the host-time counters, which the recordings of
# this cell and of ``longcat560-serve-batch`` predate
TWINS = ("device_fed_share.batch.serve", "host_offcpu_share.batch.serve",
         "host_gc_share.batch.serve", "host_compile_share.batch.serve")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return nemotron_tiny.make_root(tmp_path_factory.mktemp("bench_root"))


def real_cell():
    return spec.Cell(CELL, tiny.REPO)


# -- the new cell's files ---------------------------------------------------------
def nemotron_cell(root):
    """The cell's files load and name each other. It keeps its own metrics
    (``NEW``) and the shared ones by name, each moving ``serve_tok_s`` and
    listing the cell, and it may get more. ``NEW`` stands together and in the
    order given wherever it stands in ``per_layer``: entries a later PR
    appends come after it."""
    cell = spec.Cell(CELL, root)
    assert cell.arch() is arch and cell.chips == 1
    assert cell.kind().__name__ == "bench.kinds.closed_loop"
    assert importlib.import_module(arch.REFERENCE) is ref
    assert {m["name"] for m in cell.end_to_end()} == {"serve_tok_s", "setup_s"}
    mine = {m["name"]: m for m in cell.per_layer()}
    assert set(mine) >= set(NEW) | set(SHARED)
    for name, m in mine.items():
        assert m["moves"] == "serve_tok_s" and CELL in m["workloads"]
        assert (m["workloads"] == [CELL]) == (name in NEW)
        f = cell.metric_file(name)
        assert (f["unit"], f["layer"], f["moves"]) == (
            m["unit"], m["layer"], m["moves"])
        assert callable(cell.reader(name))
    names = [m["name"] for m in cell.benchmark["per_layer"]]
    at = names.index(NEW[0])
    assert tuple(names[at:at + len(NEW)]) == NEW
    limits = contract._json(root, "bench", "limits", CELL + ".json")
    assert set(limits["limits"]) == {"token_gap_max", "token_gap_mean"}
    for name in {n for n, _ in arch.walk(cell.config)} | {"embed", "head"}:
        assert callable(getattr(ref, name))
    entry = cell.config_entry
    assert len(entry["why"]) <= 200 and len(cell.entry["why"]) <= 200


def test_the_new_cells_files_load_and_name_each_other():
    for rule in contract.EVERY:          # the repository with the cell in it
        rule(tiny.REPO)
    nemotron_cell(tiny.REPO)


def test_the_configuration_is_the_catalogs_with_three_keys_reduced():
    cfg = real_cell().config
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "hybrid_override_pattern":
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376,
        "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
        "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 22, "num_hidden_layers": 88,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    assert len(published["hybrid_override_pattern"]) == 88
    differs = {k for k, v in published.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["published"] == {k: published[k] for k in differs}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["first_expert"]) == (11, 128, 32768, 0)
    assert arch.pattern(cfg) == "MEMEMEM*EME" and arch.router_width(cfg) == 512
    for key in ("deployment", "assumed", "precision"):
        assert cfg[key]
    assert "NOT BUILT" in cfg["assumed"]["multi-token prediction"]
    entry = real_cell().config_entry
    assert entry["source"] == cfg["source"]
    assert set(entry["reduced"]) == differs


def test_counts_at_the_published_widths():
    """The issue's arithmetic: 109.64 M a Mamba layer, 35.66 M the attention
    layer, 54.53 M an expert layer outside its experts, 5.505 M an expert;
    4.648 B held = 9.30 GB; 21.28 MB of state a sequence."""
    cfg = real_cell().config
    assert arch.mamba_params(cfg) == 4096 * 18560 + 8192 * 4096 == 109_576_192
    assert arch.attention_params(cfg) == 2 * 4096 * 4096 + 2 * 4096 * 256 \
        == 35_651_584
    assert arch.moe_dense_params(cfg) == 4096 * 512 + 2 * 4096 * 1024 \
        + 2 * 4096 * 5376 == 54_525_952
    assert arch.expert_params(cfg) == 2 * 1024 * 2688 == 5_505_024
    assert arch.kinds(cfg) == {"M": 5, "*": 1, "E": 5}
    assert arch.held_share(cfg) == 0.25
    dense = 5 * 109_576_192 + 35_651_584 + 5 * 54_525_952
    assert arch.dense_params(cfg) == dense
    assert arch.block_matmul_params(cfg) == dense + 5 * 22 * 0.25 * 5_505_024
    assert arch.head_params(cfg) == 4096 * 32768
    # K and V of ONE attention layer: 2 heads x 128, 2 bytes
    assert arch.kv_bytes_per_token(cfg) == 1024
    assert arch.attention_flops(cfg, 1000) == 4.0 * 1000 * 32 * 128
    assert arch.attention_geometry(cfg) == {"layers": 1, "heads": 32,
                                            "head_dim": 128}
    assert arch.state_bytes_per_sequence(cfg, tails=False) \
        == 5 * 128 * 64 * 128 * 4 == 20_971_520
    assert arch.state_bytes_per_sequence(cfg) == 20_971_520 + 5 * 3 * 10240 * 2
    assert arch.scan_flops_per_row(cfg) == 5 * (5 * 8192 * 128 + 8192)
    assert arch.scan_row_bytes(cfg) == 5 * (10240 * 2 + 512 + 4 * 8192)
    small = 5 * (5 * 10240 + 3 * 128 + 8192) + 5 * 512 + 12 * 4096
    stored = dense + small + 5 * 128 * 5_505_024 + 2 * 32768 * 4096
    assert arch.n_params(cfg) == stored
    assert 9.29e9 < 2 * stored < 9.31e9                          # 9.30 GB
    assert [n for n, _ in arch.walk(cfg)] == [
        {"M": "mamba", "E": "moe", "*": "attention"}[k] for k in "MEMEMEM*EME"]
    leaves = arch.top_specs(cfg) + [
        (arch.layer_prefix(i) + n, s, k) for i in range(11)
        for n, s, k in arch.layer_specs(cfg, i)]
    assert sum(int(np.prod(s)) for _, s, _ in leaves) == stored
    # the published model, from the same functions: 120.67 B, 12.77 B a token
    whole = dict(cfg, num_hidden_layers=88, n_routed_experts=512,
                 vocab_size=131072)
    assert abs(arch.n_params(whole) - 120.67e9) < 0.01e9
    active = arch.block_matmul_params(whole) + 2 * arch.head_params(whole)
    assert abs(active - 12.77e9) < 0.02e9


def test_the_traffic_is_the_issues_letter_for_letter():
    t = real_cell().traffic
    e, n = t["engine"], t["clients"]
    assert t["kind"] == "closed-loop"
    assert e == {"max_seqs": 192, "token_budget": 320, "block_size": 16,
                 "max_model_len": 1152, "num_blocks": 13824}
    assert n == 192 and t["requests"] == 2 * n
    assert e["max_model_len"] == t["prompt_len"]["max"] + t["output_len"]["max"]
    assert e["num_blocks"] == n * -(-e["max_model_len"] // e["block_size"])
    assert t["prompt_len"] == {"shape": "uniform", "min": 64, "max": 384}
    assert t["output_len"] == {"shape": "uniform", "min": 256, "max": 768}
    assert (t["fill_steps"], t["check_requests"], t["order_seed"]) == (200, 6, 7)
    cfg = real_cell().config
    state = n * arch.state_bytes_per_sequence(cfg)
    pages = e["num_blocks"] * e["block_size"] * arch.kv_bytes_per_token(cfg)
    assert 4.08e9 < state < 4.10e9 and 0.22e9 < pages < 0.23e9


def test_counts_equal_a_hand_count_at_the_tiny_size():
    h, d, conv, heads = 32, 64, 64 + 2 * 2 * 16, 8
    assert arch.pattern(CFG) == "MEM*E" and arch.kinds(CFG) == {
        "M": 2, "*": 1, "E": 2}
    assert arch.mamba_params(CFG) == h * (d + conv + heads) + d * h
    assert arch.attention_params(CFG) == 2 * h * 32 + 2 * h * 8
    assert arch.moe_dense_params(CFG) == h * 16 + 2 * h * 16 + 2 * h * 48
    assert arch.expert_params(CFG) == 2 * 16 * 24
    assert arch.held_share(CFG) == 0.5
    assert arch.state_bytes_per_sequence(CFG) == 2 * (8 * 8 * 16 * 4
                                                      + 3 * conv * 2)


def test_three_kinds_of_layer_make_three_weight_programs_and_the_model_takes_them():
    cfg = dict(CFG, moe_shared_expert_intermediate_size=56)   # lists of its own
    before = W._make._cache_size()
    made = W.all_weights(arch, cfg, SEED)
    assert W._make._cache_size() - before == 4       # top, and M, E, *
    from bench.lib import system
    model = system.build_model(arch, cfg, SEED)
    assert {n for n, _ in model.named_parameters()} == set(made)
    assert made["lm_head.weight"].shape == (256, 32)
    assert made["backbone.layers.1.mixer.experts.down_proj"].shape == (8, 24, 16)
    assert made["backbone.layers.1.mixer.gate.weight"].shape == (32, 16)
    assert made["backbone.layers.0.mixer.conv1d.weight"].shape == (4, 128)
    assert model.__class__.__module__ == "paddle_tpu.models.nemotron_h"
    assert (model.config.experts_held, model.config.first_expert,
            model.config.n_routed_experts, model.config.pattern) \
        == (8, 4, 16, "MEM*E")


# -- correct, and what is not ---------------------------------------------------
def test_the_cell_is_correct_through_the_harness(root):
    res = R.execute(spec.Cell(nemotron_tiny.CELL, root), tiny.args(seed=SEED),
                    tiny.DEVICE)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    for name, c in res["compared"].items():
        assert 0 <= c["value"] < c["limit"], name


def test_an_altered_token_is_not_correct(root, monkeypatch):
    from paddle_tpu.serving import engine as eng
    orig = eng._argmax_rows
    monkeypatch.setattr(eng, "_argmax_rows",
                        lambda logits: (orig(logits) + 1) % 256)
    res = R.execute(spec.Cell(nemotron_tiny.CELL, root), tiny.args(),
                    tiny.DEVICE)
    assert res["correct"] is False
    assert res["compared"]["token_gap_max"]["value"] > 1.0


def _sequences(seed):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 256, n).tolist(),
             rng.integers(0, 256, 40).tolist()) for n in (20, 50, 70)]


@pytest.mark.parametrize("stand_in", ("float8", "state_dropped", "no_routed",
                                      "no_shared"))
def test_a_stand_in_is_not_correct_on_three_seeds(root, stand_in):
    """The float8 control, and the reference with one part of the
    mathematics left out, in the program's place: each fails a limit on
    every seed; the reference itself passes. (The bias used in the weights
    is seen at the weights, ``tests/test_nemotron_h.py``: normalised over
    the chosen, it moves the tokens too little at any even routing.)"""
    cell = spec.Cell(nemotron_tiny.CELL, root)
    for seed in (1, 2, 3):
        seqs = _sequences(seed)
        right = serving.reference_logits(arch, CFG, seed, seqs)
        if stand_in == "float8":
            low = in_the_programs_place(arch, CFG, seed, seqs, right, fp8)
        else:
            with faulty(stand_in, CFG) as wrong:
                low = in_the_programs_place(arch, wrong, seed, seqs, right)
        correct, compared = compare.judge(low, cell)
        assert not correct, (seed, compared)
    assert compare.judge(in_the_programs_place(arch, CFG, 3, seqs, right),
                         cell)[0]
    assert set(FAULTS) == {"state_dropped", "no_routed", "no_shared",
                           "bias_in_weight"}


# -- the new readers on two steps of a traced run on a v5e -----------------------
TWO_STEPS = os.path.join(tiny.DATA, "nemotron_two_steps.trace.json.gz")
CHAT = os.path.join(tiny.DATA, "chat_two_steps.trace.json.gz")      # PR 26


def test_the_readers_read_two_recorded_steps(monkeypatch):
    """Two steps of the cell's traced run (my chip run, PR 37, seed
    3700000004), cut by ``bench/tools/cut_trace.py``."""
    read_two_recorded_steps(monkeypatch, tiny.REPO)


def read_two_recorded_steps(monkeypatch, root):
    """The cell's per-layer metrics in ``root`` over the recorded steps: what
    reads nothing is what the kind samples on the host, and what counts
    arguments the recording's program did not write yet (``bench_recorded``),
    among them the host-time twins of the batch cells."""
    cell = spec.Cell(CELL, root)
    # the run these steps were cut from: a gap of 41.4 ms untraced
    quiet = {"itl_mean_s": 0.0414, "itl_p95_s": 0.0427, "engine_step_s": 0.0414}
    ctx = tiny.recorded_context(monkeypatch, TWO_STEPS, cell, **quiet)
    assert len(ctx["measured"]["steps"]) == 2
    got = {m["name"]: cell.reader(m["name"])(ctx, m["name"])
           for m in cell.per_layer()}
    host = {"batch_pool_live_share"}          # sampled a step by the kind
    uncounted = bench_recorded.uncounted(cell, TWO_STEPS)
    assert set(TWINS) <= uncounted
    silent = host | uncounted
    assert {n for n, v in got.items() if v is None} == silent
    assert (got["batch_gap_mean_s"], got["batch_gap_p95_s"],
            got["batch_step_s"]) == tuple(quiet.values())
    for name, value in got.items():
        if name in silent:
            continue
        assert value >= 0, name
        if name.endswith("_share.serve") or "roofline" in name \
                or "mfu" in name:
            assert value <= 100.0, (name, value)
    assert 55 < got["ssm_scan_roofline.serve"] <= 100       # bandwidth-bound
    assert 60 < got["moe_experts_roofline.serve"] <= 100
    assert 35 < got["step_hbm_roofline.state.serve"] <= 100
    assert 3 < got["step_mfu.state.serve"] <= 100
    assert 90 < got["state_live_share.serve"] <= 100        # 192 callers
    assert 0 <= got["state_prefill_row_share.serve"] < 45
    assert 20 < got["expert_held_share.serve"] < 30         # 25 % if even
    assert 1 <= got["expert_load_peak.serve"] < 12
    assert 25 < got["ssm_scan_time_share.serve"] < 50
    parts = sum(got[n] for n in (
        "ssm_scan_time_share.serve", "ssm_proj_time_share.serve",
        "moe_experts_time_share.serve", "moe_route_time_share.serve",
        "moe_latent_time_share.serve"))
    assert 85 < parts <= 100
    t = S.load(TWO_STEPS)
    scans = [o for o in t["ops"] if o[2].startswith("ssm_scan")]
    assert len(scans) == 2 * 5                   # one call a Mamba layer
    grouped = [o for o in t["ops"] if o[2].startswith("grouped_experts")]
    assert len(grouped) == 2 * 5                 # one an expert layer
    for scope in ("ssm_proj", "ssm_conv", "ssm_scan", "attn_proj", "kv_write",
                  "paged_attention", "moe_route", "moe_latent", "moe_experts",
                  "moe_shared", "head"):
        assert S.time_in(t, [scope]) > 0, scope
    (run, _) = S.named(t, "serve.run")
    assert {"state_slots", "state_slots_max", "state_rows_prefill",
            "state_resets"} <= set(run[3])
    assert int(run[3]["state_slots_max"]) == 192
    (emit, _) = S.named(t, "serve.emit")
    assert {"moe_pairs", "moe_pairs_held", "moe_pairs_zero",
            "moe_peak_tokens", "moe_experts_touched"} <= set(emit[3])
    assert int(emit[3]["moe_pairs_zero"]) == 0


def test_the_new_readers_read_nothing_from_a_program_without_a_state(
        monkeypatch):
    """The parent's program has none of the scopes and counters (the chat
    cell's recorded steps stand for it), and another architecture keeps no
    state: every new reader answers None and raises nothing."""
    cell = real_cell()
    ctx = tiny.recorded_context(monkeypatch, CHAT, cell)
    spans = ("ssm_scan_time_share.serve", "ssm_scan_inferred_share.serve",
             "ssm_proj_time_share.serve", "moe_latent_time_share.serve",
             "ssm_scan_roofline.serve", "state_live_share.serve",
             "state_prefill_row_share.serve")
    for name in spans:
        assert cell.reader(name)(ctx, name) is None, name
    # the whole-step shares fall back on even routing and a state a sampled row
    for name in ("step_mfu.state.serve", "step_hbm_roofline.state.serve"):
        assert cell.reader(name)(ctx, name) > 0
    other = dict(ctx, arch=importlib.import_module("bench.archs.llama"))
    for name in ("ssm_scan_roofline.serve", "step_mfu.state.serve",
                 "step_hbm_roofline.state.serve"):
        assert cell.reader(name)(other, name) is None, name
    monkeypatch.setattr(T, "find", lambda _dir: None)
    for name in spans:
        assert cell.reader(name)(ctx, name) is None, name
