"""The LongCat-Flash adapter, its plain reference and its cell through the
harness on the CPU: the new cell's files load, the walk and the counts against
the configuration's arithmetic, one compiled weight program for all layers,
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
import longcat_tiny
from bench import run as R
from bench.archs import longcat_flash as arch
from bench.lib import compare, serving, spans as S, spec, trace as T, weights as W
from bench.reference import longcat_flash_block as ref
from bench.reference.common import fp8
from bench.tools.longcat_faults import FAULTS, faulty, in_the_programs_place

SEED = 3400000123            # past 2**31, as the driver's are
CFG = longcat_tiny.LONGCAT
CELL = "longcat560-serve-batch"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return longcat_tiny.make_root(tmp_path_factory.mktemp("bench_root"))


def real_cell():
    return spec.Cell(CELL, tiny.REPO)


# -- the new cell's files ---------------------------------------------------------
def test_the_new_cells_files_load_and_name_each_other():
    """``contract.batch_cell``: the cell's 23 metrics of PR 34 by name and
    what later PRs gave it, each moving ``serve_tok_s`` and listing the cell,
    its files and its limits; the cell on no list whose ``moves`` it does not
    report."""
    cell = contract.batch_cell(tiny.REPO)
    assert cell.arch() is arch


def test_the_configuration_is_the_catalogs_with_two_keys_reduced():
    cfg = real_cell().config
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    differs = {k for k, v in published.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"]) == {"num_layers", "n_routed_experts"}
    assert cfg["published"] == {k: published[k] for k in differs}
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["first_expert"]) \
        == (4, 16, 0)
    assert arch.router_width(cfg) == 768
    for key in ("deployment", "vocabulary", "assumed", "precision"):
        assert cfg[key]
    (entry,) = [c for c in real_cell().benchmark["configs"]
                if c["name"] == "longcat-flash-omni-ep32-l4"]
    assert entry["source"] == cfg["source"]
    assert set(entry["reduced"]) == differs


def test_counts_at_the_published_widths():
    cfg = real_cell().config
    # an attention block: q_a 9.44 M, q_b 18.87, kv_a 3.54, kv_b 8.39, o 50.33
    assert arch.mla_params(cfg) == (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576
                                    + 512 * 64 * 256 + 64 * 128 * 6144) \
        == 90_570_752
    # a layer outside its experts: two blocks, two FFNs of 3 x 6144 x 12288,
    # the router 6144 x 768: 638.8 M = 1.278 GB
    assert arch.dense_layer_params(cfg) == 2 * 90_570_752 \
        + 2 * 3 * 6144 * 12288 + 6144 * 768 == 638_844_928
    assert arch.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    # a token's 12 choices fall on a held expert 16 times in 768
    assert arch.held_share(cfg) == 16 / 768 and arch.zero_share(cfg) == 1 / 3
    assert arch.block_matmul_params(cfg) == 4 * (638_844_928 + 37_748_736 / 4)
    assert arch.head_params(cfg) == 6144 * 131072
    # 8 latent rows of 512 + 64 numbers a token, 2 bytes each, no V
    assert arch.kv_bytes_per_token(cfg) == 9216
    assert arch.attention_flops(cfg, 1000) == 2.0 * 64 * 1000 * (576 + 512) * 8
    assert arch.attention_geometry(cfg) == {
        "layers": 8, "heads": 64, "head_dim": 192, "latent_row": 576,
        "value_row": 512}
    stored = 4 * (638_844_928 + 768 + 4 * 6144 + 2 * (1536 + 512)
                  + 16 * 37_748_736) + 2 * 131072 * 6144 + 6144
    assert arch.n_params(cfg) == stored
    assert 13.1e9 < 2 * stored < 13.3e9                         # 13.2 GB
    assert arch.walk(cfg) == [("block", i) for i in range(4)]
    leaves = arch.top_specs(cfg) + [
        (arch.layer_prefix(i) + n, s, k) for i in range(4)
        for n, s, k in arch.layer_specs(cfg, i)]
    assert sum(int(np.prod(s)) for _, s, _ in leaves) == stored


def test_the_traffic_follows_the_issues_rules_at_its_callers():
    t = real_cell().traffic
    e, n = t["engine"], t["clients"]
    assert n == 256 and e["max_seqs"] == n             # the issue's number
    assert e["token_budget"] == n + 64 and t["requests"] == 2 * n
    pages = -(-e["max_model_len"] // e["block_size"])
    assert e["max_model_len"] == t["prompt_len"]["max"] + t["output_len"]["max"]
    assert e["num_blocks"] == n * pages                # nothing is preempted
    assert (t["prompt_len"]["min"], t["prompt_len"]["max"]) == (32, 160)
    assert (t["output_len"]["min"], t["output_len"]["max"]) == (384, 1024)
    assert (t["fill_steps"], t["check_requests"], t["order_seed"]) == (120, 6, 7)
    # the pool: 8 rows of 640 (576 padded to whole lane tiles) a token
    assert e["num_blocks"] * e["block_size"] * 8 * 640 * 2 == 3_103_784_960


def test_counts_equal_a_hand_count_at_the_tiny_size():
    h, inter, width, v = 96, 192, 32, 256
    mla = h * 24 + 24 * 4 * 12 + h * 12 + 8 * 4 * 16 + 4 * 8 * h
    dense = 2 * mla + 2 * 3 * h * inter + h * 24
    assert arch.mla_params(CFG) == mla and arch.dense_layer_params(CFG) == dense
    assert arch.expert_params(CFG) == 3 * h * width
    assert arch.block_matmul_params(CFG) == 2 * (dense + 3 * (4 / 24) * 3 * h * width)
    assert arch.kv_bytes_per_token(CFG) == 2 * 2 * 12 * 2
    assert arch.n_params(CFG) == 2 * (dense + 24 + 4 * h + 2 * (24 + 8)
                                      + 4 * 3 * h * width) + 2 * v * h + h


def test_all_layers_share_one_weight_program_and_the_model_takes_them():
    cfg = dict(CFG, ffn_hidden_size=200)      # lists no other test has made
    before = W._make._cache_size()
    made = W.all_weights(arch, cfg, SEED)
    assert W._make._cache_size() - before == 2       # top, and one layer list
    from bench.lib import system
    model = system.build_model(arch, cfg, SEED)
    assert {n for n, _ in model.named_parameters()} == set(made)
    assert made["lm_head.weight"].shape == (256, 96)
    assert made["model.layers.1.mlp.experts.down_proj"].shape == (4, 32, 96)
    assert made["model.layers.0.mlp.router.classifier.weight"].shape == (96, 24)
    dec = model.__class__.__module__
    assert dec == "paddle_tpu.models.longcat_flash"
    assert (model.config.experts_held, model.config.first_expert,
            model.config.n_routed_experts) == (4, 4, 16)


# -- correct, and what is not ---------------------------------------------------
def test_the_cell_is_correct_through_the_harness(root):
    res = R.execute(spec.Cell(longcat_tiny.CELL, root), tiny.args(seed=SEED),
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
    res = R.execute(spec.Cell(longcat_tiny.CELL, root), tiny.args(),
                    tiny.DEVICE)
    assert res["correct"] is False
    assert res["compared"]["token_gap_max"]["value"] > 1.0


def _sequences(seed):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 256, n).tolist(),
             rng.integers(0, 256, 40).tolist()) for n in (20, 50, 70)]


@pytest.mark.parametrize("stand_in", ("float8",) + FAULTS)
def test_a_stand_in_is_not_correct_on_three_seeds(root, stand_in):
    """The float8 control, and the reference with one part of the
    mathematics left out or wrong, in the program's place: each fails a
    limit on every seed; the reference itself passes."""
    cell = spec.Cell(longcat_tiny.CELL, root)
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


# -- the new readers on two steps of a traced run on a v5e -----------------------
TWO_STEPS = os.path.join(tiny.DATA, "longcat_two_steps.trace.json.gz")
CHAT = os.path.join(tiny.DATA, "chat_two_steps.trace.json.gz")      # PR 26


def test_the_new_readers_read_two_recorded_steps(monkeypatch):
    cell = real_cell()
    # the run these steps were cut from: a gap of 34.2 ms untraced
    quiet = {"itl_mean_s": 0.0342, "itl_p95_s": 0.0357, "engine_step_s": 0.0337}
    ctx = tiny.recorded_context(monkeypatch, TWO_STEPS, cell, **quiet)
    assert len(ctx["measured"]["steps"]) == 2
    got = {m["name"]: cell.reader(m["name"])(ctx, m["name"])
           for m in cell.per_layer()}
    # sampled a step by the kind, or a counter the recording (PR 34) lacks
    host = {"batch_pool_live_share", "attn_tiles_ahead_share.batch.serve"}
    assert {n for n, v in got.items() if v is None} == host
    assert (got["batch_gap_mean_s"], got["batch_gap_p95_s"],
            got["batch_step_s"]) == tuple(quiet.values())
    # busy time a step from the trace over the UNTRACED period: the slice's
    # own length (the profiler's half speed) is in neither
    assert 5 < got["device_idle_share.batch.serve"] < 30
    without = dict(ctx, measured={k: v for k, v in ctx["measured"].items()
                                  if k not in quiet})
    assert cell.reader("device_idle_share.batch.serve")(
        without, "device_idle_share.batch.serve") is None
    for name, value in got.items():
        if name not in host:
            assert value >= 0, name
        if name not in host and (name.endswith("_share.serve")
                                 or "roofline" in name or "mfu" in name):
            assert value <= 100.0, (name, value)
    assert 40 < got["moe_experts_roofline.serve"] <= 100
    assert 2 < got["latent_attn_roofline.serve"] <= 100
    assert 20 < got["step_hbm_roofline.batch.serve"] <= 100
    assert 10 < got["step_mfu.batch.serve"] <= 100
    assert 0.5 < got["expert_held_share.serve"] < 4       # 2.08 % if even
    assert 25 < got["expert_zero_share.serve"] < 42       # 33.3 % if even
    assert 1 <= got["expert_load_peak.serve"] < 6
    parts = sum(got[n + "_time_share.serve"] for n in (
        "moe_experts", "moe_route", "latent_attn", "dense_path", "latent_write"))
    assert 85 < parts <= 100
    t = S.load(TWO_STEPS)
    kernels = [o for o in t["ops"] if o[2].startswith("latent_paged_attention")]
    assert len(kernels) == 2 * 8                 # one call a cache entry
    grouped = [o for o in t["ops"] if o[2].startswith("grouped_experts")]
    assert len(grouped) == 2 * 4                 # one a double layer
    for scope in ("moe_route", "moe_experts", "moe_zero", "latent_attention",
                  "attn_proj", "mlp", "head", "kv_write"):
        assert S.time_in(t, [scope]) > 0, scope
    (emit, _) = S.named(t, "serve.emit")
    assert {"moe_pairs", "moe_pairs_held", "moe_pairs_zero", "moe_peak_tokens",
            "moe_experts_touched", "moe_held_mean_tokens"} <= set(emit[3])


def test_the_new_readers_read_nothing_from_a_program_without_them(monkeypatch):
    """The parent's program has none of the scopes, kernels and counters
    (the chat cell's recorded steps stand for it), and another architecture
    has no expert layer: every new reader answers None and raises nothing."""
    cell = real_cell()
    ctx = tiny.recorded_context(monkeypatch, CHAT, cell)
    new = ("step_mfu.batch.serve", "step_hbm_roofline.batch.serve",
           "moe_experts_roofline.serve", "latent_attn_roofline.serve",
           "moe_experts_time_share.serve", "moe_route_time_share.serve",
           "expert_held_share.serve", "expert_zero_share.serve",
           "expert_load_peak.serve")
    for name in new[2:]:
        assert cell.reader(name)(ctx, name) is None, name
    # the whole-step shares fall back on the adapter's even routing
    for name in new[:2]:
        assert cell.reader(name)(ctx, name) > 0
    other = dict(ctx, arch=importlib.import_module("bench.archs.llama"))
    for name in new[:4] + ("device_idle_share.batch.serve",):
        assert cell.reader(name)(other, name) is None, name
    monkeypatch.setattr(T, "find", lambda _dir: None)
    for name in new[2:]:
        assert cell.reader(name)(ctx, name) is None, name
