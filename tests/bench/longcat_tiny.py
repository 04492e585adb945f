"""``bench_tiny``'s root with one more cell, ``tiny-longcat-closed``: the real
LongCat-Flash adapter, reference and kind over a tiny share (hidden 96, 4
heads, a latent row of 8 + 4, 2 double layers, 4 experts held of 16 routed
and 8 zero, top 3, vocabulary 256)."""
from __future__ import annotations

import json
import os

import bench_tiny as tiny

LONGCAT = {"architecture": "longcat_flash", "attention_bias": False,
           "vocab_size": 256, "hidden_size": 96, "ffn_hidden_size": 192,
           "expert_ffn_hidden_size": 32, "num_layers": 2,
           "num_attention_heads": 4, "kv_lora_rank": 8, "q_lora_rank": 24,
           "qk_rope_head_dim": 4, "v_head_dim": 8, "qk_nope_head_dim": 8,
           "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
           "routed_scaling_factor": 6, "n_routed_experts": 4,
           "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
           "rope_theta": 10000.0, "attention_method": "MLA",
           "zero_expert_num": 8, "zero_expert_type": "identity",
           "moe_topk": 3, "first_expert": 4, "initializer_range": 0.05,
           "router_init_std": 0.3, "router_bias_std": 0.02,
           "published": {"num_layers": 28, "n_routed_experts": 16},
           "reduced": {"num_layers": "test", "n_routed_experts": "test"}}
CELL = "tiny-longcat-closed"
# bfloat16 runs at these sizes read at most 0.0004 and 0.00002 over five
# seeds; the float8 control at least 1.36 and 0.021 over three, the mildest
# fault (the bias used in the weight) at least 0.38 and 0.0058
LIMITS = {"token_gap_max": 0.2, "token_gap_mean": 0.004}


def make_root(tmp) -> str:
    """``bench_tiny.make_root`` and the cell, which reports what the real
    one does: ``serve_tok_s`` and ``setup_s``, and the per-layer metrics
    that list ``longcat560-serve-batch``."""
    root = tiny.make_root(tmp)
    tiny._dump(os.path.join(root, "bench", "configs", "tiny-longcat.json"),
               LONGCAT)
    tiny._dump(os.path.join(root, "bench", "limits", CELL + ".json"),
               {"limits": LIMITS})
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-longcat", "source": "test",
                             "file": "bench/configs/tiny-longcat.json",
                             "reduced": ["num_layers", "n_routed_experts"]})
    bench["workloads"].append({"name": CELL, "config": "tiny-longcat",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "test"})
    mine = {m["name"] for m in real["end_to_end"] + real["per_layer"]
            if "longcat560-serve-batch" in m.get("workloads", ())}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in mine:
            m["workloads"] = m["workloads"] + [CELL]
    tiny._dump(path, bench)
    return root
