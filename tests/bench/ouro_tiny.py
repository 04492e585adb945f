"""``bench_tiny``'s root with one more cell, ``tiny-ouro-closed``: the real
Ouro adapter, reference and kind over a tiny looped configuration (hidden 64,
4 heads, 2 layers x 3 passes, vocabulary 256)."""
from __future__ import annotations

import json
import os

import bench_tiny as tiny

OURO = {"architecture": "ouro", "head_dim": 16, "hidden_act": "silu",
        "hidden_size": 64, "initializer_range": 0.05, "intermediate_size": 128,
        "max_position_embeddings": 256, "num_attention_heads": 4,
        "num_hidden_layers": 2, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 10000.0,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 3, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 256}
CELL = "tiny-ouro-closed"
# bfloat16 runs at these sizes read at most 0.011 and 0.0003 over five seeds;
# the float8 control at least 0.25 and 0.02 over three
LIMITS = {"token_gap_max": 0.06, "token_gap_mean": 0.003}


def make_root(tmp) -> str:
    """``bench_tiny.make_root`` and the looped cell, which reports what the
    closed-loop cell beside it does."""
    root = tiny.make_root(tmp)
    tiny._dump(os.path.join(root, "bench", "configs", "tiny-ouro.json"), OURO)
    tiny._dump(os.path.join(root, "bench", "limits", CELL + ".json"),
               {"limits": LIMITS})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-ouro", "source": "test",
                             "file": "bench/configs/tiny-ouro.json",
                             "reduced": []})
    bench["workloads"].append({"name": CELL, "config": "tiny-ouro",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-gpt-closed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    tiny._dump(path, bench)
    return root
