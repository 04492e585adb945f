"""A benchmark in miniature for the tests: the real harness, kinds, readers
and references over tiny configurations in a temporary root, on the CPU."""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

LLAMA = {"architecture": "llama", "hidden_act": "silu", "hidden_size": 64,
         "initializer_range": 0.05, "intermediate_size": 128,
         "max_position_embeddings": 256, "num_attention_heads": 4,
         "num_hidden_layers": 2, "num_key_value_heads": 2,
         "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "sliding_window": None,
         "tie_word_embeddings": False, "vocab_size": 256}
GPT2 = {"architecture": "gpt2", "activation_function": "gelu",
        "initializer_range": 0.05, "layer_norm_epsilon": 1e-5, "n_embd": 64,
        "n_head": 4, "n_inner": 128, "n_layer": 2, "n_positions": 128,
        "tie_word_embeddings": True, "vocab_size": 256}
TRAIN = {"kind": "train-batches", "batch": 4, "seq": 64, "lr": 3e-4,
         "weight_decay": 0.1, "clip_norm": 1.0, "remat": "full"}
ENGINE = {"max_seqs": 4, "token_budget": 16, "block_size": 8,
          "max_model_len": 128, "num_blocks": 64}
CHAT = {"kind": "open-loop-paced", "engine": ENGINE, "rate": 8.0, "tail_s": 1.0,
        "jitter": 0.5, "order_seed": 3,
        "prompt_len": {"shape": "lognormal", "median": 24, "sigma": 0.7,
                       "min": 8, "max": 60},
        "output_len": {"shape": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 4, "max": 16},
        "check_requests": 3}
# eight callers: the comparison draws from the first half of the fill, whose
# outputs the kind cuts to an eighth .. a half, so the outputs are long enough
# for four of them to hold some thirty tokens
CLOSED = {"kind": "closed-loop", "engine": dict(ENGINE, max_seqs=8, num_blocks=96),
          "clients": 8, "requests": 24, "fill_steps": 4, "order_seed": 3,
          "prompt_len": {"shape": "uniform", "min": 8, "max": 24},
          "output_len": {"shape": "uniform", "min": 12, "max": 40},
          "check_requests": 3}
# set from readings at these sizes on the CPU: bfloat16 runs read loss gaps of
# 5e-6..5e-5, gradient gaps of 0.001..0.005 and change gaps of 0.001..0.012
TRAIN_LIMITS = {"loss1_gap": 8e-5, "loss2_gap": 8e-5, "loss3_gap": 8e-5,
                "grad_norm_gap": 0.02, "change_norm_gap": 0.03}
# bfloat16 runs at these sizes read at most 0.02 and 0.0007; float8 0.16 and 0.0075
SERVE_LIMITS = {"token_gap_max": 0.1, "token_gap_mean": 0.005}

CELLS = {
    "tiny-gpt-train": ("tiny-gpt", GPT2, "tiny-train", TRAIN, TRAIN_LIMITS),
    "tiny-llama-train": ("tiny-llama", LLAMA, "tiny-train", TRAIN, TRAIN_LIMITS),
    "tiny-llama-chat": ("tiny-llama", LLAMA, "tiny-chat", CHAT, SERVE_LIMITS),
    "tiny-gpt-closed": ("tiny-gpt", GPT2, "tiny-closed", CLOSED, SERVE_LIMITS),
}


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(tmp) -> str:
    """A root holding a tiny ``BENCHMARK.json`` and its data files; the
    metric files are the benchmark's own, copied."""
    root = str(tmp)
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    shutil.copytree(os.path.join(REPO, "bench", "metrics"),
                    os.path.join(root, "bench", "metrics"))
    configs, workloads = {}, []
    for cell, (cname, cfg, tname, traffic, limits) in CELLS.items():
        _dump(os.path.join(root, "bench", "configs", cname + ".json"), cfg)
        _dump(os.path.join(root, "bench", "traffic", tname + ".json"), traffic)
        _dump(os.path.join(root, "bench", "limits", cell + ".json"),
              {"limits": limits})
        configs[cname] = {"name": cname, "source": "test",
                          "file": f"bench/configs/{cname}.json", "reduced": []}
        workloads.append({"name": cell, "config": cname, "traffic": tname,
                          "chips": 1, "why": "test"})

    def retarget(metrics):
        out = []
        for m in metrics:
            m = dict(m)
            if "workloads" in m:
                kinds = {json.load(open(os.path.join(
                    REPO, "bench", "traffic", w["traffic"] + ".json")))["kind"]
                    for w in real["workloads"] if w["name"] in m["workloads"]}
                m["workloads"] = [c for c, v in CELLS.items()
                                  if v[3]["kind"] in kinds]
            out.append(m)
        return out

    _dump(os.path.join(root, "BENCHMARK.json"), {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 2, "configs": list(configs.values()),
        "workloads": workloads, "end_to_end": retarget(real["end_to_end"]),
        "per_layer": retarget(real["per_layer"])})
    return root


def args(seed=1, seconds=1.5, trace=0):
    return argparse.Namespace(seed=seed, seconds=seconds, trace=trace)


DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def recorded_steps(path):
    """The step records a kind would have kept for the steps of a recorded
    trace, from its ``serve.run`` spans: rows from their counts; each
    sequence's live context taken as its walked pages, whole: an overcount."""
    from bench.lib import spans as S
    steps = []
    for run in S.named(S.load(path), "serve.run"):
        a = run[3]
        rows = int(a["prefill_tokens"]) + int(a["decode_tokens"])
        live = 16 * int(a.get("pages_walked", 0))
        steps.append((0.0, 0.0, rows, int(a["decode_tokens"]), live, live,
                      None, 0))
    return steps


def recorded_context(monkeypatch, path, cell, steps=None, **quiet):
    """What ``run.collect`` hands a reader, over a recorded trace: the
    reduction of the one device, and a slice whose steps are ``steps``
    (``recorded_steps`` of the trace itself unless given: a run's records do
    not shrink when the export is cut); ``quiet``: what the kind measured on
    the host's clock before the profiler started."""
    from bench.lib import trace as T
    monkeypatch.setattr(T, "find", lambda _dir: path)
    steps = recorded_steps(path) if steps is None else steps
    reduced = T.reduce_dir("/nowhere", 1.0, len(steps))
    return {"cell": cell, "arch": cell.arch(), "trace": reduced, "notes": {},
            "measured": dict(quiet, steps=steps, slice=(0, len(steps))),
            "peaks": PEAKS}
