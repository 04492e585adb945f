"""A copy of the repository's own benchmark grown as the next configuration
would grow it: by files and appended entries alone, no file or entry that is
there edited. It adds one configuration (the tiny LongCat-Flash share with
its cuts stated: depth, 8 experts held of 32, a quarter of the vocabulary),
one cell judged on ``serve_tok_s`` and ``setup_s``, and two per-layer entries
at the END of ``per_layer``: ``OWN``, which lists the new cell alone, and
``SHARED``, which lists both batch cells too and counts an argument that no
recorded program writes (a windowed attention's pages, as a model with
sliding-window layers would bring)."""
from __future__ import annotations

import json
import os
import shutil

import bench_tiny as tiny
import longcat_tiny

CELL, CONFIG, TRAFFIC = "next-mla-batch", "next-mla-ep8-l2", "next-closed"
OWN, SHARED = "next_requests_done", "window_page_share.serve"
BATCH_CELLS = ["longcat560-serve-batch", "nemotron120-serve-batch"]
MLA = dict(
    longcat_tiny.LONGCAT, n_routed_experts=8, first_expert=8, vocab_size=320,
    published={"num_layers": 28, "n_routed_experts": 32, "vocab_size": 1280},
    reduced={"num_layers": "test", "n_routed_experts": "test",
             "vocab_size": "rows 0..319 of 1,280"})


def make_root(tmp) -> str:
    """The repository's ``BENCHMARK.json`` and the data files it names,
    copied to ``tmp``, and the files and entries above."""
    root = str(tmp)
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), root)
    bench = os.path.join(root, "bench")
    for d in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(tiny.REPO, "bench", d),
                        os.path.join(bench, d))
    tiny._dump(os.path.join(bench, "configs", CONFIG + ".json"), MLA)
    tiny._dump(os.path.join(bench, "traffic", TRAFFIC + ".json"), tiny.CLOSED)
    tiny._dump(os.path.join(bench, "limits", CELL + ".json"),
               {"limits": longcat_tiny.LIMITS})
    tiny._dump(os.path.join(bench, "metrics", OWN + ".json"),
               {"reader": "measured", "field": "requests", "unit": "1",
                "layer": "scheduler / KV pool", "moves": "serve_tok_s"})
    tiny._dump(os.path.join(bench, "metrics", SHARED + ".json"),
               {"reader": "span_counts", "span": "serve.run",
                "num": ["window_pages_walked"], "den": ["pages_walked"],
                "unit": "%", "layer": "kernels, serving",
                "moves": "serve_tok_s"})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": CONFIG, "source": "test",
                          "file": f"bench/configs/{CONFIG}.json",
                          "reduced": sorted(MLA["reduced"]), "why": "test"})
    bm["workloads"].append({"name": CELL, "config": CONFIG,
                            "traffic": TRAFFIC, "chips": 1, "why": "test"})
    for m in bm["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append(CELL)
    bm["per_layer"].append({
        "name": OWN, "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "scheduler / KV pool",
        "moves": "serve_tok_s", "workloads": [CELL]})
    bm["per_layer"].append({
        "name": SHARED, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels, serving",
        "moves": "serve_tok_s", "workloads": [CELL] + BATCH_CELLS})
    tiny._dump(path, bm)
    return root
