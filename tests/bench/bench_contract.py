"""What ``BENCHMARK.json`` and the files it names must hold, as functions of a
root: the tests hold the repository to them, and ``test_bench_harness`` holds
a COPY of the repository's benchmark with one more configuration, cell and
two metrics to them too, so that what a later PR brings as files is judged by
the rules and not by the cells that were there on the day the rules were
written. Each rule's assertion says which rule it is."""
from __future__ import annotations

import importlib
import json
import os
import re

import bench_tiny as tiny
from bench.lib import spec

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
# a width is never cut: sizes, latent and projection ranks, head sizes, by
# their suffix; the GPT-2 style names of the same; the experts a token takes
WIDTH_SUFFIXES = ("_dim", "_rank", "_size")
WIDTH_NAMES = {"n_embd", "n_inner", "d_model", "d_ff", "expand"}
PER_TOKEN = re.compile(r"(per_tok|top_?k)")
EXPERTS_HELD = re.compile(r"(^|_)experts?($|_)")
VOCABULARY_FLOOR = 8          # at least an eighth of the published rows
EXPERTS_FLOOR = 8             # at least 8 routed experts held


def _json(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def reduced_keys(entry, held):
    """What ``reduced`` may list (the ``model-configs`` guide, section 4):
    depth, the experts held here and the rows of the vocabulary held here,
    the last two down to their floors; never a width."""
    assert set(entry["reduced"]) == set(held["reduced"]), \
        "BENCHMARK.json and the configuration's file list other reduced keys"
    for key in entry["reduced"]:
        assert held[key] != held["published"][key], \
            f"{key} is listed as reduced and equals the published value"
        if key == "vocab_size":
            assert held[key] * VOCABULARY_FLOOR >= held["published"][key], \
                f"a vocabulary of {held[key]} rows is under an eighth of the " \
                f"published {held['published'][key]}"
            continue
        assert not (key.endswith(WIDTH_SUFFIXES) or key in WIDTH_NAMES
                    or PER_TOKEN.search(key)), \
            f"{key}: a width may not be cut, only depth, the experts held " \
            "and the rows of the vocabulary"
        if EXPERTS_HELD.search(key):
            assert held[key] >= EXPERTS_FLOOR, \
                f"{key}: {held[key]} experts held, under the floor of 8"


def benchmark_json(root, perf_md=os.path.join(tiny.REPO, "PERF.md")):
    """``BENCHMARK.json`` against the limits of its contract."""
    bm = _json(root, "BENCHMARK.json")
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= bm["run_seconds"] <= 51
    cells = [w["name"] for w in bm["workloads"]]
    configs = [c["name"] for c in bm["configs"]]
    assert len(set(cells)) == len(cells) and len(set(configs)) == len(configs)
    assert {w["config"] for w in bm["workloads"]} == set(configs)
    assert len({(w["config"], w["traffic"]) for w in bm["workloads"]}) == len(cells)
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(1, len(cells) // 4)
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(root, c["file"]))
        assert len(c["why"]) <= 200 and NAME.match(c["name"])
        assert len(c["reduced"]) <= 16
        reduced_keys(c, _json(root, c["file"]))
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert os.path.exists(os.path.join(
            root, "bench", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            root, "bench", "limits", w["name"] + ".json"))
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(set(names)) == len(names), "two metrics of one name"
    layers = set()
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(
            root, "bench", "metrics", m["name"] + ".json"))
        moved = e2e[m["moves"]].get("workloads", cells)
        astray = set(m.get("workloads", cells)) - set(moved)
        assert not astray, f"{m['name']} moves {m['moves']}, which " \
            f"{sorted(astray)} does not report"
        assert set(m.get("workloads", cells)) <= set(cells)
        layers.add(m["layer"])
    with open(perf_md) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    for cell in cells:          # every cell: setup_s, one more, one per-layer
        mine = [m for m in bm["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(mine) >= 2, f"{cell} reports setup_s alone"
        assert any(cell in m.get("workloads", cells) for m in bm["per_layer"]), \
            f"{cell} has no per-layer metric"
    assert len(json.dumps(bm)) < 64 * 1024


# -- the batch cell (PR 34), by name ---------------------------------------------
BATCH_CELL = "longcat560-serve-batch"
BATCH_METRICS = frozenset({
    "step_mfu.batch.serve", "step_hbm_roofline.batch.serve",
    "device_idle_share.batch.serve", "batch_step_s", "batch_host_s",
    "batch_gap_mean_s", "batch_gap_p95_s", "batch_pool_live_share",
    "moe_experts_time_share.serve", "moe_experts_inferred_share.serve",
    "moe_route_time_share.serve", "moe_route_inferred_share.serve",
    "latent_attn_time_share.serve", "latent_attn_inferred_share.serve",
    "dense_path_time_share.serve", "dense_path_inferred_share.serve",
    "latent_write_time_share.serve", "latent_write_inferred_share.serve",
    "moe_experts_roofline.serve", "latent_attn_roofline.serve",
    "expert_held_share.serve", "expert_zero_share.serve",
    "expert_load_peak.serve"})                              # the 23 of PR 34
BATCH_METRICS_SINCE = frozenset({"attn_tiles_ahead_share.batch.serve"})  # PR 36


def batch_cell(root):
    """The batch cell's files load and name each other; it keeps its metrics
    by name, each moving ``serve_tok_s`` and listing the cell (other cells may
    stand on the same lists, and the cell may get more metrics), and it
    stands on no list whose ``moves`` it does not report."""
    from bench.archs import longcat_flash as arch
    from bench.reference import longcat_flash_block as ref
    cell = spec.Cell(BATCH_CELL, root)
    assert cell.arch() is arch and cell.chips == 1
    assert cell.kind().__name__ == "bench.kinds.closed_loop"
    assert importlib.import_module(arch.REFERENCE) is ref
    reported = {m["name"] for m in cell.end_to_end()}
    assert reported == {"serve_tok_s", "setup_s"}
    mine = {m["name"]: m for m in cell.per_layer()}
    assert len(BATCH_METRICS) == 23
    assert BATCH_METRICS | BATCH_METRICS_SINCE <= set(mine), \
        f"the batch cell lost {sorted((BATCH_METRICS | BATCH_METRICS_SINCE) - set(mine))}"
    for name, m in mine.items():
        assert m["moves"] in reported, \
            f"{BATCH_CELL} stands on {name}'s list, which moves {m['moves']}"
        assert m["moves"] == "serve_tok_s" and BATCH_CELL in m["workloads"]
        f = cell.metric_file(name)
        assert (f["unit"], f["layer"], f["moves"]) == (
            m["unit"], m["layer"], m["moves"])
        assert callable(cell.reader(name))
    limits = _json(root, "bench", "limits", BATCH_CELL + ".json")["limits"]
    assert set(limits) == {"token_gap_max", "token_gap_mean"}
    for name in {n for n, _ in arch.walk(cell.config)} | {"embed", "head"}:
        assert callable(getattr(ref, name))
    return cell


EVERY = (benchmark_json, batch_cell)
