"""The counts that say how far the paged attention kernel engages:
``pages_walked`` / ``pages_tabled`` and ``attn_tiles`` / ``attn_tiles_ahead``
on the engine's ``serve.run`` span against a hand count, and the benchmark's
metric files over them (``paged_walk_share.serve``,
``attn_tiles_ahead_share.serve``) against the names that exist (reader, span,
arguments, cells)."""
import gzip
import importlib
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler as prof
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import EngineConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
METRIC = json.load(open(os.path.join(
    REPO, "bench", "metrics", "paged_walk_share.serve.json")))
BENCHMARK = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
TWO_STEPS = os.path.join(REPO, "tests", "bench", "data",
                         "chat_two_steps.trace.json.gz")   # cut in PR 26


@pytest.fixture(scope="module")
def run_spans():
    """The ``serve.run`` spans' arguments of an engine that serves three
    prompts: one step of three prefill entries, then decode steps."""
    paddle.seed(3)
    cfg = LlamaConfig.tiny(vocab_size=61, hidden_size=32, layers=2, heads=4,
                           kv_heads=2, seq=64)
    cfg.use_flash_attention = False
    eng = ServingEngine(LlamaForCausalLM(cfg), EngineConfig(
        max_seqs=4, token_budget=48, block_size=4, num_blocks=64))
    rng = np.random.default_rng(0)
    with prof.Profiler() as p:
        for n in (21, 6, 11):
            eng.submit(rng.integers(1, 61, n).tolist(), max_new_tokens=3)
        while eng.step():
            pass
    assert eng.max_pages_per_seq == 16
    runs = [e["args"] for e in p._events if e["name"] == "serve.run"]
    # the engine keeps the sums; a step that schedules nothing opens no
    # ``serve.run`` and counts nothing
    assert eng.telemetry()["attention_tiles"] == {
        "attn_tiles": sum(a["attn_tiles"] for a in runs),
        "attn_tiles_ahead": sum(a["attn_tiles_ahead"] for a in runs)}
    assert not eng.step() and eng._attn_tiles(eng.sched.schedule()) == 0
    assert len([e for e in p._events if e["name"] == "serve.run"]) \
        == len(runs)
    return runs


def test_pages_walked_and_tabled_equal_the_hand_count(run_spans):
    prefill, first_decode, second_decode = run_spans[:3]
    # three entries, contexts 21, 6 and 11 in pages of 4: 6 + 2 + 3
    assert prefill["prefill_tokens"] == 21 + 6 + 11
    assert prefill["pages_walked"] == 6 + 2 + 3
    # the sampled token is fed at positions 21, 6, 11: contexts 22, 7, 12
    assert first_decode["decode_tokens"] == 3
    assert first_decode["pages_walked"] == 6 + 2 + 3
    # ... then 23, 8, 13: the third sequence opens its fourth page
    assert second_decode["pages_walked"] == 6 + 2 + 4
    # what the gather read: the whole table once a packed row, every step
    assert {a["pages_tabled"] for a in run_spans} == {48 * 16}


def test_attn_tiles_and_those_fetched_ahead_equal_the_hand_count(run_spans):
    prefill, first_decode = run_spans[:2]
    # rows 21, 6 and 11 in tiles of 16: 2 + 1 + 1; all but the step's first
    # tile find their first pages fetched by the tile before
    assert (prefill["attn_tiles"], prefill["attn_tiles_ahead"]) == (4, 3)
    # a decode row is a tile
    assert (first_decode["attn_tiles"],
            first_decode["attn_tiles_ahead"]) == (3, 2)
    assert all(a["attn_tiles_ahead"] == a["attn_tiles"] - 1
               for a in run_spans)


# the batch cell's twin (``.batch.serve``, ``serve_tok_s``) is not declared:
# ``tests/bench/test_bench_longcat.py`` holds that cell to PR 34's 23 metrics
@pytest.mark.parametrize("name,moves,cells", [
    ("attn_tiles_ahead_share.serve", "itl_p95_s",
     ["mistral7b-serve-chat", "cgpt67-serve-decode", "ouro26-serve-decode"]),
])
def test_the_tiles_ahead_metric_files_name_what_exists(run_spans, name, moves,
                                                       cells):
    spec = json.load(open(os.path.join(REPO, "bench", "metrics",
                                       name + ".json")))
    assert spec["reader"] == "span_counts" and spec["span"] == "serve.run"
    assert (spec["num"], spec["den"]) == (["attn_tiles_ahead"],
                                          ["attn_tiles"])
    assert set(spec["num"]) | set(spec["den"]) <= set(run_spans[0])
    (entry,) = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    # appended by PR 35, after everything PR 34 left
    assert BENCHMARK["per_layer"].index(entry) >= 62
    assert entry == {"name": name, "unit": "%", "better": "higher",
                     "source": "program_counter",
                     "layer": "kernels, serving", "moves": moves,
                     "workloads": cells}
    assert (spec["unit"], spec["layer"], spec["moves"]) \
        == (entry["unit"], entry["layer"], entry["moves"])


def test_the_metric_file_names_what_exists(run_spans):
    reader = importlib.import_module("bench.readers." + METRIC["reader"])
    assert callable(reader.read)
    assert METRIC["span"] == "serve.run"
    assert set(METRIC["num"]) | set(METRIC["den"]) <= set(run_spans[0])
    (entry,) = [m for m in BENCHMARK["per_layer"]
                if m["name"] == "paged_walk_share.serve"]
    # appended by PR 27; what later PRs add comes after it
    assert BENCHMARK["per_layer"].index(entry) == 35
    assert (entry["unit"], entry["layer"], entry["moves"]) \
        == (METRIC["unit"], METRIC["layer"], METRIC["moves"])
    # the serving cells that report the metric it moves (a cell judged on
    # serve_tok_s alone, longcat560-serve-batch, is on none of its lists)
    (moved,) = [m for m in BENCHMARK["end_to_end"]
                if m["name"] == entry["moves"]]
    serving = [w["name"] for w in BENCHMARK["workloads"]
               if w["name"] in moved["workloads"]]
    assert len(serving) == 3
    assert entry["workloads"] == serving and entry["better"] == "lower"
    assert entry["source"] == "program_counter"


def test_the_tiles_ahead_share_of_the_served_steps_is_the_hand_count(
        monkeypatch, run_spans):
    from bench.lib import spans as S
    from bench.readers import span_counts
    tiles = json.load(open(os.path.join(
        REPO, "bench", "metrics", "attn_tiles_ahead_share.serve.json")))

    class TilesCell(Cell):
        def metric_file(self, name):
            return tiles

    two = [(20 * i, 10, "serve.run", {k: str(v) for k, v in a.items()})
           for i, a in enumerate(run_spans[:2])]
    monkeypatch.setattr(S, "of_run", lambda ctx: {"spans": two, "ops": []})
    # 3 of the prefill step's 4 tiles and 2 of the decode step's 3
    assert span_counts.read({"cell": TilesCell()}, "m") \
        == pytest.approx(100.0 * 5 / 7)


class Cell:
    root, name = "/nowhere", "cell"

    def metric_file(self, name):
        return METRIC


def test_the_reader_sums_over_the_spans_that_carry_the_counts(monkeypatch):
    from bench.lib import spans as S
    from bench.readers import span_counts
    spans = [(0, 10, "serve.run", {"pages_walked": "11",
                                   "pages_tabled": "768"}),
             (20, 10, "serve.run", {"pages_walked": "12",
                                    "pages_tabled": "768"}),
             (40, 10, "serve.run", {"prefill_tokens": "5"}),  # an older step
             (60, 10, "serve.step", {})]
    monkeypatch.setattr(S, "of_run", lambda ctx: {"spans": spans, "ops": []})
    assert span_counts.read({"cell": Cell()}, "m") \
        == pytest.approx(100.0 * 23 / 1536)


@pytest.mark.parametrize("trace", [TWO_STEPS, None])
def test_the_reader_reads_nothing_from_a_program_without_the_counts(
        monkeypatch, trace):
    """PR 26's recorded chat steps have ``serve.run`` spans with the four
    older counts only, as the parent commit's program has; ``span_ratio``
    raises KeyError there, this reader leaves the metric out. No trace at
    all reads nothing too."""
    from bench.lib import spans as S, trace as T
    from bench.readers import span_counts, span_ratio
    monkeypatch.setattr(T, "find", lambda _dir: trace)
    ctx = {"cell": Cell()}
    assert span_counts.read(ctx, "m") is None
    if trace:
        with gzip.open(trace) as f:
            assert b"serve.run" in f.read()
        assert S.named(S.load(trace), "serve.run")
        with pytest.raises(KeyError):
            span_ratio.read(ctx, "m")
