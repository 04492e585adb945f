"""The next configuration arrives as files and appended entries alone: on a
copy of the repository's benchmark grown by one configuration, one cell and
two per-layer entries at the end of ``per_layer``, one of them also on the
batch cells' lists (``grown_tiny``), every rule of the contract holds, and so
does each cell's own check as the nemotron, longcat and ouro tests hold it,
over its files and over its recorded steps."""
import json
import os

import pytest

import bench_contract as contract
import bench_recorded
import bench_tiny as tiny
import grown_tiny as grown
import test_bench_longcat as longcat
import test_bench_nemotron as nemotron
import test_bench_ouro as ouro
from bench.lib import spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return grown.make_root(tmp_path_factory.mktemp("grown_root"))


def _benchmark(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_root_only_appends(root):
    """What was there stands as it was and in its place; the two entries
    come after ``nemotron120-serve-batch``'s own, which are no longer last
    (the place an older check of that cell held them to)."""
    real, bm = _benchmark(tiny.REPO), _benchmark(root)
    for key in ("command", "paths", "run_seconds"):
        assert bm[key] == real[key]
    for key in ("configs", "workloads", "per_layer"):
        assert bm[key][:len(real[key])] == real[key]
    assert [m["name"] for m in bm["per_layer"][len(real["per_layer"]):]] \
        == [grown.OWN, grown.SHARED]
    for was, now in zip(real["end_to_end"], bm["end_to_end"]):
        cells = [c for c in now.get("workloads", ()) if c != grown.CELL]
        assert dict(now, workloads=cells) == dict(was, workloads=cells)
    names = [m["name"] for m in bm["per_layer"]]
    assert tuple(names[-len(nemotron.NEW):]) != nemotron.NEW


def test_every_rule_of_the_contract_holds(root):
    for rule in contract.EVERY:
        rule(root)


def ouro_cell(root):
    """The looped cell keeps the metrics its test reads, each moving
    ``itl_p95_s`` as its file says, over a reader that is there."""
    cell = spec.Cell("ouro26-serve-decode", root)
    mine = {m["name"]: m for m in cell.per_layer()}
    assert set(ouro.NEW) <= set(mine)
    for name in ouro.NEW:
        f = cell.metric_file(name)
        assert mine[name]["moves"] == "itl_p95_s"
        assert (f["unit"], f["layer"], f["moves"]) == (
            mine[name]["unit"], mine[name]["layer"], mine[name]["moves"])
        assert callable(cell.reader(name))


@pytest.mark.parametrize("check", (nemotron.nemotron_cell,
                                   contract.batch_cell, ouro_cell),
                         ids=("nemotron", "longcat", "ouro"))
def test_each_cells_own_check_holds(root, check):
    check(root)


@pytest.mark.parametrize("test", (nemotron, longcat),
                         ids=("nemotron", "longcat"))
def test_each_batch_cells_recorded_steps_read_as_before(root, monkeypatch,
                                                        test):
    """The appended entry on the batch cells' lists counts an argument the
    recordings lack: the rule names it among what reads nothing, and every
    other reading of the recorded steps holds as it does in the repository."""
    cell = spec.Cell(test.CELL, root)
    assert grown.SHARED in {m["name"] for m in cell.per_layer()}
    assert grown.SHARED in bench_recorded.uncounted(cell, test.TWO_STEPS)
    test.read_two_recorded_steps(monkeypatch, root)


def test_the_new_cell_reads_its_own_entries(root, monkeypatch):
    cell = spec.Cell(grown.CELL, root)
    assert {m["name"] for m in cell.end_to_end()} == {"serve_tok_s",
                                                      "setup_s"}
    assert [m["name"] for m in cell.per_layer()] == [grown.OWN, grown.SHARED]
    ctx = {"cell": cell, "measured": {"requests": 7}}
    assert cell.reader(grown.OWN)(ctx, grown.OWN) == 7
    ctx = tiny.recorded_context(monkeypatch, longcat.TWO_STEPS, cell)
    assert cell.reader(grown.SHARED)(ctx, grown.SHARED) is None
