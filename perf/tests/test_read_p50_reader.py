"""PR 43's reader, ``tail.read_p50_ms``: the window's read median, which tells
the level a run of the two 10,000-record rf=4 cells ran at (PERF.md section 7,
first), on a canned snapshot and on windows that hold nothing for it."""

import json
import os

import pytest

import layer_reader
import run
from test_data_driven import REPO, SNAP

__all__ = [
    "test_the_read_median_is_read_off_the_windows_latencies",
    "test_a_window_without_reads_reports_nothing",
    "test_the_read_median_is_keyed_to_the_two_cells_that_fall_into_levels",
    "test_the_entry_is_the_files",
]

NAME = "tail.read_p50_ms"
PERF = os.path.join(REPO, "perf")


def read(cell, snap):
    data = run.load_cell(REPO, cell)
    return {k: v["value"] for k, v in
            run.read_layer_metrics(data["layer_dir"], data["bench"], cell, snap).items()}


@pytest.mark.parametrize("cell", ["rf4-ycsb-a", "rf4-recover"])
@pytest.mark.parametrize("median", [3.822, 7.741])  # chiprun_out/pr42b: the fast level, the slow one
def test_the_read_median_is_read_off_the_windows_latencies(cell, median):
    snap = dict(SNAP, latency=dict(SNAP["latency"], read_p50_ms=median))
    got = read(cell, snap)
    assert got[NAME] == median
    # and the tail beside it reads what it read before
    assert got["tail.read_p95_ms"] == SNAP["latency"]["read_p95_ms"]


@pytest.mark.parametrize("cell", ["rf4-ycsb-a", "rf4-recover"])
def test_a_window_without_reads_reports_nothing(cell):
    # no read issued: run.py leaves the key out; never a 0
    assert NAME not in read(cell, SNAP)
    assert NAME not in read(cell, dict(SNAP, latency={}))
    # a median that fell on a failed operation is printed, as the tails are
    snap = dict(SNAP, latency={"read_p50_ms": run.FAILED_LATENCY_MS})
    assert read(cell, snap)[NAME] == run.FAILED_LATENCY_MS


def test_the_read_median_is_keyed_to_the_two_cells_that_fall_into_levels():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    # (PR 45 added its cell, the same cluster and mix with a Byzantine member)
    assert entry["workloads"] == ["rf4-ycsb-a", "rf4-recover", "rf4-byz1-ycsb-a"]
    snap = dict(SNAP, latency=dict(SNAP["latency"], read_p50_ms=5.0))
    for cell in (w["name"] for w in bench["workloads"]):
        assert (NAME in read(cell, snap)) == (cell in entry["workloads"]), cell


def test_the_entry_is_the_files():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    mod = layer_reader.load(os.path.join(PERF, "layer_metrics", NAME + ".py"))
    assert {k: entry[k] for k in ("name", "unit", "layer", "moves", "source")} == {
        "name": mod.NAME, "unit": mod.UNIT, "layer": mod.LAYER, "moves": mod.MOVES, "source": mod.SOURCE}
    assert entry["better"] == "lower" and "bound" not in entry
    # the layer and the metric it moves are the read tail's
    beside = next(m for m in bench["per_layer"] if m["name"] == "tail.read_p95_ms")
    assert (entry["layer"], entry["moves"], entry["source"]) == (beside["layer"], beside["moves"], beside["source"])
