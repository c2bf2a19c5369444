"""Tier-1's hold on perf/run.py's helpers (the warm-up's reach, the trace's start, the keyed metrics): the tests live with the benchmark
(``perf/tests/test_run_helpers.py``); this file collects them from there, the way
``tests/test_perf_hostspans.py`` collects the trace reduction's."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_run_helpers import *  # noqa: E402,F401,F403


def test_the_readers_are_keyed_to_their_cells_and_the_five_lists_gained_the_new_one():  # noqa: F811
    """PR 46 appended fourteen entries to ``per_layer``: PR 45's five are found
    by their names, not as the list's last five
    (``perf/tests/test_byzantine_cell.py`` is a benchmark file, and a PR that is
    not a ``benchmark`` PR edits none); the rest of what that test held is held
    here."""
    import test_byzantine_cell as m

    bench = m.json.load(open(m.os.path.join(m.REPO, "BENCHMARK.json")))
    by_name = {e["name"]: e for e in bench["per_layer"]}
    names = [e["name"] for e in bench["per_layer"]]
    first = names.index(m.READERS[0])
    assert names[first:first + 5] == m.READERS + [m.BUILT]
    assert all(by_name[n]["workloads"] == [m.CELL] and by_name[n]["moves"] == "ops_s"
               and by_name[n]["source"] == "program_counter" for n in m.READERS)
    assert by_name[m.BUILT]["workloads"] == ["n64-ycsb-a", "n64-ycsb-c", m.CELL]
    for name in ("tail.update_p95_ms", "tail.read_p95_ms", "tail.read_p50_ms", "client.write1_p50_ms.ops",
                 "client.write2_wait_p50_ms.ops", "device.idle_share.ops"):
        assert by_name[name]["workloads"][-1] == m.CELL and by_name[name]["workloads"].count(m.CELL) == 1
    snap = dict(m.snapshot(), latency=dict(m.SNAP["latency"], read_p50_ms=5.0))
    for cell in (w["name"] for w in bench["workloads"]):
        got = m.read(cell, snap)
        for name in m.READERS + [m.BUILT]:
            assert (name in got) == (cell in by_name[name]["workloads"]), (cell, name)


def test_the_cell_is_what_the_issue_states():  # noqa: F811
    """PR 46 appended a configuration and a cell: PR 45's are counted among
    them, not as eight and six; the rest of what that test held is held here."""
    import test_byzantine_cell as m

    data = m.run.load_cell(m.REPO, m.CELL)
    bench, cell = data["bench"], data["cell"]
    assert cell == {"name": m.CELL, "config": m.CONFIG, "traffic": "ycsb-a", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "forge-cert" in cell["why"]
    assert [e["name"] for e in bench["end_to_end"] if m.run.metric_applies(e, m.CELL)] == ["ops_s", "setup_s"]
    assert [w["name"] for w in bench["workloads"]].index(m.CELL) == 7
    assert [c["name"] for c in bench["configs"]].index(m.CONFIG) == 5
    assert not [w for w in bench["workloads"] if w["chips"] != 1]
    twin = m.run.load_cell(m.REPO, "rf4-ycsb-a")
    assert data["traffic"] == twin["traffic"] and "faults" not in data["traffic"]
    assert all(data["config"][k] == twin["config"][k] for k in (
        "replicas", "rf", "f", "quorum", "recordcount", "threads", "generator_processes", "load_threads",
        "replica_processes", "storage_engine", "wal_fsync", "admission", "fast_path", "rehearsal"))
    assert m.run.warm_reach(384, {512, 8192}, 3, 32, 32,
                            m.run.replay_items(data["config"], data["verbs"])) == 0
