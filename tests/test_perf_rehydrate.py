"""Tier-1's hold on PR 33's cell ``rf4-30k-rehydrate`` (its five per-layer
readers, its traffic file, its configuration, its warm-up), collected from
``perf/tests`` the way ``tests/test_perf_verify_wait_reader.py`` collects the
recovery's readers.  ``tests/test_rehydrate.py`` holds the product's side."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_rehydrate_readers import *  # noqa: E402,F401,F403


def test_the_cell_reports_ops_and_setup_end_to_end_and_is_on_no_list_that_was_there():  # noqa: F811
    """PR 37 appended six entries to ``per_layer``: PR 33's five are found by
    their names, not as the list's last five
    (``perf/tests/test_rehydrate_readers.py`` is a benchmark file, and a PR
    that is not a ``benchmark`` PR edits none); the rest of what that test
    held is held here."""
    import test_rehydrate_readers as m

    data = m.run.load_cell(m.base.REPO, m.CELL)
    bench, cell = data["bench"], data["cell"]
    assert cell == {"name": m.CELL, "config": "rf4-n5-30k-rehydrate", "traffic": "ycsb-a-kill1-rehydrate",
                    "chips": 1, "why": cell["why"]} and len(cell["why"]) <= 200
    assert [e["name"] for e in bench["end_to_end"] if m.run.metric_applies(e, m.CELL)] == ["ops_s", "setup_s"]
    keyed = [e for e in bench["per_layer"] if m.CELL in e.get("workloads", ())]
    names = [e["name"] for e in bench["per_layer"]]
    first = names.index(m.READERS[0])
    assert [e["name"] for e in keyed] == m.READERS == names[first:first + 5]
    assert all(e["workloads"] == [m.CELL] and e["moves"] == "ops_s" for e in keyed)
    unkeyed = [e["name"] for e in bench["per_layer"] if "workloads" not in e and e["moves"] == "ops_s"]
    assert len(unkeyed) == 11
    got = m.base.read(m.CELL, dict(m.base.SNAP, platform="tpu",
                                   host_spans={"window": m.base.WINDOW, "probe": m.base.PROBE}))
    assert set(unkeyed) <= set(got)
    assert not [k for k in got if k.startswith(("recovery.", "tail.", "client.", "resync."))]
