"""PR 25's reader, ``verifier.resolve_wait_ms``, on the canned reduction that
``test_span_readers.py`` uses, with and without the span it reads."""

import pytest

import test_span_readers as base

RESOLVE = "mochi.verifier.resolve"
# 200 chunks: 2.4 s between the backend's return and the loop's turn, 4 ms of resolving
WINDOW = dict(base.WINDOW, spans=dict(base.WINDOW["spans"], **{
    RESOLVE: base.span(200, 0.004, items=30_000, wait_us=2_400_000)}))
NAME = "verifier.resolve_wait_ms"


def snapshot(window, probe=base.PROBE):
    return dict(base.SNAP, platform="tpu", host_spans={"window": window, "probe": probe})


@pytest.mark.parametrize("cell", ["n64-ycsb-a", "rf4-ycsb-a"])
def test_resolve_wait_is_the_mean_of_the_hand_over_and_the_resolution(cell):
    got = base.read(cell, snapshot(WINDOW))
    assert got[NAME] == pytest.approx((2400.0 + 4.0) / 200)
    # and the readers beside it read what they read before
    assert got["verifier.queue_wait_ms"] == pytest.approx(base.EXPECT["verifier.queue_wait_ms"])


@pytest.mark.parametrize("cell", ["n64-ycsb-a", "rf4-ycsb-a"])
def test_a_service_without_the_span_reports_nothing_for_it(cell):
    # the parent commit's service: every other span, no resolve span
    got = base.read(cell, snapshot(base.WINDOW))
    assert NAME not in got and "verifier.queue_wait_ms" in got
    # the span only in the probe's trace is not the window's
    probe = dict(base.PROBE, spans=dict(base.PROBE["spans"], **{RESOLVE: base.span(2, 0.0001, wait_us=900)}))
    assert NAME not in base.read(cell, snapshot(base.WINDOW, probe))
    # an untraced run, and a window in which no chunk was flushed
    assert NAME not in base.read(cell, dict(base.SNAP))
    idle = dict(base.WINDOW, spans=dict(base.WINDOW["spans"], **{RESOLVE: base.span(0, 0.0)}))
    assert NAME not in base.read(cell, snapshot(idle))


def test_the_entry_names_the_layer_and_metric_the_queue_wait_does():
    data = base.run.load_cell(base.REPO, "n64-ycsb-a")
    by_name = {m["name"]: m for m in data["bench"]["per_layer"]}
    mine, beside = by_name[NAME], by_name["verifier.queue_wait_ms"]
    # PR 26 keyed both to the cells whose windows send the service a verify RPC
    # (a read-only cell's service records neither span)
    assert mine["workloads"] == ["n64-ycsb-a", "rf4-ycsb-a", "rf4-recover", "rf4-50k-recover"]
    assert {k: mine[k] for k in mine if k != "name"} == {k: beside[k] for k in beside if k != "name"}
