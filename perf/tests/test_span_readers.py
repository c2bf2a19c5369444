"""The readers that this PR adds, each on a canned reduction of a run's two
traces (``perf/hostspans.py``'s output, under the snapshot's ``host_spans``),
and on snapshots that hold nothing for them."""

import pytest

import hostspans
import run
import canned_faults as canned
from test_data_driven import REPO, SNAP


def span(count, seconds, **sums):
    return {"count": count, "seconds": seconds, "threads": [0], "sums": sums}


WINDOW = {
    "window_s": 5.0, "device_planes": 1,
    "programs": {hostspans.COMB_PROGRAM: {"launches": 4, "seconds": 0.0064, "ops": {}}},
    "spans": {
        "mochi.service.rpc.reply": span(8000, 0.4, wait_us=160_000_000),
        "mochi.service.rpc.admit": span(8000, 0.8),
        "mochi.verifier.memo": span(8000, 0.5, items=344_000),
        "mochi.verifier.chunk": span(200, 0.9, items=30_000, wait_us=1_200_000),
        "mochi.verifier.flush": span(200, 0.85, items=30_000),
        "mochi.verifier.prepare": span(4, 0.012),
        "mochi.gc": span(2, 0.025),
    },
    "routes": {"host": {"count": 196, "seconds": 0.70, "items": 7000, "buckets": [0]},
               "device": {"count": 4, "seconds": 0.15, "items": 3000, "buckets": [1024]}},
    "ticks": [[100_000_000, 50_000_000, 1], [1_100_000_000, 50_900_000, 2], [4_100_000_000, 53_400_000, 3]],
    "idle_s": 4.99,
    "idle_by_cause_s": {"build": 0.0, "host_verify": 0.7, "prepare": 0.012, "dispatch": 0.002, "readback": 0.03,
                        "flush": 0.006, "rpc_memo": 1.2, "gc": 0.02, "no_span": 3.02},
    "gaps": [["no_span", 0.4, 0.9]],
}
PROBE = {
    "window_s": 0.1, "device_planes": 1,
    "programs": {hostspans.LADDER_PROGRAM: {"launches": 1, "seconds": 0.0055, "ops": {}},
                 hostspans.COMB_PROGRAM: {"launches": 1, "seconds": 0.0016, "ops": {}}},
    "spans": {"mochi.verifier.prepare": span(2, 0.008)},
    "routes": {"device": {"count": 2, "seconds": 0.05, "items": 1024, "buckets": [512]}},
    "ticks": [], "idle_s": 0.09, "idle_by_cause_s": {"no_span": 0.05}, "gaps": [],
}
EXPECT = {
    "service.loop_cpu_share": 100 * (53.4 - 50.0) / 4.0 / 1.0,   # 3.4 CPU-s over 4.0 s between ticks
    "service.rpc_ms": (160_000.0 + 400.0) / 8000,
    "service.memo_us_per_item": 1e6 * 0.5 / 344_000,
    "verifier.queue_wait_ms": 1200.0 / 200,
    "verifier.flush_busy_share": 100 * 0.85 / 5.0,
    "verifier.host_us_per_item": 1e6 * 0.70 / 7000,
    "verifier.device_us_per_item": 1e6 * (0.15 + 0.05) / (3000 + 1024),
    "service.gc_pause_share": 100 * 0.025 / 5.0,
    "prepare.us_per_item": 1e6 * (0.012 + 0.008) / (3000 + 1024),
    "kernel.ladder_ms": 5.5,
    "kernel.comb_ms": 1.6,
    "device.idle_attributed_share": 100 * (1 - 3.02 / 4.99),
}
assert EXPECT["service.loop_cpu_share"] == pytest.approx(85.0)


def read(cell, snap):
    data = run.load_cell(REPO, cell)
    return {k: v["value"] for k, v in
            run.read_layer_metrics(data["layer_dir"], data["bench"], cell, snap).items()}


@pytest.mark.parametrize("name", sorted(EXPECT))
@pytest.mark.parametrize("cell", ["n64-ycsb-a", "rf4-ycsb-a"])
def test_each_new_reader_on_the_canned_traces(cell, name):
    # every one moves ops_s and lists no cells, so both cells report it
    snap = dict(SNAP, platform="tpu", host_spans={"window": WINDOW, "probe": PROBE})
    assert read(cell, snap)[name] == pytest.approx(EXPECT[name])


def test_a_trace_without_spans_reports_only_what_the_device_plane_holds():
    # the parent commit's service leaves no mochi.* span; its programs carry the same names
    bare = {k: dict(v, spans={}, routes={}, ticks=[]) for k, v in (("window", WINDOW), ("probe", PROBE))}
    got = read("n64-ycsb-a", dict(SNAP, platform="tpu", host_spans=bare))
    assert {k for k in got if k in EXPECT} == {"kernel.ladder_ms", "kernel.comb_ms"}


def test_nothing_to_read_is_nothing_reported():
    # an untraced or CPU snapshot, a run whose traces were not found, a window without a second tick
    for extra in ({}, {"host_spans": {}}, {"platform": "tpu", "trace": {"window": {"window_s": 5.0, "busy_s": 0.0}}}):
        snap = dict(SNAP, **extra)
        got = read("rf4-ycsb-a", snap)
        assert not set(got) & set(EXPECT), extra
        assert snap["host_spans"] == {}   # kept, so the readers share one look
    one_tick = dict(WINDOW, ticks=WINDOW["ticks"][:1])
    got = read("rf4-ycsb-a", dict(SNAP, platform="tpu", host_spans={"window": one_tick}))
    assert "service.loop_cpu_share" not in got and "service.rpc_ms" in got
    assert "kernel.ladder_ms" not in got and "verifier.device_us_per_item" in got  # no probe trace here


# ------------------------------------------- the recovery's own two readers

RECOVERY_CELLS = ["rf4-recover", "rf4-50k-recover"]


def replayed(device_items, flushed_ms=None, trace=None):
    """A kill and a restart whose replay sent ``device_items`` signatures to the
    device; ``flushed_ms``: the service's flush-device timer (before, after)."""
    faults = canned.records()
    back = faults[1]
    back["after"] = dict(back["after"], service=dict(canned.SERVICE1, device_items=device_items))
    if flushed_ms is not None:
        for look, ms in zip(("before", "after"), flushed_ms):
            timers = {"verifier.flush-host": {"count": 9, "sum_ms": 7.0}}
            if ms is not None:
                timers["verifier.flush-device"] = {"count": 3, "sum_ms": ms}
            back[look] = dict(back[look], service_stages={"timers": timers, "counters": {}})
    return dict(SNAP, platform="tpu", faults=faults, cluster={"quorum": 3}, trace=trace or {})


@pytest.mark.parametrize("snap,expect", [
    (replayed(76_800, (100.0, 2020.0)), 25.0),       # 1.92 s of device-route flushes for 76,800 items
    (replayed(768, (None, 19.2)), 25.0),             # the timer had never ticked before the restart
    (replayed(0, (100.0, 100.0)), None),             # rf4-recover's shape: the memo answered the replay
    (replayed(768), None),                           # a look without the stage timers
    (dict(SNAP, platform="tpu"), None),              # a cell without a schedule
])
def test_the_replays_device_route_is_timed_per_item_where_it_took_it(snap, expect):
    got = read("rf4-50k-recover", snap).get("recovery.device_us_per_item")
    assert got == (pytest.approx(expect) if expect is not None else None)
    # keyed to the cell whose replay reaches the device: rf4-recover's never does, and a
    # listed cell has to report what it lists
    assert "recovery.device_us_per_item" not in read("rf4-recover", snap)


@pytest.mark.parametrize("cell", RECOVERY_CELLS)
@pytest.mark.parametrize("trace,platform,expect", [
    # the restart's command came 10.02 s into the window and READY 4 s later
    ({"window_s": 5.0, "busy_s": 1.25, "started_s": 10.01}, "tpu", 25.0),
    ({"window_s": 5.0, "busy_s": 0.0, "started_s": 10.01}, "tpu", 0.0),     # the memo answered it
    ({"window_s": 5.0, "busy_s": 1.25, "started_s": 25.0}, "tpu", None),    # a trace of the window's end
    ({"window_s": 5.0, "busy_s": 1.25}, "tpu", None),                       # nobody said when it started
    ({"window_s": 5.0, "busy_s": 1.25, "started_s": 10.01}, "cpu", None),
])
def test_device_busy_share_reads_the_trace_that_covers_the_recovery(cell, trace, platform, expect):
    snap = dict(replayed(768), platform=platform, trace={"window": trace})
    got = read(cell, snap).get("recovery.device_busy_share")
    assert got == (pytest.approx(expect) if expect is not None else None)
    # and none of it is there where nothing was restarted
    assert "recovery.device_busy_share" not in read(cell, dict(snap, faults=[]))


def test_the_new_cell_reports_what_rf4_recover_reports_for_its_recovery():
    bench = run.load_cell(REPO, "rf4-50k-recover")["bench"]
    keyed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
             if "rf4-recover" in m.get("workloads", ())}
    both = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
            if "rf4-50k-recover" in m.get("workloads", ())}
    # all of them but the read median (PR 43), which tells the two levels only the 10,000-record cells
    # fall into; the update tail is end to end in neither since PR 43 (rf4-ycsb-a alone keeps it)
    assert keyed - both == {"tail.read_p50_ms"}
    # and since PR 43 both read their update path under the ``.ops`` names; what the 50,000-record
    # cell alone has is the replay's device time an item (the 10,000-record replay sends the chip none)
    assert both - keyed == {"recovery.device_us_per_item"}
    assert {m + ".ops" for m in (
        "client.write1_p50_ms", "client.write2_wait_p50_ms", "verifier.items_per_flush",
        "verifier.device_item_share", "device.idle_share", "store.fsyncs_per_update")} | {
            "tail.update_p95_ms"} <= both & keyed
