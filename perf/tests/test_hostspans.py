"""``perf/hostspans.py`` on a small recorded chip trace (TPU v5 lite, PR 24,
``perf/tests/span_shape.py``): two programs under the names the product pins,
the product's ``mochi.*`` spans on two threads, one long gap under
``host_verify``, one under no span."""

import json
import os
import shutil

import pytest

import hostspans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "host_spans.xplane.pb")
TRACED_S = float(open(os.path.join(DATA, "host_spans.traced_seconds.txt")).read())
CAUSES = [c for c, _ in hostspans.CAUSES] + [hostspans.NO_SPAN]


@pytest.fixture(scope="module")
def reduced():
    return hostspans.reduce_file(TRACE, TRACED_S)


def test_launches_per_named_program(reduced):
    assert reduced["device_planes"] == 1
    progs = reduced["programs"]
    assert set(progs) == {hostspans.LADDER_PROGRAM, hostspans.COMB_PROGRAM}
    assert progs[hostspans.LADDER_PROGRAM]["launches"] == 1
    assert progs[hostspans.COMB_PROGRAM]["launches"] == 2
    # an operation is counted under the launch it ran in: the loops have an owner
    assert "while" in progs[hostspans.LADDER_PROGRAM]["ops"] and "while" in progs[hostspans.COMB_PROGRAM]["ops"]
    for p in progs.values():   # a loop's event holds its body's, so the operations do not add up
        assert 0 < max(p["ops"].values()) == p["ops"]["while"] <= p["seconds"]
    assert hostspans.program_ms_per_launch({"probe": reduced}, "probe", hostspans.COMB_PROGRAM) == \
        pytest.approx(1e3 * progs[hostspans.COMB_PROGRAM]["seconds"] / 2)
    assert hostspans.program_ms_per_launch({"probe": reduced}, "probe", "jit_other") is None
    assert hostspans.program_ms_per_launch({}, "probe", hostspans.COMB_PROGRAM) is None


def test_spans_by_name_on_two_threads(reduced):
    spans = reduced["spans"]
    assert {n: s["count"] for n, s in spans.items()} == {
        "mochi.verifier.chunk": 4, "mochi.verifier.flush": 4, "mochi.verifier.prepare": 3,
        "mochi.verifier.dispatch": 3, "mochi.verifier.readback": 3, "mochi.verifier.host_verify": 1,
        "mochi.service.rpc.admit": 3, "mochi.verifier.memo": 3, "mochi.service.rpc.reply": 3,
        "mochi.service.tick": 2,
    }
    loop, flusher = spans["mochi.service.rpc.admit"]["threads"], spans["mochi.verifier.flush"]["threads"]
    assert len(loop) == len(flusher) == 1 and loop != flusher
    assert spans["mochi.service.tick"]["threads"] == loop
    assert spans["mochi.verifier.memo"]["sums"] == {"items": 129}
    assert spans["mochi.service.rpc.reply"]["sums"] == {"wait_us": 21000}
    assert spans["mochi.verifier.chunk"]["sums"] == {"items": 3 * 512 + 43, "wait_us": 3 * 2500 + 2100}
    assert spans["mochi.verifier.host_verify"]["seconds"] == pytest.approx(0.060, abs=0.003)
    assert reduced["routes"]["device"] == {"count": 3, "seconds": pytest.approx(0.031, abs=0.006),
                                           "items": 1536, "buckets": [512]}
    assert reduced["routes"]["host"]["items"] == 43 and reduced["routes"]["host"]["buckets"] == [0]
    assert len(reduced["ticks"]) == 2 and reduced["ticks"][0][0] < reduced["ticks"][1][0]
    assert reduced["ticks"][1][2] - reduced["ticks"][0][2] == pytest.approx(4300, abs=1500)  # epoch_us, ~4 ms apart


def test_idle_by_cause_sums_to_idle(reduced):
    by = reduced["idle_by_cause_s"]
    assert list(by) == CAUSES
    assert sum(by.values()) == pytest.approx(reduced["idle_s"], abs=1e-9)
    assert reduced["idle_s"] == pytest.approx(TRACED_S, abs=1e-3)   # the device ran for microseconds
    assert by["host_verify"] == pytest.approx(0.060, abs=0.003)
    assert by["prepare"] == pytest.approx(0.030, abs=0.003)
    # the three RPCs ran under host_verify, which outranks them: nothing is counted twice
    assert by["rpc_memo"] == 0.0 and by["build"] == 0.0 and by["gc"] == 0.0
    assert by["no_span"] > 0.080


def test_the_longest_gaps_are_labelled_by_what_holds_most_of_each(reduced):
    gaps = reduced["gaps"]
    assert len(gaps) == 10 and gaps == sorted(gaps, key=lambda g: g[1], reverse=True)
    labels = {round(g[1], 2): g[0] for g in gaps[:4]}
    # between the ladder and the first comb launch: the 60 ms of host verification
    under_span = next(g for g in gaps if g[0] == "host_verify")
    assert under_span[1] == pytest.approx(0.061, abs=0.004) and under_span[2] > 0.95
    # between the two comb launches: 80 ms under nothing, then 10 ms of prepare
    under_none = next(g for g in gaps if 0.085 < g[1] < 0.1)
    assert under_none[0] == "no_span" and 0.8 < under_none[2] < 0.95, labels
    assert all(g[0] in CAUSES and 0 < g[2] <= 1 for g in gaps)


def test_interval_arithmetic():
    a = [[0, 10], [20, 30], [40, 50]]
    b = [[5, 25], [45, 60]]
    assert hostspans.intersect(a, b) == [[5, 10], [20, 25], [45, 50]]
    assert hostspans.subtract(a, b) == [[0, 5], [25, 30], [40, 45]]
    assert hostspans.subtract(a, []) == a and hostspans.subtract([], a) == []
    assert hostspans.subtract([[0, 10]], [[0, 3], [4, 6], [9, 12]]) == [[3, 4], [6, 9]]
    assert hostspans.length(hostspans.intersect(a, b)) + hostspans.length(hostspans.subtract(a, b)) == 30


def test_precedence_and_labels_on_made_up_spans():
    spans = [("mochi.service.rpc.admit", 0, 100, 0, {}), ("mochi.verifier.host_verify", 40, 60, 1, {}),
             ("mochi.verifier.build", 50, 55, 2, {}), ("mochi.gc", 90, 130, 0, {}),
             ("mochi.service.tick", 0, 1000, 0, {})]
    idle, seconds, pieces = hostspans.idle_by_cause([[200, 300]], spans, 1000)
    assert idle == [[0, 200], [300, 1000]]
    ns = {c: round(s * 1e9) for c, s in seconds.items()}
    assert ns == {"build": 5, "host_verify": 15, "prepare": 0, "dispatch": 0, "readback": 0, "flush": 0,
                  "rpc_memo": 80, "gc": 30, "no_span": 770}   # the tick is a mark, not a cause
    assert hostspans.label_gaps(idle, pieces) == [["no_span", 7e-7, 1.0], ["rpc_memo", 2e-7, 0.4]]


def test_of_finds_the_newest_runs_traces_and_keeps_the_reduction(tmp_path, reduced, capsys):
    for run_dir, kinds in (("old-1", ("window",)), ("new-2", ("window", "probe"))):
        for kind in kinds:
            d = tmp_path / run_dir / f"trace-{kind}" / "plugins" / "profile" / "2026_01_01"
            d.mkdir(parents=True)
            shutil.copy(TRACE, d / "host.xplane.pb")
    os.utime(tmp_path / "old-1" / "trace-window", (1, 1))
    assert set(hostspans.run_traces(str(tmp_path))) == {"window", "probe"}
    snap = {"platform": "tpu", "trace": {"window": {"window_s": TRACED_S}, "probe": {"window_s": TRACED_S}}}
    got = hostspans.of(snap, out_root=str(tmp_path))
    assert got["window"] == json.loads(json.dumps(reduced)) == got["probe"]
    assert snap["host_spans"] is got and hostspans.of(snap) is got      # one reduction for all readers
    said = capsys.readouterr().out
    assert f"{hostspans.LADDER_PROGRAM} x1" in said and "by cause: host_verify" in said
    assert "3 flushes to the device, 1536 items" in said and "buckets [512]" in said
    # nothing to find, or not a TPU run: an empty reduction, and no exception
    assert hostspans.of({"platform": "tpu", "trace": {"window": {"window_s": 1.0}}}, out_root=str(tmp_path / "x")) == {}
    assert hostspans.of({"platform": "cpu", "trace": snap["trace"]}, out_root=str(tmp_path)) == {}
    assert hostspans.of({"platform": "tpu", "trace": {}}, out_root=str(tmp_path)) == {}
