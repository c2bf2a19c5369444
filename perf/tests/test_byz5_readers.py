"""PR 46's cell ``n16-byz5-ycsb-a``: its fourteen readers on canned snapshots
(ten that hand on to an accepted reader, four that read the SDK's grant
counters, its marks and its timers' runs), what each gives where there is
nothing to read (the parent's SDK keeps no grant counters; another cell's
snapshot), that the entries are the files, and that the configuration and the
cell are what the issue states.  ``test_rehearsal_byz5.py`` walks the cell end
to end on the CPU, by hand; ``tests/test_byzantine_mix.py`` holds the
product's side."""

import json
import os

import pytest

import layer_reader
import reference_members as members_ref
import run
import test_byzantine_cell as byz1

REPO, PERF = byz1.REPO, byz1.PERF
CELL, CONFIG = "n16-byz5-ycsb-a", "n16-f5-byz5"
STATED = {"server-1": "forge-cert", "server-4": "forge-cert", "server-7": "forge-cert",
          "server-10": "stale-replay", "server-13": "stale-replay"}
HANDED_ON = {  # reader -> the accepted reader it hands on to
    "byz5.lies_per_op": "byz.lies_per_op",
    "byz5.bad_grants_per_update": "byz.bad_grants_per_update",
    "byz5.read_fallback_share": "byz.read_fallback_share",
    "byz5.callers_avoiding_members_share": "byz.callers_avoiding_member_share",
    "byz5.certificates_built_share": "client.certificates_built_share",
    "byz5.update_p95_ms": "tail.update_p95_ms",
    "byz5.read_p95_ms": "tail.read_p95_ms",
    "byz5.write1_p50_ms": "client.write1_p50_ms",
    "byz5.write2_wait_p50_ms": "client.write2_wait_p50_ms",
    "byz5.device_idle_share": "device.idle_share",
}
COUNTED = ["byz5.voting_grant_share", "byz5.stale_grants_dropped_per_update",
           "byz5.members_caught_by_own_kind_share", "byz5.attempts_again_per_update"]
READERS = list(HANDED_ON) + COUNTED
SERVERS = [f"server-{i}" for i in range(16)]


def reports(mutated):
    """``replica_counters(...)["byzantine"]`` of sixteen replicas whose
    ``/status`` names its strategy: the five members at ``mutated`` changed
    answers each, eleven honest."""
    honest = {"equivocations": {}, "bad_grants": {}, "resync_bad_certificates": 0,
              "strategy": None, "mutated_responses": 0, "dropped_requests": 0}
    return {"byzantine": {sid: dict(honest, strategy=STATED.get(sid),
                                    mutated_responses=mutated if sid in STATED else 0)
                          for sid in SERVERS}}


GRANTS = {  # 500 updates acknowledged in 520 Write1 rounds; 16 answers a round but for 40 left behind
    "client.grants-received": 8_280, "client.grants-voting": 5_500, "client.grants-dropped-signature": 1_490,
    "client.grants-dropped-timestamp": 1_000, "client.grants-refused": 70, "client.grants-unused": 220,
}


def snapshot(members=STATED, grants=True, **gained):
    """``SNAP`` (1,000 operations answered, 500 of them updates) as a window of
    the new cell: each member changed 800 answers."""
    marks = {f"suspect.bad-grant.{sid}": 480 for sid, s in STATED.items() if s == "forge-cert"}
    marks.update({f"suspect.grant-conflict.{sid}": 495 for sid, s in STATED.items() if s == "stale-replay"})
    marks.update({"suspect.tally-outvoted.server-1": 470, "suspect.tally-outvoted.server-10": 12,
                  "suspect.grant-conflict.server-3": 6, "suspect.tally-outvoted.server-5": 4})
    total = dict(marks, **{"calls.read-transactions": 505, "calls.write-transactions": 530,
                           "client.certificates-built": 505, "client.certificates-received": 5_555},
                 **(GRANTS if grants else {}), **gained)
    counters = {"sum": total, "callers": {"calls.read-transactions": 32},
                "marks": {sid: [3] * 8 + [40] * 24 for sid in STATED} | {"server-3": [1] * 6}}
    snap = byz1.snapshot(members=members, before=reports(100), after=reports(900))
    return dict(snap, cluster={"replicas": 16, "rf": 16, "f": 5, "quorum": 11, "byzantine": members},
                generator=dict(snap["generator"], sdk_counters=counters))


def test_the_fourteen_readers_on_a_canned_snapshot():
    got = byz1.read(CELL, snapshot())
    assert [n for n in READERS if n not in got] == ["byz5.device_idle_share"]  # a CPU snapshot has no trace
    assert got["byz5.lies_per_op"] == pytest.approx(5 * 800 / 1000)
    assert got["byz5.bad_grants_per_update"] == pytest.approx(3 * 480 / 500)
    assert got["byz5.read_fallback_share"] == pytest.approx(100.0 * 5 / 500)
    assert got["byz5.callers_avoiding_members_share"] == 100.0
    assert got["byz5.certificates_built_share"] == pytest.approx(100.0 / 11)
    assert got["byz5.update_p95_ms"] == 900.0 and got["byz5.read_p95_ms"] == 400.0
    assert got["byz5.write1_p50_ms"] == pytest.approx(2.0) and got["byz5.write2_wait_p50_ms"] == pytest.approx(20.0)
    assert got["byz5.voting_grant_share"] == pytest.approx(100.0 * 5_500 / 8_280)
    assert got["byz5.stale_grants_dropped_per_update"] == pytest.approx(2.0)
    assert got["byz5.members_caught_by_own_kind_share"] == 100.0
    assert got["byz5.attempts_again_per_update"] == pytest.approx(30 / 500)
    # the canned counters add up as the product's must
    assert members_ref.grant_identity(GRANTS) == 0
    tpu = dict(snapshot(), platform="tpu", trace={"window": {"device_planes": 1, "busy_s": 0.005, "window_s": 5.0}})
    assert byz1.read(CELL, tpu)["byz5.device_idle_share"] == pytest.approx(99.9)


@pytest.mark.parametrize("name,target", sorted(HANDED_ON.items()))
def test_a_reader_that_hands_on_reads_what_the_accepted_reader_reads(name, target):
    snap = dict(snapshot(), platform="tpu", latency={"update_p95_ms": 311.0, "read_p95_ms": 77.0},
                trace={"window": {"device_planes": 1, "busy_s": 0.05, "window_s": 5.0}})
    mine = layer_reader.load(os.path.join(PERF, "layer_metrics", name + ".py"))
    theirs = layer_reader.load(os.path.join(PERF, "layer_metrics", target + ".py"))
    assert mine.read(snap) == theirs.read(snap) is not None
    assert (mine.UNIT, mine.LAYER, mine.SOURCE) == (theirs.UNIT, theirs.LAYER, theirs.SOURCE)
    assert mine.MOVES == "ops_s"
    # and nothing where that gives nothing
    assert mine.read(byz1.SNAP) == theirs.read(byz1.SNAP)


def test_a_member_caught_by_another_strategys_kind_alone_is_not_caught_by_its_own():
    # a forger that is only ever outvoted, a replayer whose grants never conflict: 3 of 5
    swapped = snapshot()
    total = swapped["generator"]["sdk_counters"]["sum"]
    del total["suspect.bad-grant.server-7"], total["suspect.grant-conflict.server-13"]
    total["suspect.tally-outvoted.server-7"] = 400
    total["suspect.bad-grant.server-13"] = 400
    assert byz1.read(CELL, swapped)["byz5.members_caught_by_own_kind_share"] == pytest.approx(60.0)
    # a replayer that is outvoted at the read tally alone IS caught by its own kind
    outvoted = snapshot()
    del outvoted["generator"]["sdk_counters"]["sum"]["suspect.grant-conflict.server-10"]
    assert byz1.read(CELL, outvoted)["byz5.members_caught_by_own_kind_share"] == 100.0


@pytest.mark.parametrize("why,snap,silent", [
    ("the parent's SDK keeps no grant counters", snapshot(grants=False), COUNTED[:2]),
    ("no member stated", snapshot(members={}), ["byz5.lies_per_op", "byz5.bad_grants_per_update",
                                                 "byz5.callers_avoiding_members_share", COUNTED[2]]),
    ("a window without an acknowledged update", dict(snapshot(), updates_ok=0),
     ["byz5.bad_grants_per_update", COUNTED[1], COUNTED[3]]),
    ("a generator that keeps no counters", dict(snapshot(), generator=dict(byz1.SNAP["generator"])),
     ["byz5.bad_grants_per_update", "byz5.read_fallback_share", "byz5.callers_avoiding_members_share",
      "byz5.certificates_built_share"] + COUNTED),
])
def test_a_reader_that_finds_nothing_gives_nothing_and_never_a_zero(why, snap, silent):
    got = byz1.read(CELL, snap)
    assert [n for n in READERS if n not in got and n != "byz5.device_idle_share"] == silent, why


def test_every_reader_is_keyed_to_the_new_cell_alone():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    first = [m["name"] for m in bench["per_layer"]].index(READERS[0])
    assert [m["name"] for m in bench["per_layer"][first:first + len(READERS)]] == READERS
    assert all(by_name[n]["workloads"] == [CELL] and by_name[n]["moves"] == "ops_s" for n in READERS)
    snap = dict(snapshot(), latency=dict(byz1.SNAP["latency"], read_p50_ms=5.0))
    for cell in (w["name"] for w in bench["workloads"]):
        got = byz1.read(cell, snap)
        assert [n for n in READERS if n in got] == (READERS[:9] + COUNTED if cell == CELL else []), cell
    # no accepted entry's list gained the cell: the accepted readers it needs are handed on to
    assert not [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ()) and m["name"] not in READERS]


@pytest.mark.parametrize("name", READERS)
def test_the_entries_are_the_files(name):
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = layer_reader.load(os.path.join(PERF, "layer_metrics", name + ".py"))
    assert {k: getattr(mod, k.upper()) for k in ("name", "unit", "layer", "moves", "source")} == \
        {k: entry[k] for k in ("name", "unit", "layer", "moves", "source")}
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    accepted = {m["layer"] for m in bench["per_layer"] if not m["name"].startswith("byz5.")}
    assert entry["layer"] in accepted  # a layer the benchmark already names, letter for letter
    assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_the_configuration_is_what_the_issue_states():
    new = run.load_cell(REPO, CELL)["config"]
    assert (new["replicas"], new["rf"], new["f"], new["quorum"]) == (16, 16, 5, 11)
    assert (new["recordcount"], new["fieldcount"], new["fieldlength"]) == (10_000, 10, 100)
    assert new["byzantine"] == STATED and run.stated_members(new) == STATED
    assert (new["replica_processes"], new["threads"], new["generator_processes"], new["load_threads"]) == (16, 32, 4, 32)
    assert list(new["reduced"]) == ["recordcount"] and new["memo_items"] == 65_536 and new["chips"] == 1
    assert new["rehearsal"] == {"replicas": 16, "rf": 16, "f": 5, "quorum": 11, "recordcount": 96,
                                "replica_processes": 4, "threads": 4, "generator_processes": 2, "load_threads": 8}
    twin = json.load(open(os.path.join(PERF, "configs", "rf4-n5-byz1.json")))
    for key in ("storage_engine", "wal_fsync", "admission", "fast_path", "transport", "verifier",
                "fieldcount", "fieldlength", "recordcount", "threads", "generator_processes", "load_threads"):
        assert new[key] == twin[key], key
    assert len(new["guarantees"]) == 4 and new["guarantees"][1] == twin["guarantees"][1]
    assert new["guarantees"][0] == twin["guarantees"][0].replace("3", "11")
    assert new["guarantees"][2] == twin["guarantees"][2].replace("3 grants", "11 grants")
    assert "eleven honest members" in new["guarantees"][3] and "no honest replica is accused" in new["guarantees"][3]
    assert {"members", "strategies", "message_delay_ms", "field_packing", "device_state"} <= set(new["assumed"])
    assert all(word in new["assumed"]["strategies"] for word in ("silent", "equivocate", "storm", "session-attack"))
    assert "tests/test_bigcluster.py" in new["source_detail"]
    entry = next(c for c in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["configs"] if c["name"] == CONFIG)
    assert entry["source"] == new["source"] and len(new["source"]) <= 200 and "BASELINE.json configs[2]" in new["source"]
    assert entry["reduced"] == ["recordcount"] and entry["file"] == "perf/configs/n16-f5-byz5.json"
    assert len(entry["why"]) <= 200
    # the arithmetic the deployment stands on: eleven honest of sixteen, no slack
    a = members_ref.arithmetic(new["rf"], new["f"], new["quorum"], len(STATED))
    assert a["holds"] and a["slack"] == 0 and a["voting_share"] == 68.75


def test_the_cell_is_what_the_issue_states():
    data = run.load_cell(REPO, CELL)
    bench, cell = data["bench"], data["cell"]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "ycsb-a", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "rf4-byz1-ycsb-a" in cell["why"] and "11 of 11" in cell["why"]
    assert [m["name"] for m in bench["end_to_end"] if run.metric_applies(m, CELL)] == ["ops_s", "setup_s"]
    assert [w["name"] for w in bench["workloads"]][-1] == CELL and [c["name"] for c in bench["configs"]][-1] == CONFIG
    assert not [w for w in bench["workloads"] if w["chips"] != 1]
    assert data["traffic"] == run.load_cell(REPO, "rf4-byz1-ycsb-a")["traffic"] and data["verbs"] == []
    # 32 writers of 11-grant certificates can pile up past the crossover: every bucket is offered, as at n=64
    assert run.warm_reach(384, {512, 8192}, 11, 32, 32, run.replay_items(data["config"], data["verbs"])) == 8192


def test_the_swapped_control_exchanges_the_first_two_members_that_differ():
    import control_swapped

    assert control_swapped.swapped(STATED) == dict(STATED, **{"server-1": "stale-replay", "server-10": "forge-cert"})
    with pytest.raises(SystemExit):
        control_swapped.swapped({"server-1": "forge-cert", "server-2": "forge-cert"})
