"""PR 37's cell ``rf4-30k-resync``: its six readers on a canned kill and
resyncing restart (``canned_resync.py``), what its traffic file, its verb and
its configuration are held to before a boot, and what the harness offers it in
warm-up.  ``test_rehearsal_resync.py`` walks the cell end to end on the CPU, by
hand."""

import asyncio
import json
import os
import time
import types

import pytest

import canned_resync as canned
import reference as ref
import run
import schedule
import test_span_readers as base

CELL = "rf4-30k-resync"
PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIX = os.path.join(PERF, "traffic", "ycsb-a-kill1-resync.json")
FAULTS = os.path.join(PERF, "faults")
READERS = ["resync.ready_s", "resync.catchup_ms", "resync.digest_ms", "resync.delta_share",
           "resync.pulled_per_adopted", "resync.device_item_share"]
DIGEST_READERS = ["resync.digest_ms"]     # what the parent commit's report cannot give


def snapshot(platform="tpu", **kwargs):
    return dict(base.SNAP, platform=platform, faults=canned.records(**kwargs), cluster={"quorum": 3})


def test_the_six_readers_on_the_canned_resync():
    got = base.read(CELL, snapshot())
    assert {k: got[k] for k in READERS} == {
        "resync.ready_s": 7.2,
        "resync.catchup_ms": 1_900.0,
        # its own walks alone: the round trips of four pulls overlap them and each other
        "resync.digest_ms": 350.0,
        "resync.delta_share": pytest.approx(100 * 1_600 / 24_000),
        "resync.pulled_per_adopted": pytest.approx(2_000 / 1_600),
        # the service's gain between the two looks: 768 of 2,000 signatures on the device
        "resync.device_item_share": pytest.approx(100 * 768 / (768 + 1232)),
    }


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("snap", [
    dict(base.SNAP),                                              # a cell without a schedule
    dict(snapshot(), faults=[]),
    dict(snapshot(), faults=canned.records()[:1]),                # killed, not back
])
def test_each_reader_says_nothing_where_nothing_was_restarted(name, snap):
    assert name not in base.read(CELL, snap)


@pytest.mark.parametrize("name", READERS[1:])
def test_each_reader_of_the_report_says_nothing_where_the_replica_keeps_none(name):
    # a plain restart (the control's): a ``storage`` without ``resync``, or a None
    assert name not in base.read(CELL, snapshot(report=None))
    plain = snapshot()
    plain["faults"][1]["after"]["replica"]["storage"]["resync"] = None
    assert name not in base.read(CELL, plain)


def test_on_the_parent_commits_report_the_digest_reader_says_nothing_and_the_others_read():
    got = base.read(CELL, snapshot(report=canned.PARENT_REPORT))
    assert not [k for k in DIGEST_READERS if k in got]
    assert {k for k in READERS if k in got} == set(READERS) - set(DIGEST_READERS)
    assert got["resync.catchup_ms"] == 1_900.0 and got["resync.pulled_per_adopted"] == pytest.approx(1.25)


def test_the_ratios_need_something_to_divide_by():
    nothing = dict(canned.REPORT, entries_adopted=0)
    got = base.read(CELL, snapshot(report=nothing))
    assert "resync.pulled_per_adopted" not in got and got["resync.delta_share"] == 0.0
    empty = snapshot()
    empty["faults"][1]["after"]["replica"]["store"]["keys_live"] = 0
    assert "resync.delta_share" not in base.read(CELL, empty)
    quiet = snapshot()
    quiet["faults"][1]["after"]["service"] = quiet["faults"][1]["before"]["service"]
    assert "resync.device_item_share" not in base.read(CELL, quiet)


def test_the_cell_reports_ops_and_setup_end_to_end_and_is_on_no_list_that_was_there():
    data = run.load_cell(base.REPO, CELL)
    bench, cell = data["bench"], data["cell"]
    assert cell == {"name": CELL, "config": "rf4-n5-30k-resync", "traffic": "ycsb-a-kill1-resync",
                    "chips": 1, "why": cell["why"]} and len(cell["why"]) <= 200
    assert [m["name"] for m in bench["end_to_end"] if run.metric_applies(m, CELL)] == ["ops_s", "setup_s"]
    keyed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert [m["name"] for m in keyed] == READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "ops_s" for m in keyed)
    # the readers that apply unkeyed: the eleven that move ops_s, as in rf4-30k-rehydrate
    unkeyed = [m["name"] for m in bench["per_layer"] if "workloads" not in m and m["moves"] == "ops_s"]
    assert len(unkeyed) == 11
    got = base.read(CELL, dict(base.SNAP, platform="tpu", host_spans={"window": base.WINDOW, "probe": base.PROBE}))
    assert set(unkeyed) <= set(got)
    assert not [k for k in got if k.startswith(("recovery.", "rehydrate.", "tail.", "client."))]
    config = next(c for c in bench["configs"] if c["name"] == "rf4-n5-30k-resync")
    assert config["reduced"] == ["recordcount"] and len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert config["source"] == data["config"]["source"]


def test_the_configuration_is_the_rehydration_cells_but_for_what_the_issue_lists():
    new = run.load_cell(base.REPO, CELL)["config"]
    old = json.load(open(os.path.join(PERF, "configs", "rf4-n5-30k-rehydrate.json")))
    differ = {k for k in set(new) | set(old) if new.get(k) != old.get(k)}
    assert differ == {"name", "source", "recovery", "guarantees", "reduced", "assumed"}
    assert new["recordcount"] == 30_000 and list(new["reduced"]) == ["recordcount"]
    assert new["reduced"]["recordcount"].startswith(old["reduced"]["recordcount"])
    assert new["guarantees"][:3] == old["guarantees"][:3]
    assert "resync=complete" in new["guarantees"][3] and "OWN storage directory" in new["recovery"]
    assert set(new["assumed"]) == (set(old["assumed"]) - {"lost_disk"}) | {"kept_disk", "data_dir"}
    assert new["memo_items"] == 65_536 and new["rehearsal"] == old["rehearsal"]


def verbs():
    return schedule.validate(json.load(open(MIX))["faults"], FAULTS)


def test_the_mix_is_the_issues_and_its_schedule_binds_on_this_configuration_and_not_at_n64():
    mix = json.load(open(MIX))
    assert mix["faults"] == [{"at_s": 2.0, "do": "kill_replica", "replica": "seeded"},
                             {"at_s": 4.0, "do": "restart_replica_resync", "replica": "same"}]
    assert (mix["readproportion"], mix["updateproportion"], mix["requestdistribution"],
            mix["zipfian_constant"], mix["loop"]) == (0.5, 0.5, "zipfian", 0.99, "closed")
    kill, back = verbs()
    assert kill.KILLS and back.RESTARTS and back.END_TO_END == "recover_s"
    events = schedule.bind(mix["faults"], [kill, back], 2**31 + 77, 30.0, 5, 1, {f"server-{i}": i for i in range(5)})
    assert [e["do"] for e in events] == ["kill_replica", "restart_replica_resync"]
    assert events[0]["server_id"] == events[1]["server_id"]
    # the rehydration cell's instants and the same draw: the same fault, with the disk
    other = json.load(open(os.path.join(PERF, "traffic", "ycsb-a-kill1-rehydrate.json")))
    assert [(e["at_s"], e["replica"]) for e in mix["faults"]] == [(e["at_s"], e["replica"]) for e in other["faults"]]
    # the window's trace starts at the restart's command
    assert run.trace_from_s(events, 30.0, run.TRACE_SECONDS) == 4.0
    # n64 packs 64 replicas on 11 processes: a kill takes a whole process
    with pytest.raises(schedule.ScheduleError, match="shares its process"):
        schedule.bind(mix["faults"], [kill, back], 7, 30.0, 64, 21, {f"server-{i}": i % 11 for i in range(64)})


def test_the_verb_refuses_a_product_that_cannot_pass_the_flag(monkeypatch):
    from mochi_tpu.testing.process_cluster import ProcessCluster

    async def restart_replica(self, server_id):   # a product before PR 33
        return None

    monkeypatch.setattr(ProcessCluster, "restart_replica", restart_replica)
    with pytest.raises(schedule.ScheduleError, match="takes no 'resync'"):
        schedule.load_verb(FAULTS, "restart_replica_resync")
    with pytest.raises(run.RunFailure, match="takes no 'resync'"):
        run.load_cell(base.REPO, CELL)


def cluster_with(tmp_path, restart_replica, report="as it boots"):
    """What the verb takes of a ``PerfCluster``, over a storage root that holds one replica's log.  The
    replica's ``/status`` shows ``report`` as its last full pass: by default one that begins as it is read."""
    (tmp_path / "server-3").mkdir(exist_ok=True)
    (tmp_path / "server-3" / "wal-0.log").write_bytes(b"x")

    def replica_status(server_id, wait_s=0.0):
        rep = dict(canned.REPORT, began_epoch_us=time.time_ns() // 1000) if report == "as it boots" else report
        return {"storage": {"engine": "wal", "resync": rep}}

    return types.SimpleNamespace(storage_root=str(tmp_path), restart_replica=restart_replica,
                                 replica_status=replica_status, n_servers=5)


def test_the_verb_leaves_the_directory_and_passes_resync(tmp_path):
    seen = []

    async def restart_replica(server_id, resync=False):
        seen.append((server_id, resync, sorted(os.listdir(tmp_path / server_id))))

    back = verbs()[1]
    pc = cluster_with(tmp_path, restart_replica)
    timed = asyncio.run(back.run(pc, {"server_id": "server-3"}, {}))
    assert seen == [("server-3", True, ["wal-0.log"])] and set(timed) == {"ready_s"}
    # the parent commit's report (no ``began_epoch_us``, no digest counters) is held to the rest
    asyncio.run(back.run(cluster_with(tmp_path, restart_replica, canned.PARENT_REPORT), {"server_id": "server-3"}, {}))
    # an emptied directory is the OTHER cell's fault, not this one's
    os.unlink(tmp_path / "server-3" / "wal-0.log")
    with pytest.raises(AssertionError):
        asyncio.run(back.run(pc, {"server_id": "server-3"}, {}))
    assert len(seen) == 2


NOW_US = canned.REPORT["began_epoch_us"]
ONE_ABANDONED = dict(canned.REPORT, by_peer=dict(canned.REPORT["by_peer"], **{
    "server-4": dict(canned.REPORT["by_peer"]["server-4"], abandoned=1)}))


@pytest.mark.parametrize("report,why", [
    (None, "no resync pass on record"),                                     # a plain restart, or a flag that does nothing
    ({}, "no resync pass on record"),
    (dict(canned.REPORT, complete=False), "not a full one run to its end"),      # READY ... resync=INCOMPLETE
    (dict(canned.REPORT, full=False), "not a full one run to its end"),
    (ONE_ABANDONED, "pulls abandoned: {'server-4': 1}"),                    # complete (f=1 allows it), yet not this cell's
    (dict(canned.REPORT, peers=3, by_peer={s: p for s, p in canned.REPORT["by_peer"].items() if s != "server-4"}),
     "3 of 4 peers asked"),
    (dict(canned.REPORT, shards_compared=0, shards_matched=0), "decided nothing"),
    (dict(canned.REPORT, began_epoch_us=NOW_US - 60_000_000), "outside this boot"),  # an earlier boot's pass
    (dict(canned.REPORT, began_epoch_us=NOW_US + 60_000_000), "outside this boot"),
])
def test_ready_without_a_complete_pass_of_this_boot_is_refused(tmp_path, report, why):
    back = verbs()[1]
    assert why in back.not_caught_up(report, 4, NOW_US - 7_000_000, NOW_US + 1_000)
    assert back.not_caught_up(canned.REPORT, 4, NOW_US - 7_000_000, NOW_US + 1_000) is None
    assert back.not_caught_up(canned.PARENT_REPORT, 4, NOW_US - 7_000_000, NOW_US + 1_000) is None

    async def restart_replica(server_id, resync=False):
        return None

    with pytest.raises(back.NotCaughtUp, match="server-3 printed READY, but"):
        asyncio.run(back.run(cluster_with(tmp_path, restart_replica, report), {"server_id": "server-3"}, {}))


def test_the_controls_plain_restart_is_refused_by_the_cells_own_look(tmp_path):
    """``faults_plain/``: the shipped plain restart, then ``hold``: as the harness meets it, the schedule's task raises."""
    seen = []

    async def restart_replica(server_id, resync=False):
        seen.append(resync)

    kill, plain = schedule.validate(json.load(open(MIX))["faults"], os.path.join(PERF, "tests", "faults_plain"))
    assert kill.KILLS and plain.RESTARTS and plain.END_TO_END == "recover_s"
    events = [{"at_s": 0.0, "do": "restart_replica_resync", "server_id": "server-3", "verb": plain}]
    look = lambda sid: {"replica": None, "service": {}}   # noqa: E731
    with pytest.raises(Exception, match="NotCaughtUp|no resync pass on record"):
        asyncio.run(schedule.run(cluster_with(tmp_path, restart_replica, None), events, 0.0, look))
    assert seen == [False]
    # and a replica that did make the pass goes through the same look
    asyncio.run(schedule.run(cluster_with(tmp_path, restart_replica), events, 0.0, look))


def test_a_boot_that_is_not_ready_within_the_verbs_limit_raises(tmp_path, monkeypatch):
    back = verbs()[1]
    assert back.READY_LIMIT_S == 120.0 < run.READY_TIMEOUT_S
    monkeypatch.setattr(back, "READY_LIMIT_S", 0.05)

    async def never_ready(server_id, resync=False):
        await asyncio.sleep(30)

    with pytest.raises(asyncio.TimeoutError):
        asyncio.run(back.run(cluster_with(tmp_path, never_ready), {"server_id": "server-3"}, {}))

    # as the harness meets it: the schedule's task raises, and ``run_cell`` says so and closes the cluster
    async def the_schedule():
        events = [{"at_s": 0.0, "do": "restart_replica_resync", "server_id": "server-3", "verb": back}]
        await schedule.run(cluster_with(tmp_path, never_ready), events, 0.0,
                           lambda sid: {"replica": None, "service": {}})

    with pytest.raises(asyncio.TimeoutError):
        asyncio.run(the_schedule())


@pytest.mark.parametrize("cell,reach", [("n64-ycsb-a", 8192), ("n64-ycsb-c", 688), ("rf4-ycsb-a", 0),
                                        ("rf4-recover", 0), ("rf4-50k-recover", 8192),
                                        ("rf4-30k-rehydrate", 8192), (CELL, 8192)])
def test_the_new_cell_gets_a_replay_reach_and_the_six_accepted_cells_keep_theirs(cell, reach):
    data = run.load_cell(base.REPO, cell)
    config, traffic = data["config"], data["traffic"]
    if cell == CELL:
        assert run.replay_items(config, data["verbs"]) == 72_000 > config["memo_items"] == 65_536
        # the rehearsal's shape stays under the memo: nothing is offered there
        assert run.replay_items(dict(config, **config["rehearsal"]), data["verbs"]) == 0
    writers = config["threads"] if float(traffic["updateproportion"]) > 0 else 0
    assert run.warm_reach(384, {512, 8192}, config["quorum"], config["load_threads"], writers,
                          run.replay_items(config, data["verbs"])) == reach


def test_the_recoverys_checks_pass_on_a_resynced_restart_and_fail_on_one_that_lost_keys():
    checks = {c.name: c for c in ref.check_recovery(canned.records())}
    assert all(c.ok for c in checks.values()) and checks["replicas_restarted"].value == 1
    lost = canned.records(entries=20_000)
    lost[1]["after"]["replica"]["store"]["keys_live"] = 20_000
    checks = {c.name: c for c in ref.check_recovery(lost)}
    assert not checks["replicas_back_with_fewer_keys_than_held_before_the_kill"].ok


def test_the_plain_reference_takes_the_newest_certified_entry_of_the_replica_or_a_peer():
    import layer_reader
    r = layer_reader.load(os.path.join(PERF, "reference_resync.py"), "perf_")
    owners = lambda key: {"a", "b", "c", "d"} if key != "theirs" else {"b", "c", "d", "e"}   # noqa: E731
    own = {"kept": (5, b"five", 3), "behind": (2, b"two", 3), "ahead": (9, b"nine", 3), "thin": (8, b"x", 2)}
    peers = {"b": {"kept": (5, b"five", 3), "behind": (4, b"four", 3), "ahead": (7, b"seven", 3),
                   "new": (1, b"one", 3), "theirs": (3, b"t", 3), "thin": (6, b"six", 3)},
             "c": {"behind": (3, b"three", 3), "forged": (9, b"f", 2)}}
    want = r.resynced(own, peers, owners, "a", 3)
    assert want == {"kept": (5, b"five"), "behind": (4, b"four"), "ahead": (9, b"nine"),
                    "new": (1, b"one"), "thin": (6, b"six")}
    assert r.behind({k: v[:2] for k, v in own.items()}, want) == {"behind", "new"}   # thin (8 > 6) is not behind
    assert r.differences(want, want) == {"missing": 0, "extra": 0, "older": 0, "other_bytes": 0}


def test_behind_when_sorts_each_record_the_replica_lacks_by_when_it_was_committed():
    import behind_when

    recs = canned.records()                     # killed at 105, command at 110, READY 7.2 s later; window from 100
    offset = canned.REPORT["began_epoch_us"] / 1e6 - 115.3      # the pass began at 115.3 on the window's clock
    hist, direct = {}, {"server-2": {}}
    for rec, acked in enumerate([103.0, 108.0, 112.0, 116.0, 120.0, None], start=1):
        h = hist[rec] = ref.KeyHistory()
        h.add_write((9, rec), -float("inf"), -float("inf"), 1)          # the load's
        h.add_write((0, 1), 101.0, 101.5, 2)                            # one it has
        if acked is not None:
            h.add_write((1, 1), acked - 0.5, acked, 3)                  # one it lacks
            h.add_write((2, 1), acked + 0.1, float("inf"), 4)           # and one nobody was answered for
        h.seal()
        direct["server-2"][rec] = (0, 1, 2, 3)
    direct["server-2"][7] = None
    got = behind_when.take_apart(direct, hist, recs, 100.0, lambda us: us / 1e6 - offset)
    assert {k: got[k] for k in ("behind", *behind_when.PHASES)} == {
        "behind": 5, "before_kill": 1, "down": 1, "booting": 1, "during_pass": 1, "after_ready": 1}
    assert [r["record"] for r in got["records"]] == [1, 2, 3, 4, 5]
    assert got["records"][3] == {"server_id": "server-2", "record": 4, "phase": "during_pass",
                                 "lacks_issued_s": 15.5, "lacks_acked_s": 16.0, "holds_acked_s": 1.5}
    assert got["bounds_s"] == pytest.approx({"before_kill": 5.0, "down": 10.0, "booting": 15.3, "during_pass": 17.2})
    # a plain restart has no pass on record: what it lacks until READY committed while it was down or booting
    plain = behind_when.take_apart(direct, hist, canned.records(report=None), 100.0, None)
    assert (plain["booting"], plain["during_pass"], plain["after_ready"]) == (2, 0, 1)
