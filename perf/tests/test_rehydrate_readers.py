"""PR 33's cell ``rf4-30k-rehydrate``: its five readers on a canned kill and
re-hydrating restart (``canned_rehydrate.py``), what its traffic file and its
configuration are held to before a boot, and what the harness offers it in
warm-up.  ``test_rehearsal_rehydrate.py`` walks the cell end to end on the CPU,
by hand."""

import json
import os

import pytest

import canned_rehydrate as canned
import reference as ref
import run
import schedule
import test_span_readers as base

CELL = "rf4-30k-rehydrate"
PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIX = os.path.join(PERF, "traffic", "ycsb-a-kill1-rehydrate.json")
FAULTS = os.path.join(PERF, "faults")
READERS = ["rehydrate.ready_s", "rehydrate.pulled_per_adopted", "rehydrate.verify_wait_ms",
           "rehydrate.device_item_share", "rehydrate.device_busy_share"]


def snapshot(platform="tpu", trace=None, **kwargs):
    return dict(base.SNAP, platform=platform, faults=canned.records(**kwargs), cluster={"quorum": 3},
                trace={"window": trace} if trace else {})


def test_the_five_readers_on_the_canned_rehydration():
    got = base.read(CELL, snapshot(trace={"window_s": 5.0, "busy_s": 0.4, "started_s": 4.01}))
    assert {k: got[k] for k in READERS} == {
        "rehydrate.ready_s": 18.1,
        "rehydrate.pulled_per_adopted": pytest.approx(71_940 / 23_980),
        "rehydrate.verify_wait_ms": 6_250.5,
        # the service's gain between the two looks: 768 of 2,000 signatures on the device
        "rehydrate.device_item_share": pytest.approx(100 * 768 / (768 + 1232)),
        "rehydrate.device_busy_share": pytest.approx(8.0),
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
    # the parent commit's replica, or a plain restart: a ``storage`` without ``resync``, or a None
    assert name not in base.read(CELL, snapshot(report=None))
    plain = snapshot()
    plain["faults"][1]["after"]["replica"]["storage"]["resync"] = None
    assert name not in base.read(CELL, plain)


@pytest.mark.parametrize("trace,platform,expect", [
    ({"window_s": 5.0, "busy_s": 1.25, "started_s": 4.01}, "tpu", 25.0),
    ({"window_s": 5.0, "busy_s": 0.0, "started_s": 4.01}, "tpu", 0.0),
    ({"window_s": 5.0, "busy_s": 1.25, "started_s": 25.0}, "tpu", None),    # another stretch of the window
    ({"window_s": 5.0, "busy_s": 1.25}, "tpu", None),
    ({"window_s": 5.0, "busy_s": 1.25, "started_s": 4.01}, "cpu", None),
])
def test_device_busy_share_reads_the_trace_that_covers_the_rehydration(trace, platform, expect):
    got = base.read(CELL, snapshot(platform, trace)).get("rehydrate.device_busy_share")
    assert got == (pytest.approx(expect) if expect is not None else None)


def test_pulled_per_adopted_and_the_device_share_need_something_to_divide_by():
    nothing = dict(canned.REPORT, entries_adopted=0)
    assert "rehydrate.pulled_per_adopted" not in base.read(CELL, snapshot(report=nothing))
    quiet = snapshot()
    quiet["faults"][1]["after"]["service"] = quiet["faults"][1]["before"]["service"]
    assert "rehydrate.device_item_share" not in base.read(CELL, quiet)


def test_the_cell_reports_ops_and_setup_end_to_end_and_is_on_no_list_that_was_there():
    data = run.load_cell(base.REPO, CELL)
    bench, cell = data["bench"], data["cell"]
    assert cell == {"name": CELL, "config": "rf4-n5-30k-rehydrate", "traffic": "ycsb-a-kill1-rehydrate",
                    "chips": 1, "why": cell["why"]} and len(cell["why"]) <= 200
    assert [m["name"] for m in bench["end_to_end"] if run.metric_applies(m, CELL)] == ["ops_s", "setup_s"]
    keyed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # in the order PR 33 appended them (later PRs append their own after them)
    assert [m["name"] for m in keyed] == READERS
    first = [m["name"] for m in bench["per_layer"]].index(READERS[0])
    assert [m["name"] for m in bench["per_layer"][first:first + len(READERS)]] == READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "ops_s" for m in keyed)
    # the readers that apply unkeyed: the eleven that move ops_s, as in rf4-50k-recover
    unkeyed = [m["name"] for m in bench["per_layer"] if "workloads" not in m and m["moves"] == "ops_s"]
    assert len(unkeyed) == 11
    got = base.read(CELL, dict(base.SNAP, platform="tpu", host_spans={"window": base.WINDOW, "probe": base.PROBE}))
    assert set(unkeyed) <= set(got) and not [k for k in got if k.startswith(("recovery.", "tail.", "client."))]


def test_the_configuration_is_rf4_n5_50k_but_for_what_the_issue_lists():
    new, old = base.run.load_cell(base.REPO, CELL)["config"], json.load(open(os.path.join(PERF, "configs", "rf4-n5-50k.json")))
    differ = {k for k in set(new) | set(old) if new.get(k) != old.get(k)}
    assert differ == {"name", "source", "recordcount", "recovery", "guarantees", "reduced", "assumed", "memo_items_origin"}
    assert new["recordcount"] == 30_000 and list(new["reduced"]) == ["recordcount"]
    assert new["guarantees"][:3] == old["guarantees"][:3] and "--resync-on-boot" in new["guarantees"][3]
    assert new["rehearsal"] == old["rehearsal"] and "--resync-on-boot" in new["recovery"]


def verbs():
    return schedule.validate(json.load(open(MIX))["faults"], FAULTS)


def test_the_mix_is_the_issues_and_its_schedule_binds_on_this_configuration_and_not_at_n64():
    mix = json.load(open(MIX))
    assert mix["faults"] == [{"at_s": 2.0, "do": "kill_replica", "replica": "seeded"},
                             {"at_s": 4.0, "do": "restart_replica_rehydrate", "replica": "same"}]
    assert (mix["readproportion"], mix["updateproportion"], mix["requestdistribution"],
            mix["zipfian_constant"], mix["loop"]) == (0.5, 0.5, "zipfian", 0.99, "closed")
    kill, back = verbs()
    assert kill.KILLS and back.RESTARTS and back.END_TO_END == "recover_s"
    events = schedule.bind(mix["faults"], [kill, back], 2**31 + 77, 30.0, 5, 1, {f"server-{i}": i for i in range(5)})
    assert [e["do"] for e in events] == ["kill_replica", "restart_replica_rehydrate"]
    assert events[0]["server_id"] == events[1]["server_id"]
    # the window's trace starts at the re-hydration's command
    assert run.trace_from_s(events, 30.0, run.TRACE_SECONDS) == 4.0
    # n64 packs 64 replicas on 11 processes: a kill takes a whole process
    with pytest.raises(schedule.ScheduleError, match="shares its process"):
        schedule.bind(mix["faults"], [kill, back], 7, 30.0, 64, 21, {f"server-{i}": i % 11 for i in range(64)})


def test_the_verb_refuses_a_product_that_cannot_pass_the_flag(monkeypatch):
    from mochi_tpu.testing.process_cluster import ProcessCluster

    async def restart_replica(self, server_id):   # the parent commit's
        return None

    monkeypatch.setattr(ProcessCluster, "restart_replica", restart_replica)
    with pytest.raises(schedule.ScheduleError, match="takes no 'resync'"):
        schedule.load_verb(FAULTS, "restart_replica_rehydrate")
    # as the harness meets it: no result before anything boots
    with pytest.raises(run.RunFailure, match="takes no 'resync'"):
        run.load_cell(base.REPO, CELL)


@pytest.mark.parametrize("cell,reach", [("n64-ycsb-a", 8192), ("n64-ycsb-c", 688), ("rf4-ycsb-a", 0),
                                        ("rf4-recover", 0), ("rf4-50k-recover", 8192), (CELL, 8192)])
def test_the_new_cell_gets_a_replay_reach_and_the_five_accepted_cells_keep_theirs(cell, reach):
    data = run.load_cell(base.REPO, cell)
    config, traffic = data["config"], data["traffic"]
    if cell == CELL:
        assert run.replay_items(config, data["verbs"]) == 72_000 > config["memo_items"]
        # the rehearsal's shape stays under the memo: nothing is offered there
        assert run.replay_items(dict(config, **config["rehearsal"]), data["verbs"]) == 0
    writers = config["threads"] if float(traffic["updateproportion"]) > 0 else 0
    assert run.warm_reach(384, {512, 8192}, config["quorum"], config["load_threads"], writers,
                          run.replay_items(config, data["verbs"])) == reach


def test_the_recoverys_checks_pass_on_a_rehydration_and_fail_on_an_empty_restart():
    checks = {c.name: c for c in ref.check_recovery(canned.records())}
    assert all(c.ok for c in checks.values()) and checks["replicas_restarted"].value == 1
    empty = canned.records(report=None)
    empty[1]["after"]["replica"]["store"]["keys_live"] = 37    # what was written again since READY
    checks = {c.name: c for c in ref.check_recovery(empty)}
    assert not checks["replicas_back_with_fewer_keys_than_held_before_the_kill"].ok
