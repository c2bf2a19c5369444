"""The fault schedule: what a traffic file and a cell are refused for, how a
schedule binds to a seed, and what the reference and the readers make of the
records it leaves."""

import asyncio
import json
import math
import os

import pytest

import reference as ref
import run
import schedule
import ycsb
from test_data_driven import SNAP

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PERF)
FAULTS = os.path.join(PERF, "faults")
KILL1 = [{"at_s": 5.0, "do": "kill_replica", "replica": "seeded"},
         {"at_s": 10.0, "do": "restart_replica", "replica": "same"}]
ONE_EACH = {f"server-{i}": i for i in range(5)}


def mix_file(tmp_path, faults):
    path = tmp_path / "traffic" / "mix.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps({"readproportion": 0.5, "updateproportion": 0.5,
                                "requestdistribution": "zipfian", "faults": faults}))
    return str(path)


@pytest.mark.parametrize("faults,why", [
    ([{"at_s": 5.0, "do": "melt_replica", "replica": "seeded"}], "unknown fault verb"),
    ([{"at_s": 5.0, "do": "../run", "replica": "seeded"}], "unknown fault verb"),
    ([{"at_s": 5.0, "do": "restart_replica", "replica": "seeded"}], "nothing killed"),
    ([{"at_s": 5.0, "do": "kill_replica", "replica": "seeded"},
      {"at_s": 6.0, "do": "restart_replica", "replica": "seeded"}], "nothing killed"),
    ([{"at_s": 5.0, "do": "kill_replica", "replica": "seeded"}], "never restarted"),
    ([{"at_s": 5.0, "do": "kill_replica", "replica": "seeded"},
      {"at_s": 4.0, "do": "restart_replica", "replica": "same"}], "at or after"),
    ([{"at_s": -1, "do": "kill_replica", "replica": "seeded"},
      {"at_s": 4.0, "do": "restart_replica", "replica": "same"}], "at or after"),
    ([{"at_s": 5.0, "do": "kill_replica", "replica": "any"},
      {"at_s": 6.0, "do": "restart_replica", "replica": "same"}], "'seeded' or 'same'"),
    ([{"at_s": 5.0, "do": "kill_replica", "replica": 0},
      {"at_s": 6.0, "do": "restart_replica", "replica": "same"}], "'seeded' or 'same'"),
    ([{"at_s": 5.0, "do": "kill_replica", "replica": "same"}], "no event before"),
    ([{"at_s": 5.0, "do": "kill_replica"}], "'at_s', 'do' and 'replica'"),
    ([], "non-empty list"),
])
def test_a_traffic_file_is_refused_for(tmp_path, faults, why):
    # as ``run.load_cell`` holds it: the mix loads, its schedule does not
    mix = ycsb.load_traffic(mix_file(tmp_path, faults))
    with pytest.raises(schedule.ScheduleError, match=why):
        schedule.validate(mix["faults"], FAULTS)


def test_the_shipped_mixes_load_and_every_verb_has_its_file(tmp_path):
    assert len(schedule.validate(ycsb.load_traffic(mix_file(tmp_path, KILL1))["faults"], FAULTS)) == 2
    shipped = {n[:-5]: ycsb.load_traffic(os.path.join(PERF, "traffic", n))
               for n in os.listdir(os.path.join(PERF, "traffic"))}
    # the kill-and-restart of PR 26, the emptied restart of PR 33, the runbook's of PR 37
    assert {k for k, v in shipped.items() if "faults" in v} == {
        "ycsb-a-kill1", "ycsb-a-kill1-rehydrate", "ycsb-a-kill1-resync"}
    # a verb is a file: no verb without one, no file without a cell that runs it
    assert {n[:-3] for n in os.listdir(FAULTS) if n.endswith(".py")} == \
        {ev["do"] for v in shipped.values() for ev in v.get("faults", ())}


def bind(faults, seed=7, seconds=30.0, n=5, f=1, process_of=None):
    verbs = schedule.validate(faults, FAULTS)
    return schedule.bind(faults, verbs, seed, seconds, n, f, process_of or ONE_EACH)


@pytest.mark.parametrize("kwargs,why", [
    ({"seconds": 10.0}, "outside the window"),
    ({"process_of": {f"server-{i}": i % 2 for i in range(5)}}, "shares its process"),
    ({"f": 0}, "tolerates f=0"),
    ({"faults": KILL1 + [{"at_s": 11.0, "do": "kill_replica", "replica": "same"},
                         {"at_s": 12.0, "do": "restart_replica", "replica": "same"}],
      "process_of": {"server-0": 0, "server-1": 0, "server-2": 1, "server-3": 1, "server-4": 2, },
      "seed": 2}, "shares its process"),
])
def test_a_cell_refuses_a_schedule_it_cannot_carry(kwargs, why):
    with pytest.raises(schedule.ScheduleError, match=why):
        bind(kwargs.pop("faults", KILL1), **kwargs)


def test_two_down_at_once_is_more_than_f():
    # a traffic file cannot state it and restart both (a restart is of the
    # replica before it), so the schedule is bound as a cell would bind it
    faults = [{"at_s": 1, "do": "kill_replica", "replica": "seeded"},
              {"at_s": 2, "do": "kill_replica", "replica": "seeded"},
              {"at_s": 3, "do": "restart_replica", "replica": "same"}]
    verbs = [schedule.load_verb(FAULTS, ev["do"]) for ev in faults]
    with pytest.raises(schedule.ScheduleError, match="never restarted"):
        schedule.validate(faults, FAULTS)
    with pytest.raises(schedule.ScheduleError, match="2 replicas down at once"):
        schedule.bind(faults, verbs, 7, 30.0, 5, 1, ONE_EACH)
    events = schedule.bind(faults, verbs, 7, 30.0, 7, 2, {f"server-{i}": i for i in range(7)})
    assert len({ev["server_id"] for ev in events}) == 2 and events[1]["server_id"] == events[2]["server_id"]


def test_the_seed_draws_the_replica_and_same_follows_it():
    drawn = set()
    for seed in range(2**31, 2**31 + 40):
        kill, restart = bind(KILL1, seed=seed)
        assert kill["server_id"] == restart["server_id"] and kill["do"] == "kill_replica"
        assert bind(KILL1, seed=seed)[0]["server_id"] == kill["server_id"]   # the same seed, the same replica
        drawn.add(kill["server_id"])
    assert drawn == set(ONE_EACH)


def test_the_schedule_runs_each_verb_at_its_time_and_sums_what_it_brings():
    class Verb:
        END_TO_END = "recover_s"

        @staticmethod
        async def run(pc, event, state):
            await asyncio.sleep(0.05)
            state["ran"] = state.get("ran", 0) + 1
            return {"n": state["ran"]}

    class Quiet:
        @staticmethod
        async def run(pc, event, state):
            return {}

    events = [{"do": "a", "server_id": "server-1", "at_s": 0.1, "verb": Quiet},
              {"do": "b", "server_id": "server-1", "at_s": 0.2, "verb": Verb},
              {"do": "b", "server_id": "server-1", "at_s": 0.3, "verb": Verb}]
    looks = []

    async def go():
        import time
        return await schedule.run(None, events, time.monotonic(), lambda sid: looks.append(sid) or {"replica": None})

    records = asyncio.run(go())
    assert [r["timed"] for r in records] == [{}, {"n": 1}, {"n": 2}] and len(looks) == 6
    assert all(r["started_s"] >= r["at_s"] for r in records)
    e2e = schedule.end_to_end(events, records)
    assert set(e2e) == {"recover_s"} and e2e["recover_s"] == pytest.approx(records[1]["seconds"] + records[2]["seconds"])
    assert 0.1 <= e2e["recover_s"] < 0.5


# ------------------------------------------------------- records, checks, readers

from canned_faults import records  # noqa: E402


def values(checks):
    return {c.name: (c.value, c.ok) for c in checks}


def test_recovery_is_held_to_nothing_convicted_and_no_fewer_keys_than_held():
    assert all(c.ok for c in ref.check_recovery(records()))
    short = records(entries=7999)
    short[1]["after"]["replica"]["store"]["keys_live"] = 7990
    got = values(ref.check_recovery(short))
    assert got["replicas_back_with_fewer_keys_than_held_before_the_kill"] == (1, False)
    assert values(ref.check_recovery(records(convicted=2)))["replay_entries_convicted"] == (2, False)
    # a schedule whose replica never came back
    assert values(ref.check_recovery(records()[:1]))["replicas_restarted"] == (0, False)


def history():
    ops = [  # [kind, record, t_issue, t_done, ok, writer, seq, crc, grants]
        [1, 1, 100.0, 100.1, 1, 7, 0, 0, 0],    # long before the kill at 105: the log has it
        [1, 2, 104.5, 104.6, 1, 7, 1, 0, 0],    # inside the slack before the kill
        [1, 3, 107.0, 107.1, 1, 7, 2, 0, 0],    # while the replica was down
        [1, 4, 100.0, 100.1, 1, 7, 3, 0, 0], [1, 4, 120.0, 120.1, 1, 7, 4, 0, 0],   # written again after READY
    ]
    return ref.build_histories(ops, ycsb.LOAD_WRITER, lambda w, s: 1000 * (w % 1000) + s)


def load_write(rec):
    return (ycsb.LOAD_WRITER, rec, 1000 * (ycsb.LOAD_WRITER % 1000) + rec, 3)


def test_a_restarted_replica_alone_is_held_to_what_it_had_acknowledged():
    hist = history()
    sound = {"server-2": {1: (7, 0, 7000, 3), 2: load_write(2), 3: load_write(3), 4: (7, 4, 7004, 3)}}
    checks, counts = ref.check_direct(sound, hist, records(), 2.0, 3)
    assert all(c.ok for c in checks) and counts == {"asked": 4, "behind": 2}
    # it lost a write acknowledged 5 s before it was killed; it serves bytes nobody
    # wrote; it serves nothing (an emptied directory); a certificate short of a quorum
    for broken, failed in [
        ({1: load_write(1)}, "direct_reads_older_than_acknowledged_before_the_kill"),
        ({1: (7, 0, 1234, 3)}, "direct_reads_of_no_known_write"),
        ({1: (9, 9, 7000, 3)}, "direct_reads_of_no_known_write"),
        ({1: None}, "direct_reads_unanswered_or_empty"),
        ({1: (7, 0, 7000, 2)}, "direct_reads_under_quorum_grants"),
    ]:
        checks, _ = ref.check_direct({"server-2": {**sound["server-2"], **broken}}, hist, records(), 2.0, 3)
        assert [c.name for c in checks if not c.ok] == [failed]
    checks, _ = ref.check_direct({}, hist, records(), 2.0, 3)
    assert [c.name for c in checks if not c.ok] == ["direct_reads_sent"]


def test_the_deployment_a_configuration_states_is_compared_with_what_the_replicas_report():
    config = json.load(open(os.path.join(PERF, "configs", "rf4-n5.json")))
    sound = {"storage_engines": ["durable"], "fsync_policies": ["group"], "admission": ["True"]}
    assert all(c.ok for c in ref.check_deployment(config, sound))
    assert all(c.ok for c in ref.check_deployment(dict(config, storage_engine="paged"),
                                                  dict(sound, storage_engines=["paged"])))
    for key, other in [("storage_engines", ["durable", "paged"]), ("fsync_policies", ["off"]),
                       ("admission", ["False"]), ("storage_engines", ["memory"])]:
        failed = [c.name for c in ref.check_deployment(config, dict(sound, **{key: other})) if not c.ok]
        assert failed == [f"replicas_reporting_other_{key}"]


def test_the_recovery_readers_on_canned_records():
    data = run.load_cell(REPO, "rf4-recover")
    snap = dict(SNAP, faults=records(), cluster={"quorum": 3}, window_s=30.0, ops_ok=30000, updates_ok=15000,
                latency={"update_p95_ms": 80.0, "read_p95_ms": 20.0})
    got = {k: v["value"] for k, v in
           run.read_layer_metrics(data["layer_dir"], data["bench"], "rf4-recover", snap).items()}
    assert {k: v for k, v in got.items() if k.startswith("recovery.")} == {
        "recovery.replay_ms": 3200.0, "recovery.replay_entries": 9000.0,
        "recovery.boot_s": pytest.approx(0.8), "recovery.items_per_rpc": pytest.approx(375.0),
        "recovery.device_item_share": pytest.approx(38.4), "recovery.memo_hit_share": pytest.approx(90.0)}
    # since PR 43 the cell's update tail is a per-layer reading, as rf4-50k-recover's is (its two
    # levels are 17% apart against a bound of 10%), and what moved it is read under the ``.ops`` names
    assert got["tail.read_p95_ms"] == 20.0 and got["tail.update_p95_ms"] == 80.0
    assert {"client.write1_p50_ms.ops", "verifier.items_per_flush.ops", "verifier.device_item_share.ops",
            "store.fsyncs_per_update.ops"} <= set(got) and "client.write1_p50_ms" not in got
    # a cell without a schedule: the readers find nothing to read and say nothing
    snap.pop("faults")
    quiet = run.read_layer_metrics(data["layer_dir"], data["bench"], "rf4-recover", snap)
    assert not any(k.startswith("recovery.") for k in quiet)
    assert math.isfinite(quiet["replica.cpu_ms_per_op"]["value"])
