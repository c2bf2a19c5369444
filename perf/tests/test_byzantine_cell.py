"""PR 45: a configuration's ``byzantine`` map is bound (``schedule.bind``,
``run.stated_members``), held (``reference.check_deployment``,
``reference.check_byzantine``) and read (``cluster.byzantine_report``, the four
``byz.*`` readers, ``client.certificates_built_share``); the configuration
``rf4-n5-byz1`` and the cell ``rf4-byz1-ycsb-a`` are what the issue states; and,
at a small size on the CPU, the system with a live ``forge-cert`` member holds
every rule of the reference while the member is caught."""

import asyncio
import json
import os
import random
import time
import zlib

import pytest

import cluster as cl
import layer_reader
import reference as ref
import run
import schedule
import ycsb
from test_data_driven import REPO, SNAP

__all__ = [
    "test_the_deployment_check_counts_replicas_whose_strategy_is_not_the_stated_one",
    "test_check_byzantine_fires_on",
    "test_check_byzantine_is_quiet_on_a_member_that_lied_and_was_caught",
    "test_a_strategy_outside_the_table_is_caught_by_a_mark_of_any_kind",
    "test_bind_counts_stated_members_against_f",
    "test_the_shipped_configuration_under_the_kill_mix_is_refused_at_bind",
    "test_a_byzantine_map_is_refused_before_the_boot_for",
    "test_a_replicas_own_report",
    "test_the_generators_counters_are_added_up_over_the_workers",
    "test_the_readers_on_a_canned_snapshot",
    "test_a_reader_that_finds_nothing_gives_nothing",
    "test_the_readers_are_keyed_to_their_cells_and_the_five_lists_gained_the_new_one",
    "test_the_entries_are_the_files",
    "test_the_configuration_is_rf4_n5_but_for_what_the_issue_lists",
    "test_the_cell_is_what_the_issue_states",
    "test_the_control_table_has_every_control",
    "test_a_live_forging_member_is_caught_and_every_rule_of_the_reference_holds",
]

PERF = os.path.join(REPO, "perf")
CELL, CONFIG, MEMBER = "rf4-byz1-ycsb-a", "rf4-n5-byz1", "server-1"
STATED = {MEMBER: "forge-cert"}
READERS = ["byz.lies_per_op", "byz.bad_grants_per_update", "byz.read_fallback_share",
           "byz.callers_avoiding_member_share"]
BUILT = "client.certificates_built_share"
SERVERS = [f"server-{i}" for i in range(5)]


def reports(strategy=None, mutated=0, dropped=0, evidence=None, member=MEMBER):
    """``replica_counters(...)["byzantine"]`` of five replicas, ``member``
    reporting ``strategy`` and its counts, each holding ``evidence`` against
    its peers."""
    honest = {"equivocations": {}, "bad_grants": {}, "resync_bad_certificates": 0,
              "strategy": None, "mutated_responses": 0, "dropped_requests": 0}
    out = {sid: dict(honest, **(evidence or {})) for sid in SERVERS}
    out[member].update(strategy=strategy, mutated_responses=mutated, dropped_requests=dropped)
    return {"byzantine": out}


def value(checks, name):
    return next(c for c in checks if c.name == name)


DEPLOYED = {"storage_engines": ["durable"], "fsync_policies": ["group"], "admission": ["True"]}
STRATEGY_CHECK = "replicas_whose_strategy_differs_from_what_the_configuration_states"


@pytest.mark.parametrize("stated,reported,differ", [
    (None, None, 0),                                     # a replicas dict without the key (a canned one)
    (None, reports(), 0),                                # the seven accepted cells: none stated, none reported
    (STATED, reports(True, 9), 0),                       # stated, runs one, does not name it (today's product)
    (STATED, reports("forge-cert", 9), 0),               # names it, and it is the stated one
    (STATED, reports("silent", 0, 9), 1),                # names another
    (STATED, reports(), 1),                              # the stated member booted honest
    (STATED, None, 1),                                   # a /status without the section: no strategy
    (None, reports(True, 9), 1),                         # an unstated member lies
    (STATED, reports(True, 9, member="server-3"), 2),    # the wrong member: one honest, one unstated
    ({MEMBER: "forge-cert", "server-3": "silent"}, reports(True, 9), 1),
])
def test_the_deployment_check_counts_replicas_whose_strategy_is_not_the_stated_one(stated, reported, differ):
    config = {"storage_engine": "wal", "wal_fsync": "group", "admission": "on"}
    if stated:
        config["byzantine"] = stated
    checks = ref.check_deployment(config, dict(DEPLOYED, **(reported or {})))
    assert [c.name for c in checks][-1] == STRATEGY_CHECK and len(checks) == 4
    assert value(checks, STRATEGY_CHECK).value == differ and value(checks, STRATEGY_CHECK).limit == 0
    assert all(c.ok for c in checks[:3]) and checks[-1].ok == (differ == 0)


CAUGHT = {"suspect.bad-grant.server-1": 16_000, "suspect.tally-outvoted.server-1": 15_000,
          # what five honest members earn under contention (chiprun_out/pr44b): no lie
          "suspect.grant-conflict.server-0": 11, "suspect.tally-outvoted.server-3": 14,
          "suspect.no-response.server-4": 1, "fanout.straggler-timeout.server-2": 3,
          "calls.read-transactions": 20_000}


def test_check_byzantine_is_quiet_on_a_member_that_lied_and_was_caught():
    checks = ref.check_byzantine(STATED, reports(True, 100), reports(True, 32_100), CAUGHT)
    assert [(c.name, c.value, c.limit, c.at_least) for c in checks] == [
        ("stated_members_that_never_acted_in_the_window", 0, 0, False),
        ("lies_the_callers_caught", 16_000, 1, True),
        ("honest_replicas_accused_by_typed_evidence", 0, 0, False)]
    assert all(c.ok for c in checks)
    # a member that was started again inside the window counts anew
    again = ref.check_byzantine(STATED, reports(True, 5_000), reports(True, 40), CAUGHT)
    assert all(c.ok for c in again)


@pytest.mark.parametrize("why,before,after,gained,failed", [
    ("the member ran honest", reports(), reports(), {}, {"stated_members_that_never_acted_in_the_window": 1,
                                                         "lies_the_callers_caught": 0}),
    ("the member lied before the window and not in it", reports(True, 100), reports(True, 100), CAUGHT,
     {"stated_members_that_never_acted_in_the_window": 1}),
    ("the member swallowed requests and nobody marked it", reports(True, 0, 0), reports(True, 0, 900),
     {"suspect.tally-outvoted.server-1": 40}, {"lies_the_callers_caught": 0}),
    ("a caller threw out an honest replica's grant", reports(True, 0), reports(True, 900),
     dict(CAUGHT, **{"suspect.bad-grant.server-3": 1}), {"honest_replicas_accused_by_typed_evidence": 1}),
    ("an honest replica's certificate did not build", reports(True, 0), reports(True, 900),
     dict(CAUGHT, **{"suspect.bad-certificate.server-0": 2, "suspect.bad-certificate.server-4": 1}),
     {"honest_replicas_accused_by_typed_evidence": 2}),
    # (a replica's ``bad_grants`` are counted against the signer a bad grant CLAIMS, whoever carried
    # the certificate: no proof, so a member's tampered sync answer accuses nobody)
    ("a replica counts bad grants against honest signers", reports(True, 0),
     reports(True, 900, evidence={"bad_grants": {"server-0": 1, "server-3": 1, "server-4": 1}, "resync_bad_certificates": 1}),
     CAUGHT, {}),
    ("a replica proved an honest peer to equivocate", reports(True, 0, evidence={"equivocations": {"server-0": 1}}),
     reports(True, 900, evidence={"equivocations": {"server-0": 2}}), CAUGHT,
     {"honest_replicas_accused_by_typed_evidence": 1}),
])
def test_check_byzantine_fires_on(why, before, after, gained, failed):
    checks = ref.check_byzantine(STATED, before, after, gained)
    assert {c.name: c.value for c in checks if not c.ok} == failed, why


def test_a_strategy_outside_the_table_is_caught_by_a_mark_of_any_kind():
    stated = {"server-2": "silent"}
    run_ = (reports(True, member="server-2"), reports(True, 0, 500, member="server-2"))
    assert all(c.ok for c in ref.check_byzantine(stated, *run_, {"suspect.no-response.server-2": 7}))
    # what the replicas prove against the MEMBER accuses no honest replica, and a proof that
    # did not grow in the window is not the window's
    held = reports(True, 0, 500, evidence={"equivocations": {"server-2": 9, "server-0": 1}}, member="server-2")
    old = reports(True, evidence={"equivocations": {"server-0": 1}}, member="server-2")
    assert all(c.ok for c in ref.check_byzantine(stated, old, held, {"suspect.no-response.server-2": 7}))
    # two members: the one the callers caught least decides
    both = {MEMBER: "forge-cert", "server-2": "silent"}
    two = reports(True, 0, 500, member="server-2")
    two["byzantine"][MEMBER].update(strategy=True, mutated_responses=10)
    checks = ref.check_byzantine(both, reports(), two, {"suspect.bad-grant.server-1": 10})
    assert value(checks, "lies_the_callers_caught").value == 0
    assert value(checks, "stated_members_that_never_acted_in_the_window").value == 0


# ---------------------------------------------------------------------- bind

KILL1 = json.load(open(os.path.join(PERF, "traffic", "ycsb-a-kill1.json")))["faults"]
FAULTS = os.path.join(PERF, "faults")
ONE_EACH = {sid: i for i, sid in enumerate(SERVERS)}


def bind(seed, members=(), f=1, faults=KILL1):
    return schedule.bind(faults, schedule.validate(faults, FAULTS), seed, 30.0, 5, f, ONE_EACH, members)


def test_bind_counts_stated_members_against_f():
    draws = {seed: bind(seed)[0]["server_id"] for seed in range(40)}
    assert set(draws.values()) == set(SERVERS)
    for seed, killed in draws.items():
        if killed == MEMBER:  # the member itself goes down: one faulty replica, as before
            assert [e["server_id"] for e in bind(seed, STATED)] == [MEMBER, MEMBER]
            continue
        with pytest.raises(schedule.ScheduleError) as exc:
            bind(seed, STATED)
        assert f"{killed} down beside the stated Byzantine ['server-1']" in str(exc.value)
        assert "2 faulty replicas at once, the configuration tolerates f=1" in str(exc.value)
        # a configuration that tolerates two carries both; one that states no member binds as it did
        assert bind(seed, STATED, f=2)[0]["server_id"] == killed
        assert bind(seed, ())[0]["server_id"] == bind(seed)[0]["server_id"] == killed


def test_the_shipped_configuration_under_the_kill_mix_is_refused_at_bind(tmp_path):
    # members alone (the cell as shipped: no schedule) bind to nothing and pass
    data = run.load_cell(REPO, CELL)
    assert data["verbs"] == [] and run.stated_members(data["config"]) == STATED
    # the same configuration under ``ycsb-a-kill1``, as ``run_cell`` binds it, before anything boots
    bench = dict(data["bench"], workloads=data["bench"]["workloads"] + [
        {"name": "byz1-kill1", "config": CONFIG, "traffic": "ycsb-a-kill1", "chips": 1, "why": "refused"}])
    os.makedirs(tmp_path / "perf")
    for d in ("configs", "traffic", "faults", "layer_metrics"):
        os.symlink(os.path.join(PERF, d), tmp_path / "perf" / d, target_is_directory=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    args = run.argparse.Namespace(rehearse=False, seed=7, seconds=30.0, trace=0, keep=False)
    assert bind(7)[0]["server_id"] != MEMBER
    with pytest.raises(run.RunFailure, match="traffic 'ycsb-a-kill1': fault 0: server-. down beside the "
                                             "stated Byzantine"):
        asyncio.run(run.run_cell(args, run.load_cell(str(tmp_path), "byz1-kill1"), "launcher", "worker"))


@pytest.mark.parametrize("members,why", [
    ({"server-5": "forge-cert"}, "names 'server-5': the configuration has server-0 to server-4"),
    ({"replica-1": "forge-cert"}, "names 'replica-1'"),
    ({MEMBER: "forge-certs"}, "gives server-1 'forge-certs': unknown byzantine strategy"),
    ({MEMBER: None}, "gives server-1 None"),
    (["server-1"], "is a map of server id to strategy"),
])
def test_a_byzantine_map_is_refused_before_the_boot_for(members, why):
    config = dict(run.load_cell(REPO, CELL)["config"], byzantine=members)
    with pytest.raises(run.RunFailure, match=why):
        run.stated_members(config)
    # and a configuration that states none, or an empty map, has none
    assert run.stated_members(run.load_cell(REPO, "rf4-ycsb-a")["config"]) == {}
    assert run.stated_members(dict(config, byzantine={})) == {}


# ---------------------------------------------------------------------- read

def test_a_replicas_own_report():
    evidence = {"equivocations": {}, "bad_grants": {"server-1": 2}, "resync_bad_certificates": 0}
    honest = {"server_id": "server-0", "byzantine": evidence, "strategy_counters": {}}
    assert cl.byzantine_report(honest) == dict(evidence, strategy=None, mutated_responses=0, dropped_requests=0)
    # forge-cert swallows nothing: the counter is there at 0 from its first batch on
    member = {"server_id": MEMBER, "byzantine": evidence, "strategy_counters": {
        "byzantine.dropped-requests": 0, "byzantine.mutated-responses": 41}}
    assert cl.byzantine_report(member) == dict(evidence, strategy=True, mutated_responses=41, dropped_requests=0)
    # a product whose /status names the strategy is taken at its word
    named = dict(member, byzantine=dict(evidence, strategy="forge-cert", mutated_responses=7, dropped_requests=1))
    assert cl.byzantine_report(named) == named["byzantine"]
    # a /status without the section (and a look that took no /metrics): no strategy
    assert cl.byzantine_report({"server_id": "server-2"})["strategy"] is None


WORKERS = [
    {"sdk_counters": {"sum": {"suspect.bad-grant.server-1": 5, "calls.read-transactions": 12},
                      "callers": {"suspect.bad-grant.server-1": 2, "calls.read-transactions": 2},
                      "marks": {"server-1": [4, 1]}}},
    {"sdk_counters": {"sum": {"suspect.bad-grant.server-1": 3, "suspect.tally-outvoted.server-0": 1},
                      "callers": {"suspect.bad-grant.server-1": 1, "suspect.tally-outvoted.server-0": 1},
                      "marks": {"server-1": [3], "server-0": [1]}}},
    {},  # a worker of a control keeps none
]


def test_the_generators_counters_are_added_up_over_the_workers():
    assert run.sdk_counters(WORKERS) == {
        "sum": {"suspect.bad-grant.server-1": 8, "calls.read-transactions": 12, "suspect.tally-outvoted.server-0": 1},
        "callers": {"suspect.bad-grant.server-1": 3, "calls.read-transactions": 2, "suspect.tally-outvoted.server-0": 1},
        "marks": {"server-0": [1], "server-1": [1, 3, 4]}}
    assert run.sdk_counters([]) == {"sum": {}, "callers": {}, "marks": {}}
    json.dumps(run.sdk_counters(WORKERS))  # it goes into the kept snapshot


def snapshot(members=STATED, sdk=True, before=reports(True, 100), after=reports(True, 900, 50)):
    """``SNAP`` (1,000 operations answered, 500 of them updates) as a window of
    the new cell: the member changed 800 answers and swallowed 50 requests."""
    counters = {
        "sum": {"suspect.bad-grant.server-1": 400, "suspect.tally-outvoted.server-1": 390,
                "suspect.tally-outvoted.server-3": 2, "calls.read-transactions": 510,
                "client.certificates-built": 505, "client.certificates-received": 1515},
        "callers": {"suspect.bad-grant.server-1": 32, "calls.read-transactions": 32,
                    "suspect.tally-outvoted.server-3": 2},
        # 32 callers: 24 far past the threshold, 4 at it, 4 that never met the member
        "marks": {"server-1": [2] * 4 + [3] * 4 + [40] * 20, "server-3": [1, 1]}}
    return dict(SNAP, cluster={"replicas": 5, "rf": 4, "f": 1, "quorum": 3, "byzantine": members},
                generator=dict(SNAP["generator"], **({"sdk_counters": counters} if sdk else {})),
                before=dict(SNAP["before"], replicas=dict(SNAP["before"]["replicas"], **before)),
                after=dict(SNAP["after"], replicas=dict(SNAP["after"]["replicas"], **after)))


def read(cell, snap):
    data = run.load_cell(REPO, cell)
    return {k: v["value"] for k, v in
            run.read_layer_metrics(data["layer_dir"], data["bench"], cell, snap).items()}


def test_the_readers_on_a_canned_snapshot():
    got = read(CELL, snapshot())
    assert got["byz.lies_per_op"] == pytest.approx(850 / 1000)
    assert got["byz.bad_grants_per_update"] == pytest.approx(400 / 500)
    assert got["byz.read_fallback_share"] == pytest.approx(100.0 * 10 / 500)
    assert got["byz.callers_avoiding_member_share"] == pytest.approx(100.0 * 24 / 32)
    assert got[BUILT] == pytest.approx(100.0 * 505 / 1515)
    # the five lists: the SDK's two waits and the tails beside rf4-ycsb-a's
    assert got["client.write1_p50_ms.ops"] == pytest.approx(2.0)
    assert got["client.write2_wait_p50_ms.ops"] == pytest.approx(20.0)
    assert got["tail.update_p95_ms"] == 900.0 and got["tail.read_p95_ms"] == 400.0
    assert "tail.read_p50_ms" not in got  # SNAP's latencies hold no median
    assert read(CELL, dict(snapshot(), latency={"read_p50_ms": 4.1}))["tail.read_p50_ms"] == 4.1
    # the cell reports no update tail end to end, so what moves that tail is not its
    assert not [k for k in got if k in ("client.write1_p50_ms", "store.fsyncs_per_update", "device.idle_share")]
    tpu = dict(snapshot(), platform="tpu", trace={"window": {"device_planes": 1, "busy_s": 0.005, "window_s": 5.0}})
    assert read(CELL, tpu)["device.idle_share.ops"] == pytest.approx(99.9)


@pytest.mark.parametrize("snap,silent", [
    (snapshot(members={}), READERS[:2] + READERS[3:]),             # no member stated: only the fall-backs read
    (snapshot(sdk=False), READERS[1:] + [BUILT]),                  # a generator that keeps no counters
    (snapshot(after=reports()), READERS[:1]),                      # the member reports no count of its own
    (dict(snapshot(), updates_ok=0), READERS[1:2]),
    (dict(snapshot(), ops_ok=500), READERS[2:3]),                  # a window without reads
    (dict(snapshot(), ops_ok=0, updates_ok=0), READERS[:3]),
])
def test_a_reader_that_finds_nothing_gives_nothing(snap, silent):
    got = read(CELL, snap)
    assert [n for n in READERS + [BUILT] if n not in got] == silent
    # the snapshot of a canned cell without any of it reads none of the five, and never a 0
    assert not [n for n in READERS + [BUILT] if n in read(CELL, SNAP)]


def test_the_readers_are_keyed_to_their_cells_and_the_five_lists_gained_the_new_one():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-5:]] == READERS + [BUILT]
    assert all(by_name[n]["workloads"] == [CELL] and by_name[n]["moves"] == "ops_s"
               and by_name[n]["source"] == "program_counter" for n in READERS)
    assert by_name[BUILT]["workloads"] == ["n64-ycsb-a", "n64-ycsb-c", CELL]
    for name in ("tail.update_p95_ms", "tail.read_p95_ms", "tail.read_p50_ms", "client.write1_p50_ms.ops",
                 "client.write2_wait_p50_ms.ops", "device.idle_share.ops"):
        assert by_name[name]["workloads"][-1] == CELL and by_name[name]["workloads"].count(CELL) == 1
    snap = dict(snapshot(), latency=dict(SNAP["latency"], read_p50_ms=5.0))
    for cell in (w["name"] for w in bench["workloads"]):
        got = read(cell, snap)
        for name in READERS + [BUILT]:
            assert (name in got) == (cell in by_name[name]["workloads"]), (cell, name)


@pytest.mark.parametrize("name", READERS + [BUILT])
def test_the_entries_are_the_files(name):
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = layer_reader.load(os.path.join(PERF, "layer_metrics", name + ".py"))
    assert {k: getattr(mod, k.upper()) for k in ("name", "unit", "layer", "moves", "source")} == \
        {k: entry[k] for k in ("name", "unit", "layer", "moves", "source")}
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    layers = {m["layer"] for m in bench["per_layer"][:-5]}
    assert entry["layer"] in layers  # a layer the benchmark already names, letter for letter


# ------------------------------------------------------- the data of the cell

def test_the_configuration_is_rf4_n5_but_for_what_the_issue_lists():
    new, old = run.load_cell(REPO, CELL)["config"], json.load(open(os.path.join(PERF, "configs", "rf4-n5.json")))
    differ = {k for k in set(new) | set(old) if new.get(k) != old.get(k)}
    assert differ == {"name", "source", "source_detail", "byzantine", "guarantees", "assumed"}
    assert new["byzantine"] == STATED and list(new["reduced"]) == ["recordcount"] and new["reduced"] == old["reduced"]
    assert new["guarantees"][:3] == old["guarantees"] and len(new["guarantees"]) == 4
    assert "server-1 forging" in new["guarantees"][3] and "no honest replica is accused" in new["guarantees"][3]
    assert {k: v for k, v in new["assumed"].items() if k in old["assumed"] and k != "device_state"} == \
        {k: v for k, v in old["assumed"].items() if k != "device_state"}
    assert set(new["assumed"]) - set(old["assumed"]) == {"member", "strategy"}
    entry = next(c for c in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["configs"] if c["name"] == CONFIG)
    assert entry["source"] == new["source"] and len(new["source"]) == 196 <= 200
    assert entry["reduced"] == ["recordcount"] and entry["file"] == "perf/configs/rf4-n5-byz1.json"
    assert len(entry["why"]) <= 200


def test_the_cell_is_what_the_issue_states():
    data = run.load_cell(REPO, CELL)
    bench, cell = data["bench"], data["cell"]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "ycsb-a", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "forge-cert" in cell["why"]
    assert [m["name"] for m in bench["end_to_end"] if run.metric_applies(m, CELL)] == ["ops_s", "setup_s"]
    assert len(bench["workloads"]) == 8 and len(bench["configs"]) == 6
    assert not [w for w in bench["workloads"] if w["chips"] != 1]
    # the same mix and the same sizes as the cell without the member
    twin = run.load_cell(REPO, "rf4-ycsb-a")
    assert data["traffic"] == twin["traffic"] and "faults" not in data["traffic"]
    assert all(data["config"][k] == twin["config"][k] for k in (
        "replicas", "rf", "f", "quorum", "recordcount", "threads", "generator_processes", "load_threads",
        "replica_processes", "storage_engine", "wal_fsync", "admission", "fast_path", "rehearsal"))
    # nothing is offered to the device before the load, as in rf4-ycsb-a
    assert run.warm_reach(384, {512, 8192}, 3, 32, 32, run.replay_items(data["config"], data["verbs"])) == 0


def test_the_control_table_has_every_control():
    import control

    assert set(control.CONTROLS) == {"accept-all", "stale-reads", "emptied-storage", "forged-log",
                                     "no-resync", "plain", "honest-member", "unstated-member"}
    for name, kwargs in control.CONTROLS.items():
        assert set(kwargs) <= {"launcher", "worker_script", "faults_dir", "boot", "refused_with"}
        for key in ("launcher", "worker_script"):
            assert key not in kwargs or os.path.isfile(kwargs[key])
        assert "faults_dir" not in kwargs or os.path.isdir(kwargs["faults_dir"])
    assert control.CONTROLS["honest-member"]["boot"] == {"byzantine": None}
    assert control.CONTROLS["unstated-member"]["boot"] == {"byzantine": STATED}
    assert control.CONTROLS["plain"]["refused_with"] == "NotCaughtUp"


# ------------------------------------------- the system against the reference

RECORDS, CALLERS, OPS_A_CALLER = 48, 4, 60


async def drive(byzantine, seed):
    """A seeded run of reads and updates through the SDK against a
    ``VirtualCluster`` of five, recorded in the generator's own row format;
    what the reference is given of a real run, gathered in one process."""
    from mochi_tpu.testing.virtual_cluster import VirtualCluster

    def look(vc):
        return {"byzantine": {r.server_id: cl.byzantine_report({
            "byzantine": r.byzantine_stats(),
            "strategy_counters": {k: v for k, v in r.metrics.counters.items()
                                  if k.startswith(cl.STRATEGY_COUNTERS)}}) for r in vc.replicas}}

    pool = ycsb.value_pool(seed)
    mix = ycsb.load_traffic(os.path.join(PERF, "traffic", "ycsb-a.json"))
    async with VirtualCluster(5, rf=4, byzantine=byzantine) as vc:
        clients = [vc.client(timeout_s=20.0, rng_seed=seed + i) for i in range(CALLERS)]
        for i in range(RECORDS):
            await ycsb.sdk_update(clients[i % CALLERS], ycsb.key_name(i), ycsb.make_value(pool, ycsb.LOAD_WRITER, i))
        before, counters0 = look(vc), ycsb._counters(clients)
        ops = []

        async def caller(client, cid):
            stream = ycsb.OpStream(mix, RECORDS, random.Random(f"ops:{seed}:{cid}"))
            retried = {}
            for seq in range(OPS_A_CALLER):
                is_update, rec = stream.next()
                key, t0 = ycsb.key_name(rec), time.monotonic()
                if is_update:
                    val = ycsb.make_value(pool, cid, seq)
                    await ycsb.with_retries(lambda: ycsb.sdk_update(client, key, val), random.Random(seq), retried)
                    ops.append([1, rec, t0, time.monotonic(), 1, cid, seq, zlib.crc32(val), 0])
                else:
                    val, grants = await ycsb.with_retries(lambda: ycsb.sdk_read(client, key), random.Random(seq), retried)
                    w, s = ycsb.parse_tag(val) or (-1, -1)
                    ops.append([0, rec, t0, time.monotonic(), 1, w, s, zlib.crc32(val) if val is not None else 0, grants])

        await asyncio.gather(*(caller(c, cid) for cid, c in enumerate(clients)))
        gained = ycsb._counter_deltas(clients, counters0)
        after = look(vc)
        hist = ref.build_histories(ops, ycsb.LOAD_WRITER, lambda w, s: zlib.crc32(ycsb.make_value(pool, w, s)))
        readback = {}
        for rec in sorted(hist):
            t0 = time.monotonic()
            val, grants = await ycsb.sdk_read(clients[rec % CALLERS], ycsb.key_name(rec))
            w, s = ycsb.parse_tag(val) or (-1, -1)
            readback[rec] = (w, s, zlib.crc32(val) if val is not None else 0, grants, t0)
        for c in clients:
            await c.close()
    return ops, hist, readback, before, after, gained


@pytest.mark.parametrize("boots,idle", [(STATED, 0), ({}, 1)])
def test_a_live_forging_member_is_caught_and_every_rule_of_the_reference_holds(boots, idle):
    ops, hist, readback, before, after, gained = asyncio.run(drive(boots, 2**31 + 45))
    assert len(ops) == CALLERS * OPS_A_CALLER and {op[ref.KIND] for op in ops} == {ref.READ, ref.UPDATE}
    guarantees = ref.check_window(ops, hist, 3) + ref.check_readback(readback, hist, 3)
    assert [c.line() for c in guarantees if not c.ok] == [] and len(guarantees) == 6
    held = {c.name: c for c in ref.check_byzantine(STATED, before, after, gained["sum"])}
    assert held["stated_members_that_never_acted_in_the_window"].value == idle
    assert held["honest_replicas_accused_by_typed_evidence"].value == 0
    deployed = dict(DEPLOYED, **after)
    config = {"storage_engine": "wal", "wal_fsync": "group", "admission": "on", "byzantine": STATED}
    assert value(ref.check_deployment(config, deployed), STRATEGY_CHECK).value == idle
    updates = sum(1 for op in ops if op[ref.KIND] == ref.UPDATE)
    caught = gained["sum"].get("suspect.bad-grant.server-1", 0)
    if boots:
        assert all(c.ok for c in held.values())
        # the member sits in about four of five replica sets and forges every Write1 answer there
        assert 0.5 * updates <= caught <= 1.1 * updates
        assert all(n > 2 for n in gained["marks"][MEMBER]) and len(gained["marks"][MEMBER]) == CALLERS
        assert after["byzantine"][MEMBER]["mutated_responses"] > before["byzantine"][MEMBER]["mutated_responses"] > 0
    else:
        assert caught == 0 and held["lies_the_callers_caught"].value == 0
        assert not held["lies_the_callers_caught"].ok
