"""The harness's own waiting and sizing logic, without a cluster."""

import asyncio
import json
import os
import time

import pytest

import probe
import run
import schedule

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PERF)


def status(ladder, comb, min_device=384):
    return {"requests": 0, "items": 0, "verifier": {"hits": 0, "misses": 0, "inner": {
        "batches_flushed": 0, "fallback_batches": 0,
        "device": {"device_items": 0, "host_routed_items": 0, "min_device_items": min_device,
                   "ready_buckets": ladder, "failed_buckets": [], "comb_failed_buckets": []},
        "comb": {"ready_buckets": comb, "registered_signers": 64}}}}


class FakeCluster:
    def __init__(self, statuses):
        self.statuses = list(statuses)
        self.asked = 0

    def service_status(self):
        self.asked += 1
        return self.statuses.pop(0) if len(self.statuses) > 1 else self.statuses[0]

    def cpu_seconds(self):
        return {"verifier-service": 0.0}


@pytest.fixture
def no_control(monkeypatch):
    import service_launch

    monkeypatch.setattr(service_launch, "request", lambda ctl, req, **kw: {"programs_built": 7})


def test_programs_ready_waits_for_both_programs_of_every_offered_size(no_control, monkeypatch):
    monkeypatch.setattr(asyncio, "sleep", lambda s: _nothing())
    pc = FakeCluster([
        status([512, 8192], [512, 8192]),
        status([512, 8192], [512, 1024, 8192]),            # the comb came first
        status([512, 1024, 8192], [512, 1024, 8192]),
        status([512, 1024, 2048, 8192], [512, 1024, 2048, 8192]),
    ])
    how = asyncio.run(run.programs_ready(pc, "ctl", [384, 600, 937, 1376]))
    assert how.startswith("ready") and pc.asked == 4


async def _nothing():
    return None


def test_programs_ready_needs_nothing_where_nothing_was_offered(no_control):
    assert asyncio.run(run.programs_ready(FakeCluster([status([], [])]), "ctl", [])) == "not needed"


def test_programs_ready_falls_back_to_quiet(no_control, monkeypatch):
    monkeypatch.setattr(asyncio, "sleep", lambda s: _nothing())
    pc = FakeCluster([status([512, 8192], [512, 8192])])   # never lists a bucket for 600
    how = asyncio.run(run.programs_ready(pc, "ctl", [600], quiet_s=0.0))
    assert how.startswith("presumed")


def test_warm_sizes_cover_every_doubling_between_the_ends(monkeypatch):
    sent = []

    async def fake_mismatches(rv, signers, label, size):
        sent.append(size)
        return 0

    class RV:
        async def close(self):
            pass

    class PC:
        keypairs = {}

    monkeypatch.setattr(probe, "_verdict_mismatches", fake_mismatches)
    monkeypatch.setattr(probe, "_no_fallback_verifier", lambda pc: RV())
    got = asyncio.run(probe.warm_device_buckets(PC(), 1, 384, 1376))
    assert got == {"sizes": sent, "mismatches": 0}
    assert sent[0] == 384 and sent[-1] == 1376 and sent == sorted(sent)
    assert all(b / a <= probe.WARM_STEP + 0.01 for a, b in zip(sent, sent[1:]))


@pytest.mark.parametrize("cell,args,reach", [
    # lowest, ready, quorum, loaders, writers
    ("n64-ycsb-a", (384, {512, 8192}, 43, 8, 16), 8192),     # the callers reach the crossover: every size
    ("n64-ycsb-c", (384, {512, 8192}, 43, 8, 0), 688),       # nothing updates: only what the load can pile up
    ("rf4-ycsb-a", (384, {512, 8192}, 3, 32, 32), 0),        # 3-grant certificates reach nothing
    ("rf4-recover", (384, {512, 8192}, 3, 32, 32), 0),
    # a replay of more signatures than the memo holds reaches every bucket, as n64's callers do
    ("rf4-50k-recover", (384, {512, 8192}, 3, 32, 32, 120_000), 8192),
    ("a replay that stays under the crossover", (384, {512, 8192}, 3, 32, 32, 383), 0),
    ("no program of both kinds", (384, set(), 43, 8, 16), 0),
    ("a service that routes nothing to the device", (0, {512}, 43, 8, 16), 0),
])
def test_the_warm_up_offers_what_the_mix_and_the_load_can_reach(cell, args, reach):
    assert run.warm_reach(*args) == reach


KILL1 = os.path.join(PERF, "traffic", "ycsb-a-kill1.json")


def shipped(name):
    with open(os.path.join(PERF, "configs", name + ".json")) as fh:
        return json.load(fh)


def verbs_of(mix_path):
    with open(mix_path) as fh:
        return schedule.validate(json.load(fh)["faults"], os.path.join(PERF, "faults"))


@pytest.mark.parametrize("config,change,items", [
    ("rf4-n5-50k", {}, 50_000 * 4 // 5 * 3),              # 120,000 grants against a memo of 65,536
    ("rf4-n5-50k", {"recordcount": 27_307}, 0),           # 21,845 certificates, 65,535 grants: the memo holds them all
    ("rf4-n5-50k", {"recordcount": 27_308}, 65_538),      # one certificate more than it holds
    ("rf4-n5-50k", {"memo_items": None}, 0),
    ("rf4-n5", {}, 0),                                    # states no memo: no replay reach
    ("rf4-n5", {"recordcount": 10**6}, 0),
    ("n64-f21", {}, 0),
])
def test_the_replay_reaches_the_device_where_a_replicas_certificates_outnumber_the_stated_memo(config, change, items):
    assert run.replay_items(dict(shipped(config), **change), verbs_of(KILL1)) == items


def test_a_mix_that_restarts_nothing_has_no_replay_reach():
    assert run.replay_items(shipped("rf4-n5-50k"), []) == 0
    # and the rehearsal's shape of the new configuration stays under its memo
    config = shipped("rf4-n5-50k")
    assert run.replay_items(dict(config, **config["rehearsal"]), verbs_of(KILL1)) == 0


@pytest.mark.parametrize("cell,reach", [("n64-ycsb-a", 8192), ("n64-ycsb-c", 688), ("rf4-ycsb-a", 0),
                                        ("rf4-recover", 0), ("rf4-50k-recover", 8192)])
def test_the_shipped_cells_are_offered_what_they_were_and_the_new_one_every_size(cell, reach):
    # as ``run_cell`` calls it, on the service's shipped crossover and boot buckets
    data = run.load_cell(REPO, cell)
    config, traffic = data["config"], data["traffic"]
    writers = config["threads"] if float(traffic["updateproportion"]) > 0 else 0
    assert run.warm_reach(384, {512, 8192}, config["quorum"], config["load_threads"], writers,
                          run.replay_items(config, data["verbs"])) == reach


def test_a_schedules_trace_starts_at_the_restart_command_and_any_other_at_the_windows_end():
    events = schedule.bind(json.load(open(KILL1))["faults"], verbs_of(KILL1), 7, 30.0, 5, 1,
                           {f"server-{i}": i for i in range(5)})
    assert [e["do"] for e in events] == ["kill_replica", "restart_replica"]
    assert run.trace_from_s(events, 30.0, run.TRACE_SECONDS) == 10.0
    assert run.trace_from_s([], 30.0, run.TRACE_SECONDS) == 25.0
    # a schedule none of whose verbs brings an end-to-end metric is traced as a cell without one
    assert run.trace_from_s(events[:1], 30.0, run.TRACE_SECONDS) == 25.0


def test_a_run_past_its_limit_ends_itself_stops_what_it_started_and_says_how_far_it_came():
    stopped = []

    async def stuck():
        run.say("loaded 10 records in 1.0s, 0 failed")
        try:
            await asyncio.sleep(60)
        finally:
            stopped.append(True)  # as ``run_cell``'s own ``finally`` stops the cluster

    with pytest.raises(run.RunFailure, match="still running"):
        asyncio.run(run.within(stuck(), 0.05))
    assert stopped == [True]
    assert run.SAID[-1] == "loaded 10 records in 1.0s, 0 failed"

    async def quick():
        return {"correct": True}

    assert asyncio.run(run.within(quick(), 5.0)) == {"correct": True}
    # under the 1200 s at which a checkout's first run is ended from outside, with room to stop a cluster
    assert run.RUN_LIMIT_S <= 1200 - 60


def test_a_run_that_fails_puts_its_last_commentary_on_standard_error(monkeypatch, capsys):
    async def stuck(args, data, launcher, worker_script, boot):
        run.say("cluster READY in 1.0s")
        await asyncio.sleep(60)

    monkeypatch.setattr(run, "run_cell", stuck)
    monkeypatch.setattr(run, "RUN_LIMIT_S", time.monotonic() - run.T_PROCESS_START + 0.05)
    monkeypatch.setattr(run, "build_native", lambda: None)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert run.main(["--workload", "rf4-recover", "--seed", "1", "--seconds", "1", "--rehearse"]) == 3
    out, err = capsys.readouterr()
    assert not out.strip().startswith("{")  # no result line
    assert "[perf] said: cluster READY in 1.0s" in err
    assert err.strip().splitlines()[-1].startswith("[perf] no result: still running")


# tier-1 collects this file through ``tests/test_perf_run_helpers.py``; PR 43's
# tests (the read median's reader, the tree's facts) have no shim of their own
# there (a benchmark PR adds no file outside ``perf/``) and ride along with it
from test_byzantine_cell import *  # noqa: E402,F401,F403  (PR 45's likewise)
from test_read_p50_reader import *  # noqa: E402,F401,F403
from test_treestate import *  # noqa: E402,F401,F403
