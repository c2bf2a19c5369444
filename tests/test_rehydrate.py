"""A replica that lost its disk comes back empty and re-hydrates from its
peers (``MochiReplica.resync``, the paper's UptoSpeed; ``--resync-on-boot``).

Seeded, on the CPU, against the plain reference ``perf/reference_rehydrate.py``
(loaded by path, as the ``tests/test_perf_*.py`` shims load ``perf/tests``):
the re-hydrated replica's own store equals, key for key, the newest certified
entry any peer holds for every key it owns, and nothing else.  The same file
holds the report (``/status`` ``storage.resync``) and the spans to what
``mochi_tpu/server/stages.py`` names, with no clock in the assertions, and the
benchmark's five ``rehydrate.*`` readers to the report's own keys.
"""

import asyncio
import os
import random
import shutil
import sys
import types

import pytest

from mochi_tpu.client import TransactionBuilder
from mochi_tpu.obs import trace as obs_trace
from mochi_tpu.server import stages
from mochi_tpu.testing import VirtualCluster
from mochi_tpu.testing.byzantine import AttackStrategy, make_strategy
from mochi_tpu.testing.process_cluster import ProcessCluster
from mochi_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(REPO, "perf")
if PERF not in sys.path:
    sys.path.insert(0, PERF)  # the readers import the harness's ``schedule``

import layer_reader  # noqa: E402

ref = layer_reader.load(os.path.join(PERF, "reference_rehydrate.py"), "perf_")

VICTIM, PEER = "server-2", "server-4"
RECORDS, UPDATES = 40, 16
# a page's two attempts at a peer that answers nothing: short, the test waits for them
SILENT_TIMEOUT_S = 0.15


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=120))


def plain_store(replica) -> dict:
    """A replica's store as the reference takes it: {key: (timestamp, bytes, grants)}."""
    return {
        key: (replica.store._cert_ts(sv), bytes(sv.value), len(sv.current_certificate.grants))
        for key, sv in replica.store.data.items()
        if sv.exists and sv.current_certificate is not None
    }


async def write(client, key, value):
    await client.execute_write_transaction(TransactionBuilder().write(key, value).build())


async def rehydration(tmp_path, seed, scenario):
    """A cluster of 5 at rf=4, a seeded load and seeded updates; VICTIM is
    stopped, its storage directory emptied, and it is started again and
    re-hydrated.  Returns (cluster facts, the victim, its report)."""
    rng = random.Random(f"rehydrate:{seed}")
    keys = [f"user{rng.randrange(10**9)}" for _ in range(RECORDS)]
    storage = str(tmp_path / "storage")
    # PEER is a ByzantineReplica from the start, honest until its strategy is swapped in
    async with VirtualCluster(5, rf=4, storage_dir=storage, byzantine={PEER: AttackStrategy()}) as vc:
        client = vc.client()
        for key in keys:
            await write(client, key, rng.randbytes(64))
        for key in rng.sample(keys, UPDATES):
            await write(client, key, rng.randbytes(64))
        held = len(plain_store(vc.replica(VICTIM)))
        assert held > 0

        async def lose_the_disk(sid):
            directory = os.path.join(storage, sid)
            assert os.listdir(directory)
            shutil.rmtree(directory)
            if scenario == "updates-while-down":  # three of three grant while it is away
                for key in rng.sample(keys, UPDATES):
                    await write(client, key, rng.randbytes(64))

        if scenario in ("forge-cert", "silent"):
            peer = vc.replica(PEER)
            peer.strategy = make_strategy(scenario, seed=seed)
            peer.strategy.bind(peer)
        victim = await vc.restart_replica(VICTIM, before_boot=lose_the_disk)
        assert plain_store(victim) == {} and victim.resync_report() is None
        advanced = await victim.resync(
            timeout_s=SILENT_TIMEOUT_S if scenario == "silent" else 5.0)
        peers = {r.server_id: plain_store(r) for r in vc.replicas if r is not victim}
        served = {r.server_id: r.storage_stats()["anti_entropy"] for r in vc.replicas if r is not victim}
        facts = types.SimpleNamespace(
            want=ref.rehydrated(peers, vc.config.replica_set_for_key, VICTIM, vc.config.quorum),
            got={k: v[:2] for k, v in plain_store(victim).items()},
            held=held, advanced=advanced, served=served,
            timers={n: t.count for n, t in victim.metrics.timers.items()},
            spans=[ev for ev in victim.tracer.events() if ev["name"].startswith(stages.SPAN_PREFIX)],
            storage_stats=victim.storage_stats(),
            owned=[k for k in keys if VICTIM in vc.config.replica_set_for_key(k)],
        )
        return facts, victim.resync_report()


SCENARIOS = ("plain", "updates-while-down", "forge-cert", "silent")


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_the_rehydrated_store_equals_the_reference_key_for_key(tmp_path, scenario, seed):
    facts, report = run(rehydration(tmp_path, seed, scenario))
    # the reference: every owned key, the newest certified entry any peer holds, nothing unowned
    assert ref.differences(facts.want, facts.got) == {"missing": 0, "extra": 0, "older": 0, "other_bytes": 0}
    assert set(facts.got) == set(facts.owned) and len(facts.got) >= facts.held
    assert facts.advanced == len(facts.got)
    # the report's own arithmetic, whatever the peers did
    assert report["full"] and report["entries_pulled"] == (
        report["entries_adopted"] + report["entries_redundant"] + report["bad_certificates"])
    assert report["entries_adopted"] >= len(facts.got) and report["entries_unowned"] == 0
    assert report["peers"] == 4 and set(report["by_peer"]) == {f"server-{i}" for i in (0, 1, 3, 4)}
    assert sum(p["entries"] for p in report["by_peer"].values()) == report["entries_pulled"]
    assert sum(p["adopted"] for p in report["by_peer"].values()) == report["entries_adopted"]
    assert facts.storage_stats["resync"] == report and "replay" in facts.storage_stats
    if scenario == "forge-cert":
        # its sync entries do not verify: all of them counted, none adopted, the others cover it
        assert report["bad_certificates"] == report["by_peer"][PEER]["entries"] > 0
        assert report["by_peer"][PEER]["adopted"] == 0 and report["complete"]
    elif scenario == "silent":
        # three pulls of it (two config passes, the shard digests) ended on a page that failed twice
        # (since PR 37 the digest stage's counters stand beside them: nothing was compared with it)
        assert report["by_peer"][PEER] == dict.fromkeys(stages.PEER_KEYS, 0) | {"abandoned": 3}
        assert report["complete"] and report["bad_certificates"] == 0   # f=1 of its shards' other owners
        assert sum(p["abandoned"] for s, p in report["by_peer"].items() if s != PEER) == 0
    else:
        assert report["bad_certificates"] == 0 and report["complete"]
        assert all(p["abandoned"] == 0 for p in report["by_peer"].values())
        # an empty replica pulls each record from each of the three peers that also hold it
        assert report["entries_pulled"] == 3 * len(facts.owned)


@pytest.mark.parametrize("scenario", ["plain", "forge-cert"])
def test_each_stage_ticks_once_a_run_a_round_trip_or_a_page_and_the_spans_are_named(tmp_path, scenario):
    facts, report = run(rehydration(tmp_path, 21, scenario))
    t = facts.timers
    assert t[stages.RESYNC] == t[stages.RESYNC_CONFIG] == t[stages.RESYNC_FLUSH] == 1
    # no round trip failed: one pull tick a page, one digest tick a peer's rollups and a page of key digests
    assert t[stages.RESYNC_PULL] == report["pages"] == sum(p["pages"] for p in report["by_peer"].values())
    assert t[stages.RESYNC_DIGEST] == report["peers"] + report["digest_pages"]
    # the two config passes pull an empty page from each peer; every other page held owned entries
    data_pages = report["pages"] - 2 * report["peers"]
    assert t[stages.RESYNC_VERIFY] == t[stages.RESYNC_APPLY] == data_pages > 0
    # the serving side: what the peers counted is what the victim pulled
    assert sum(s["sync_pages_served"] for s in facts.served.values()) == report["pages"]
    assert sum(s["sync_entries_served"] for s in facts.served.values()) == report["entries_pulled"]
    assert all(s["sync_serve_ms"] > 0 for s in facts.served.values())
    # spans: the stages' constants and nothing else under the prefix, one a tick, forced (no sampling)
    names = [ev["name"] for ev in facts.spans]
    assert set(names) == {stages.SPAN_DIGEST, stages.SPAN_DIGEST_LOCAL, stages.SPAN_PULL, stages.SPAN_VERIFY,
                          stages.SPAN_APPLY, stages.SPAN_FLUSH}
    for span, timer in ((stages.SPAN_DIGEST, stages.RESYNC_DIGEST), (stages.SPAN_PULL, stages.RESYNC_PULL),
                        (stages.SPAN_DIGEST_LOCAL, stages.RESYNC_DIGEST_LOCAL),
                        (stages.SPAN_VERIFY, stages.RESYNC_VERIFY), (stages.SPAN_APPLY, stages.RESYNC_APPLY),
                        (stages.SPAN_FLUSH, stages.RESYNC_FLUSH)):
        assert names.count(span) == t[timer]
    pulls = [ev["args"] for ev in facts.spans if ev["name"] == stages.SPAN_PULL]
    assert {a["peer"] for a in pulls} == set(report["by_peer"])
    assert sum(a["entries"] for a in pulls) == report["entries_pulled"]
    assert len({ev["args"]["trace_id"] for ev in facts.spans}) == 1   # one run, one trace
    # every key of the report is one that stages.py names
    assert set(report) == {"full", "complete", "began_epoch_us", "ms", "peers", "by_peer",
                           *stages.STAGE_KEYS, *stages.COUNTER_KEYS}
    assert all(set(p) == set(stages.PEER_KEYS) for p in report["by_peer"].values())


def test_a_targeted_resync_keeps_no_report_and_records_no_span_unsampled(tmp_path):
    async def main():
        async with VirtualCluster(5, rf=4) as vc:
            await write(vc.client(), "k", b"v")
            victim = await vc.restart_replica(VICTIM)
            if VICTIM in vc.config.replica_set_for_key("k"):
                assert await victim.resync(["k"]) == 1
            assert victim.resync_report() is None and victim.storage_stats()["resync"] is None
            assert not [ev for ev in victim.tracer.events() if ev["name"].startswith(stages.SPAN_PREFIX)]
            assert victim.metrics.timers[stages.RESYNC].count <= 1

    run(main())


def test_the_wall_clock_verify_wait_counts_only_while_every_live_pull_waits(monkeypatch):
    clock = iter(range(100))
    monkeypatch.setattr(stages.time, "perf_counter", lambda: float(next(clock)))
    r = stages.ResyncRun(Metrics(), obs_trace.Tracer("t", sample_rate=0.0), full=True)   # t=0
    r.begin_pull(); r.begin_pull()
    with r.waiting():                   # one of two waits: the other can still decode and apply
        pass
    assert r.report["verify_wait_ms"] == 0.0
    with r.waiting():
        with r.waiting():               # both wait from t=1
            pass                        # t=2: 1 s in which nothing could be applied
    assert r.report["verify_wait_ms"] == 1000.0
    with r.waiting():
        r.end_pull()                    # the other pull ended: the one left is waiting, from t=3
                                        # t=4
    assert r.report["verify_wait_ms"] == 2000.0
    r.end_pull()
    assert r.finish(True)["complete"] is True


@pytest.mark.parametrize("calls,expect", [
    ([False], [[]]),                                   # the parent's argv, byte for byte
    ([True], [["--resync-on-boot"]]),
    ([True, False, True], [["--resync-on-boot"], [], ["--resync-on-boot"]]),   # that one spawn alone
])
def test_restart_replica_passes_resync_on_boot_to_that_spawn_alone(calls, expect):
    original = ["python", "-m", "mochi_tpu.server", "--config", "c.json", "--server-id", "server-1",
                "--seed-file", "s", "--storage-dir", "/d"]
    sp = types.SimpleNamespace(proc=types.SimpleNamespace(returncode=-9, pid=1), argv=list(original),
                               index=1, server_ids=["server-1"])
    pc = ProcessCluster.__new__(ProcessCluster)
    pc.host_process, pc._spawn_env, pc.pin_cores, pc.ready_timeout_s = {"server-1": sp}, None, False, 5.0
    seen = []

    async def spawn(sp_, env):
        seen.append(list(sp_.argv))

    async def nothing(*args):
        return None

    pc._spawn, pc._reap, pc._wait_ready = spawn, nothing, nothing

    async def main():
        for resync in calls:
            if resync:
                await pc.restart_replica("server-1", resync=True)
            else:
                await pc.restart_replica("server-1")     # as every caller the parent had calls it

    run(main())
    assert seen == [original + extra for extra in expect]
    assert sp.argv == original


def test_a_process_started_with_resync_on_boot_says_so_on_its_ready_line(tmp_path):
    """Real processes: the killed replica's directory is removed, the restart
    passes ``--resync-on-boot``, READY comes after the re-hydration and says
    how it went; a plain restart's READY line is the parent's."""
    async def main():
        storage = str(tmp_path / "storage")
        async with ProcessCluster(n_servers=4, rf=4, n_processes=4, storage_dir=storage) as pc:
            client = pc.client()
            for i in range(12):
                await write(client, f"key-{i}", b"value-%d" % i)
            lines = []
            wait_ready = pc._wait_ready

            async def listening(sp):
                stdout = sp.proc.stdout
                readline = stdout.readline

                async def tee():
                    line = await readline()
                    lines.append(line.decode().strip())
                    return line

                stdout.readline = tee
                try:
                    await wait_ready(sp)
                finally:
                    stdout.readline = readline

            pc._wait_ready = listening
            pc.kill_replica("server-1")
            await pc.process_for("server-1").proc.wait()
            shutil.rmtree(os.path.join(pc.storage_root, "server-1"))
            await pc.restart_replica("server-1", resync=True)
            assert lines[-1].startswith("READY server-1 ") and lines[-1].endswith(" resync=complete")
            # it serves what it re-hydrated, alone: a read needs three of four, and one more is down
            pc.kill_replica("server-2")
            await pc.process_for("server-2").proc.wait()
            res = await client.execute_read_transaction(TransactionBuilder().read("key-7").build())
            assert res.operations[0].value == b"value-7"
            await pc.restart_replica("server-2")
            assert len(lines[-1].split()) == 3      # READY <id> <port>, as it always was

    run(main())


# ---- the benchmark's readers read the report's own keys


def test_the_benchmarks_readers_read_what_the_product_reports():
    r = stages.ResyncRun(Metrics(), obs_trace.Tracer("t", sample_rate=0.0), full=True)
    r.count("entries_pulled", 72_000)
    r.count("entries_adopted", 24_000)
    r.report["verify_wait_ms"] = 1234.5
    replica = {"store": {"keys_live": 24_000}, "verifier": {},
               "storage": {"replay": {"entries": 0}, "resync": r.finish(True)}}
    service = {"device_items": 0, "host_routed_items": 0}
    snap = {"platform": "cpu", "faults": [{
        "do": "restart_replica_rehydrate", "server_id": "server-1", "started_s": 4.0, "seconds": 20.0,
        "timed": {"ready_s": 19.9}, "before": {"replica": None, "service": service},
        "after": {"replica": replica, "service": dict(service, device_items=300, host_routed_items=100)}}]}
    got = {}
    for name in ("ready_s", "pulled_per_adopted", "verify_wait_ms", "device_item_share", "device_busy_share"):
        mod = layer_reader.load(os.path.join(PERF, "layer_metrics", f"rehydrate.{name}.py"))
        assert mod.NAME == f"rehydrate.{name}" and mod.MOVES == "ops_s"
        got[name] = mod.read(snap)
    assert got == {"ready_s": 19.9, "pulled_per_adopted": 3.0, "verify_wait_ms": 1234.5,
                   "device_item_share": 75.0, "device_busy_share": None}
