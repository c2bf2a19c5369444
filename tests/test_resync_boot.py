"""A crashed replica restarts on its OWN disk and closes the gap from its peers
before READY (the runbook's ``--data-dir`` + ``--resync-on-boot``; the paper's
UptoSpeed on a replica that still has its state), in ONE bounded pass.

Seeded, on the CPU, against the plain reference ``perf/reference_resync.py``
(loaded by path, as the ``tests/test_perf_*.py`` shims load ``perf/tests``):
after the pass the restarted replica's own store equals, key for key, the
newest certified entry that it or any peer held for every key it owns when the
pass began; the same restart WITHOUT the pass is behind on exactly the keys
that committed while it was down.  The same file holds the digest stage's
counters (``/status`` ``storage.resync``) to their arithmetic, the boot to one
pass however long a writer keeps writing, and a ``ProcessCluster``'s children
to dying with the process that started them.
"""

import asyncio
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import time
import types
import urllib.request

import pytest

from mochi_tpu.client import TransactionBuilder
from mochi_tpu.protocol import SyncDigestRequestToServer, SyncRequestToServer
from mochi_tpu.server import stages
from mochi_tpu.testing import VirtualCluster
from mochi_tpu.testing import process_cluster
from mochi_tpu.testing.byzantine import AttackStrategy, make_strategy
from mochi_tpu.testing.process_cluster import ProcessCluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(REPO, "perf")
if PERF not in sys.path:
    sys.path.insert(0, PERF)  # the reference loads the harness's ``layer_reader``

import layer_reader  # noqa: E402

ref = layer_reader.load(os.path.join(PERF, "reference_resync.py"), "perf_")

VICTIM, PEER = "server-2", "server-4"
PEERS = {f"server-{i}" for i in (0, 1, 3, 4)}
RECORDS, UPDATES = 40, 16
SILENT_TIMEOUT_S = 0.15  # a page's two attempts at a peer that answers nothing
CLEAN = {"missing": 0, "extra": 0, "older": 0, "other_bytes": 0}


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


async def restart(tmp_path, seed, scenario, resync=True):
    """A cluster of 5 at rf=4, a seeded load and seeded updates; VICTIM is
    stopped, updates commit while it is down (but in ``nothing-missed``), and
    it is started again on its own directory (emptied in ``emptied``) and, with
    ``resync``, makes the boot's one pass.  Returns the facts of the run."""
    rng = random.Random(f"resync-boot:{seed}")
    keys = [f"user{rng.randrange(10**9)}" for _ in range(RECORDS)]
    storage = str(tmp_path / "storage")
    async with VirtualCluster(5, rf=4, storage_dir=storage, byzantine={PEER: AttackStrategy()}) as vc:
        client = vc.client()
        # four records that share a shard with a record that moves, and never move themselves:
        # in a shard that differs, their digests are the ones that match
        siblings, n = [], 0
        for key in keys[:4]:
            while vc.config.token_for_key(f"sibling{n}") != vc.config.token_for_key(key):
                n += 1
            siblings.append(f"sibling{n}")
            n += 1
        for key in keys + siblings:
            await write(client, key, rng.randbytes(64))
        for key in rng.sample(keys, UPDATES):
            await write(client, key, rng.randbytes(64))
        owned = {k for k in keys + siblings if VICTIM in vc.config.replica_set_for_key(k)}
        held = plain_store(vc.replica(VICTIM))
        meanwhile: set = set()

        async def while_it_is_down(sid):
            directory = os.path.join(storage, sid)
            assert os.listdir(directory)
            if scenario == "emptied":
                shutil.rmtree(directory)
            if scenario != "nothing-missed":  # three of three grant while it is away
                for key in keys[:4] + rng.sample(keys[4:], UPDATES - 4):
                    await write(client, key, rng.randbytes(64))
                    meanwhile.add(key)
            if scenario == "silent":
                # only now: a silent peer beside the one that is down would be two faults
                # in the sets that hold both, and nothing could commit there
                peer = vc.replica(PEER)
                peer.strategy = make_strategy("silent", seed=seed)
                peer.strategy.bind(peer)

        victim = await vc.restart_replica(VICTIM, before_boot=while_it_is_down)
        replayed = plain_store(victim)
        assert victim.resync_report() is None
        requests = []
        peer_send = victim._peer_send

        async def listening(sid, info, payload, timeout_s):
            requests.append((sid, payload))
            return await peer_send(sid, info, payload, timeout_s)

        victim._peer_send = listening
        racing, racer = set(), None
        if scenario == "racing":
            async def keep_writing():
                for key in rng.sample(keys, UPDATES):
                    await write(client, key, rng.randbytes(64))
                    racing.add(key)

            racer = asyncio.ensure_future(keep_writing())
            await asyncio.sleep(0)  # the first update is in flight when the pass begins
        # the peers as they stand when the pass begins: no await between this look and its start
        peers = {r.server_id: plain_store(r) for r in vc.replicas if r is not victim}
        t_before = time.time_ns() // 1000
        advanced = None
        if resync:
            advanced = await victim.resync(
                timeout_s=SILENT_TIMEOUT_S if scenario == "silent" else 5.0)
        if racer is not None:
            await racer
        return types.SimpleNamespace(
            want=ref.resynced(replayed, peers, vc.config.replica_set_for_key, VICTIM, vc.config.quorum),
            got={k: v[:2] for k, v in plain_store(victim).items()},
            replayed={k: v[:2] for k, v in replayed.items()},
            held=held, owned=owned, siblings=set(siblings), meanwhile=meanwhile & owned, racing=racing & owned,
            advanced=advanced, report=victim.resync_report(), requests=requests,
            t_before=t_before, t_after=time.time_ns() // 1000,
            timers={n: t.count for n, t in victim.metrics.timers.items()},
            spans=[ev for ev in victim.tracer.events() if ev["name"].startswith(stages.SPAN_PREFIX)],
        )


def digest_arithmetic(report):
    """The digest stage's counters add up: every key compared either matched or
    was asked for and came back, every shard compared either matched or differed."""
    assert report["keys_compared"] == report["keys_matched"] + report["entries_pulled"]
    assert 0 <= report["shards_matched"] <= report["shards_compared"]
    assert report["entries_pulled"] == (
        report["entries_adopted"] + report["entries_redundant"] + report["bad_certificates"])


SCENARIOS = ("nothing-missed", "updates-while-down", "racing", "silent", "emptied")


@pytest.mark.parametrize("seed", [31, 32])
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_after_the_pass_the_store_equals_the_reference_key_for_key(tmp_path, scenario, seed):
    f = run(restart(tmp_path, seed, scenario))
    report = f.report
    # everything that it or a quorum-certified peer held when the pass began is there, nothing else
    assert ref.differences(f.want, f.got) == CLEAN
    assert set(f.got) == f.owned
    assert f.t_before <= report["began_epoch_us"] <= f.t_after and report["full"]
    digest_arithmetic(report)
    assert set(report["by_peer"]) == PEERS and report["peers"] == 4 and report["complete"]
    if scenario == "silent":
        # three pulls of it (two config passes, the shard digests) ended on a page that failed twice
        assert report["by_peer"][PEER] == dict.fromkeys(stages.PEER_KEYS, 0) | {"abandoned": 3}
        assert sum(p["abandoned"] for s, p in report["by_peer"].items() if s != PEER) == 0
    else:
        assert all(p["abandoned"] == 0 for p in report["by_peer"].values())
    # shards: every owned shard a peer has a rollup for is compared, and either matches or differs
    differing = report["shards_compared"] - report["shards_matched"]
    if scenario == "nothing-missed":
        # the replay brought back everything: the digest stage decides that nothing is pulled
        assert f.replayed == f.got and f.advanced == 0
        assert differing == 0 == report["keys_compared"] == report["entries_pulled"]
        assert report["shards_matched"] == report["shards_compared"] > 0 and report["digest_pages"] == 0
    elif scenario == "emptied":
        # an empty store matches nothing: every shard differs at every peer, every key is asked for
        assert f.replayed == {} and f.advanced == len(f.got)
        assert report["shards_matched"] == 0 == report["keys_matched"]
        assert report["keys_compared"] == report["entries_pulled"] == 3 * len(f.owned)
    else:
        assert f.replayed == {k: v[:2] for k, v in f.held.items()}     # the replay alone: as killed
        assert differing > 0 and report["keys_compared"] > report["keys_matched"]
        # the siblings it owns lie in shards that differ and match, once a peer that holds them
        assert report["keys_matched"] >= len(f.owned & f.siblings) * (2 if scenario == "silent" else 3) > 0
        if scenario == "updates-while-down":
            # each record that moved is named by each of the three peers that hold it, and pulled
            assert report["entries_pulled"] == 3 * len(f.meanwhile)
            assert report["entries_adopted"] == f.advanced == len(f.meanwhile)
        if scenario == "racing":
            # what committed DURING the pass is not asked of it (it arrives as Write2s: the replica listens)
            assert f.racing and ref.behind(f.replayed, f.want) >= f.meanwhile - f.racing


@pytest.mark.parametrize("seed", [31, 32])
def test_the_same_restart_without_the_pass_is_behind_on_exactly_what_committed_meanwhile(tmp_path, seed):
    f = run(restart(tmp_path, seed, "updates-while-down", resync=False))
    assert f.report is None and not f.requests
    assert f.got == f.replayed == {k: v[:2] for k, v in f.held.items()}
    assert ref.behind(f.got, f.want) == f.meanwhile and f.meanwhile
    d = ref.differences(f.want, f.got)
    assert d["older"] == len(f.meanwhile) and d["missing"] == 0
    # and the pass is what closes it: the same seed with it (the test above) is behind on nothing
    g = run(restart(tmp_path / "again", seed, "updates-while-down"))
    assert g.meanwhile == f.meanwhile and ref.behind(g.got, g.want) == set()


def test_an_emptied_directory_makes_the_requests_and_leaves_the_report_it_did_before(tmp_path):
    f = run(restart(tmp_path, 33, "emptied"))
    report = f.report
    for sid in PEERS:
        kinds = [(type(p), getattr(p, "prefix", None), getattr(p, "tokens", None) is not None)
                 for s, p in f.requests if s == sid]
        pulls = report["by_peer"][sid]["pages"] - 2
        key_pages = sum(1 for k in kinds if k[0] is SyncDigestRequestToServer and k[2])
        # two config passes, the shard rollups, the key digests of every shard, then the entries
        assert kinds[:3] == [(SyncRequestToServer, "_CONFIG_", False)] * 2 + [(SyncDigestRequestToServer, None, False)]
        assert kinds[3:] == ([(SyncDigestRequestToServer, None, True)] * key_pages
                             + [(SyncRequestToServer, None, False)] * pulls) and pulls >= 1 <= key_pages
    assert sum(1 for _ in f.requests) == report["pages"] + report["peers"] + report["digest_pages"]
    # the report: PR 33's keys with PR 33's arithmetic, and the new ones beside them
    old = {"full", "complete", "ms", "config_ms", "digest_ms", "pull_ms", "verify_ms", "verify_wait_ms", "apply_ms",
           "flush_ms", "pages", "digest_pages", "entries_pulled", "entries_adopted", "entries_redundant",
           "entries_unowned", "bad_certificates", "bytes_pulled", "peers", "by_peer"}
    assert set(report) - old == {"began_epoch_us", "digest_local_ms", *stages.DIGEST_KEYS}
    assert report["entries_pulled"] == 3 * len(f.owned) and report["entries_adopted"] == len(f.owned)
    assert report["entries_redundant"] == 2 * len(f.owned) and report["bad_certificates"] == 0
    assert all(set(p) == {"pages", "entries", "adopted", "abandoned"} for p in report["by_peer"].values())


def test_the_digest_stage_ticks_its_own_timer_and_span_twice_a_peer(tmp_path):
    f = run(restart(tmp_path, 34, "updates-while-down"))
    t, report = f.timers, f.report
    # a peer: one walk for this replica's shard rollups, one for its key digests of the shards that differ
    assert t[stages.RESYNC_DIGEST_LOCAL] == 2 * report["peers"] and report["digest_local_ms"] > 0
    assert t[stages.RESYNC_DIGEST] == report["peers"] + report["digest_pages"]
    assert t[stages.RESYNC] == t[stages.RESYNC_CONFIG] == 1
    local = [ev["args"] for ev in f.spans if ev["name"] == stages.SPAN_DIGEST_LOCAL]
    assert len(local) == 2 * report["peers"] and {a["peer"] for a in local} == PEERS
    assert all(a["entries"] > 0 for a in local)
    # the pull spans carry the delta's size a page
    pulls = [ev["args"] for ev in f.spans if ev["name"] == stages.SPAN_PULL]
    assert sum(a["entries"] for a in pulls) == report["entries_pulled"] == report["keys_compared"] - report["keys_matched"]


def _http_json(port: int, path: str) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return json.loads(r.read())


def test_a_boot_with_resync_on_boot_makes_one_pass_while_a_writer_keeps_committing(tmp_path):
    """Real processes: the killed replica comes back on its own directory
    with ``--resync-on-boot`` while a writer never stops; READY follows ONE
    full pass, and says what the replica has caught up to."""
    async def main():
        storage = str(tmp_path / "storage")
        base = 20000 + os.getpid() % 10000   # below the ephemeral range, off the harness's 24000
        async with ProcessCluster(n_servers=5, rf=4, n_processes=5, storage_dir=storage,
                                  admin_base_port=base) as pc:
            client = pc.client()
            for i in range(24):
                await write(client, f"key-{i}", b"value-%d" % i)
            stop, written = asyncio.Event(), []

            async def keep_writing():
                i = 0
                while not stop.is_set():
                    await write(client, f"key-{i % 24}", b"again-%d" % i)
                    written.append(i)
                    i += 1

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
            writer = asyncio.ensure_future(keep_writing())
            try:
                await asyncio.sleep(0.3)
                pc.kill_replica("server-1")
                await pc.process_for("server-1").proc.wait()
                before = len(written)
                await asyncio.sleep(0.5)                      # commits it misses
                t0 = time.time_ns() // 1000
                await asyncio.wait_for(pc.restart_replica("server-1", resync=True), 60)
                t1 = time.time_ns() // 1000
                await asyncio.sleep(0.3)                      # and the writer goes on after READY
            finally:
                stop.set()
                await writer
            assert len(written) > before
            ready = lines[-1].split()
            assert ready[:2] == ["READY", "server-1"] and ready[-1] == "resync=complete"
            port = base + pc.process_for("server-1").index * pc.n_servers
            status, metrics = _http_json(port, "/status"), _http_json(port, "/metrics")
            report = status["storage"]["resync"]
            assert len(ready) == 4 and t0 < report["began_epoch_us"] < t1
            assert status["storage"]["replay"]["entries"] > 0      # its own disk came back first
            # ONE full pass: one tick of the whole-run config stage, one shard-rollup request a peer
            timers = metrics["timers"]
            assert timers[stages.RESYNC_CONFIG]["count"] == 1
            assert timers[stages.RESYNC_DIGEST]["count"] == report["peers"] + report["digest_pages"]
            assert report["complete"] and report["keys_compared"] == report["keys_matched"] + report["entries_pulled"]

    run(main())


HARNESS = """
import asyncio, json, sys
sys.path.insert(0, {repo!r})
from mochi_tpu.testing.process_cluster import ProcessCluster

async def main():
    pc = ProcessCluster(n_servers=4, rf=4, n_processes=3, verifier="service")
    await pc.start()
    print(json.dumps([sp.pid for sp in [*pc.processes, pc.service_process]]), flush=True)
    await asyncio.sleep(600)          # killed long before

asyncio.run(main())
"""


def _gone(pid: int) -> bool:
    """No such process, or a zombie nobody has collected yet."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as fh:
            return fh.read().rsplit(b")", 1)[1].split()[0] == b"Z"
    except OSError:
        return True


@pytest.mark.skipif(process_cluster.die_with_parent(0) is None, reason="no prctl here")
@pytest.mark.parametrize("sig", [signal.SIGKILL, signal.SIGTERM])
def test_the_children_of_a_process_cluster_die_with_the_process_that_started_them(sig):
    """A harness that is killed never reaches ``close()``: its three replica
    processes and its verifier service are gone within 5 s all the same."""
    harness = subprocess.Popen([sys.executable, "-c", HARNESS.format(repo=REPO)],
                               stdout=subprocess.PIPE, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        pids = json.loads(harness.stdout.readline())
        assert len(pids) == 4 and not any(map(_gone, pids))
        harness.send_signal(sig)
        harness.wait(timeout=10)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not all(map(_gone, pids)):
            time.sleep(0.05)
        assert [pid for pid in pids if not _gone(pid)] == []
    finally:
        if harness.poll() is None:
            harness.kill()
        harness.wait(timeout=10)
        harness.stdout.close()
