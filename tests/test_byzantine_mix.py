"""Several Byzantine members of more than one strategy in one cluster, at
the limit of the fault model (members = f): PR 46's deployment ``n16-f5-byz5``
(16 replicas, rf=16, f=5, quorum 11; three ``forge-cert``, two
``stale-replay``) and its small sibling (7 replicas, f=2, quorum 5; one of
each), driven through ``MochiDBClient`` on the CPU.

A seeded list of reads and updates, one at a time, is answered as the plain
dictionary model of ``perf/reference_members.py`` says; every acknowledged
update is read back from a quorum; each member is caught by a mark that ITS
strategy produces; no honest replica earns a mark that only a lie produces;
every replica's ``/status`` names its own strategy; and the SDK's grant
counters (``client.grants-*``) and read counters (``client.trimmed-*``) add
up, exactly.  Then a burst of writers on one record: at members = f two
writers that split the honest replicas both go round again, and the store
still ends on one of their values, under a quorum certificate.
"""

from __future__ import annotations

import asyncio
import functools
import json
import os
import random
import sys

import pytest

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
if PERF not in sys.path:
    sys.path.insert(0, PERF)

import reference_members as members_ref  # noqa: E402
import ycsb  # noqa: E402

from mochi_tpu.admin import AdminServer  # noqa: E402
from mochi_tpu.client.errors import MochiClientError  # noqa: E402
from mochi_tpu.client.txn import TransactionBuilder  # noqa: E402
from mochi_tpu.testing.virtual_cluster import VirtualCluster  # noqa: E402

N16 = {"server-1": "forge-cert", "server-4": "forge-cert", "server-7": "forge-cert",
       "server-10": "stale-replay", "server-13": "stale-replay"}
N7 = {"server-1": "forge-cert", "server-4": "stale-replay"}
SHAPES = {"n16-f5": (16, 5, 11, N16), "n7-f2": (7, 2, 5, N7)}
# what only a lie of the accused produces (perf/reference.py LIE_KINDS)
LIE_KINDS = ("bad-grant", "bad-certificate")
KEYS, OPS, CLIENTS, BURST = 12, 90, 3, 3


def gained(clients, before=None) -> dict:
    """What the SDK's counters gained since ``before`` (``ycsb._counters``;
    None: ever), summed over ``clients`` as the generator sums them, the runs
    of each timer (``calls.<timer>``) among them."""
    return ycsb._counter_deltas(clients, before or [{}] * len(clients))["sum"]


async def sdk_update(client, key, value, attempts=8):
    for attempt in range(attempts):
        try:
            await client.execute_write_transaction(TransactionBuilder().write(key, value).build())
            return attempt
        except MochiClientError:
            if attempt == attempts - 1:
                raise
            await asyncio.sleep(0.01 * (attempt + 1))


async def drive(shape: str, seed: int) -> dict:
    n, f, quorum, stated = SHAPES[shape]
    rng = random.Random(f"byzantine-mix:{shape}:{seed}")
    keys = [f"mix-{i}" for i in range(KEYS)]
    out: dict = {"shape": (n, f, quorum), "stated": stated}
    async with VirtualCluster(n, rf=n, byzantine=stated) as vc:
        assert vc.config.f == f and vc.config.quorum == quorum
        clients = [vc.client(timeout_s=30.0, rng_seed=seed + i) for i in range(CLIENTS)]
        model = members_ref.StoreModel()
        # ---- the load, then a seeded list of reads and updates, one at a time
        log, got = [], []
        for i, key in enumerate(keys):
            value = b"load-%d" % i
            await sdk_update(clients[i % CLIENTS], key, value)
            log.append(("update", key, value))
        # every caller meets every member once before anything is counted
        for c in clients:
            await ycsb.sdk_read(c, keys[0])
        before = ycsb._counters(clients)
        updates = reads = 0
        for i in range(OPS):
            client, key = clients[i % CLIENTS], keys[min(int(rng.expovariate(0.4)), KEYS - 1)]
            if rng.random() < 0.5:
                value = b"%s-%d-%d" % (shape.encode(), seed, i)
                assert await sdk_update(client, key, value) == 0  # no contention: first attempt
                log.append(("update", key, value))
                updates += 1
            else:
                log.append(("read", key))
                got.append(await ycsb.sdk_read(client, key))
                reads += 1
        out.update(log=log, got=got, updates=updates, reads=reads,
                   sequential=gained(clients, before))
        await asyncio.sleep(0.3)  # the answers after the quorum drain; every replica applies
        honest = vc.honest_replicas()
        model.replay(log)
        out["holders"] = {key: sum(1 for r in honest
                                   if (sv := r.store.data.get(key)) is not None and sv.value == want)
                          for key, want in model.values.items()}
        # ---- a burst: BURST writers of one record at once, each until acknowledged
        hot, values = keys[0], [b"burst-%d" % i for i in range(BURST)]
        before = ycsb._counters(clients)
        out["burst_attempts"] = await asyncio.gather(
            *(sdk_update(c, hot, v, attempts=16) for c, v in zip(clients, values)))
        out["burst"] = gained(clients, before)
        out["after_burst"] = [await ycsb.sdk_read(c, hot) for c in clients]
        out["burst_values"] = values
        out["total"] = gained(clients)
        # ---- what each replica says of itself
        status = {}
        for r in vc.replicas:
            shell = AdminServer(r)
            await shell.start()
            try:
                status[r.server_id] = json.loads(shell._route("/status")[2])["byzantine"]
            finally:
                await shell.close()
        out["status"] = status
        for c in clients:
            await c.close()
    return out


@functools.lru_cache(maxsize=None)
def run_of(shape: str) -> dict:
    return asyncio.run(drive(shape, 2**31 + 46))


shapes = pytest.mark.parametrize("shape", sorted(SHAPES))


@shapes
def test_the_deployment_stands_on_its_arithmetic(shape):
    n, f, quorum, stated = SHAPES[shape]
    a = members_ref.arithmetic(n, f, quorum, len(stated))
    assert a == {"honest": quorum, "holds": True, "slack": 0, "voting_share": pytest.approx(100.0 * quorum / n)}
    # one member more than f, or one replica fewer, and it no longer holds
    assert not members_ref.arithmetic(n, f, quorum, len(stated) + 1)["holds"]
    assert not members_ref.arithmetic(n - 1, f, quorum, len(stated))["holds"]
    assert members_ref.arithmetic(n, f, quorum, 0)["slack"] == f


@shapes
def test_every_read_is_answered_as_the_dictionary_model_answers_it(shape):
    run = run_of(shape)
    quorum = run["shape"][2]
    want = members_ref.StoreModel().replay(run["log"])
    assert len(want) == run["reads"] > 20 and run["updates"] > 20
    assert [value for value, _ in run["got"]] == want
    assert all(grants >= quorum for _, grants in run["got"])


@shapes
def test_every_acknowledged_update_is_held_by_a_quorum_of_honest_replicas(shape):
    run = run_of(shape)
    quorum = run["shape"][2]
    assert len(run["holders"]) == KEYS and min(run["holders"].values()) >= quorum


@shapes
def test_each_member_is_caught_by_its_own_kind_and_no_honest_replica_by_a_lie(shape):
    run = run_of(shape)
    caught = members_ref.caught_by_own_kind(run["stated"], run["total"])
    assert set(caught) == set(run["stated"]) and min(caught.values()) > 0, caught
    # the forgers by the grant check, the replayers by the subset and the tally
    for sid, strategy in run["stated"].items():
        bad = run["total"].get(f"suspect.bad-grant.{sid}", 0)
        assert (bad > 0) == (strategy == "forge-cert"), (sid, strategy, bad)
    accused = {name.split(".", 2)[2] for name, n in run["total"].items()
               if n > 0 and name.startswith("suspect.") and name.split(".")[1] in LIE_KINDS}
    assert accused and accused <= set(run["stated"])
    # and a mark of another strategy's kind is not counted as a member's own
    other = {"suspect.bad-grant.server-10": 9, "suspect.tally-outvoted.server-1": 9,
             "suspect.no-response.server-4": 9, "fanout.straggler-timeout.server-7": 9}
    assert members_ref.caught_by_own_kind(N16, other) == dict.fromkeys(N16, 0)


@shapes
def test_every_replicas_status_names_its_own_strategy(shape):
    run = run_of(shape)
    n = run["shape"][0]
    assert len(run["status"]) == n
    for sid, own in run["status"].items():
        assert own["strategy"] == run["stated"].get(sid), sid
        assert {"equivocations", "bad_grants", "resync_bad_certificates", "strategy",
                "mutated_responses", "dropped_requests"} <= set(own)
        assert own["dropped_requests"] == 0
        assert (own["mutated_responses"] > 0) == (sid in run["stated"]), (sid, own)


@shapes
def test_the_grant_counters_add_up(shape):
    run = run_of(shape)
    n, f, quorum, stated = run["shape"] + (run["stated"],)
    forgers = sum(1 for s in stated.values() if s == "forge-cert")
    replayers = len(stated) - forgers
    for phase in ("sequential", "burst", "total"):
        assert members_ref.grant_identity(run[phase]) == 0, (phase, members_ref.grant_counts(run[phase]))
    seq = members_ref.grant_counts(run["sequential"])
    # no contention: one Write1 round an update, cut from exactly the honest members
    assert run["sequential"]["calls.write1-phase"] == run["updates"]
    assert seq["voting"] == quorum * run["updates"] and seq["refused"] == seq["unused"] == 0
    assert 0 < seq["dropped-signature"] <= forgers * run["updates"]
    assert 0 < seq["dropped-timestamp"] <= replayers * run["updates"]
    assert quorum * run["updates"] < seq["received"] <= n * run["updates"]
    assert run["sequential"].get("suspect.grant-conflict." + min(
        s for s, k in stated.items() if k == "stale-replay"), 0) > 0
    # the burst: every writer was acknowledged, and a round that found no subset
    # is on the counters (unused or refused), not lost
    burst = members_ref.grant_counts(run["burst"])
    assert burst["voting"] >= quorum * BURST
    assert run["burst"]["calls.write1-phase"] >= BURST


@shapes
def test_the_read_counters_add_up(shape):
    run = run_of(shape)
    seq = run["sequential"]
    assert seq["client.trimmed-reads"] == run["reads"]
    fallbacks = seq.get("client.trimmed-read-fallbacks", 0)
    assert 0 <= fallbacks <= run["reads"]
    assert seq["calls.read-transactions"] == run["reads"] + fallbacks
    # a caller holds more than two marks against every member after its first few
    # operations, so most trimmed reads ask the honest members alone
    assert fallbacks <= run["reads"] // 2


@shapes
def test_a_burst_of_writers_on_one_record_ends_on_one_of_their_values(shape):
    run = run_of(shape)
    quorum = run["shape"][2]
    assert len(run["burst_attempts"]) == BURST
    values = {value for value, _ in run["after_burst"]}
    assert len(values) == 1 and values <= set(run["burst_values"])
    assert all(grants >= quorum for _, grants in run["after_burst"])
