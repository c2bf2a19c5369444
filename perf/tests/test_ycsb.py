"""The generator against YCSB's own numbers."""

import collections
import json
import os
import random

import pytest

import ycsb

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_zipfian_head_probabilities_are_ycsbs():
    # ZipfianGenerator over 10**10 items, constant 0.99: P(rank 0) = 1/zetan,
    # P(rank 1) = 0.5**0.99 / zetan, with YCSB's precomputed zetan
    z = ycsb.Zipfian(ycsb.SCRAMBLED_ITEM_COUNT, random.Random(7), zetan=ycsb.SCRAMBLED_ZETAN)
    n = 200_000
    counts = collections.Counter(z.next() for _ in range(n))
    assert counts[0] / n == pytest.approx(1 / 26.46902820178302, abs=0.002)
    assert counts[1] / n == pytest.approx(0.5 ** 0.99 / 26.46902820178302, abs=0.002)
    assert max(counts) < ycsb.SCRAMBLED_ITEM_COUNT


def test_zeta_of_small_counts():
    assert ycsb.zeta(1, 0.99) == 1.0
    assert ycsb.zeta(2, 0.99) == pytest.approx(1 + 0.5 ** 0.99)


def test_fnvhash64_is_javas_signed_fnv1():
    # FNV-1 of eight zero octets, then Math.abs of the signed long
    h = ycsb.FNV_OFFSET_BASIS_64
    for _ in range(8):
        h = (h * ycsb.FNV_PRIME_64) & (2 ** 64 - 1)
    expect = h if h < 2 ** 63 else 2 ** 64 - h
    assert ycsb.fnvhash64(0) == expect
    assert all(0 <= ycsb.fnvhash64(i) < 2 ** 63 for i in range(1000))
    assert len({ycsb.fnvhash64(i) for i in range(1000)}) == 1000


@pytest.mark.parametrize("records", [1000, 10000])
def test_scrambled_zipfian_hottest_record_share(records):
    # the hottest record carries rank 0's ~3.8% (plus the little that other
    # ranks hash onto it), whatever the record count, and stays in range
    g = ycsb.ScrambledZipfian(records, random.Random(3))
    n = 100_000
    counts = collections.Counter(g.next() for _ in range(n))
    assert all(0 <= k < records for k in counts)
    top = counts.most_common(2)
    assert 0.035 < top[0][1] / n < 0.05
    assert 0.016 < top[1][1] / n < 0.03
    assert top[0][0] == ycsb.fnvhash64(0) % records


def test_same_seed_same_operations_other_seed_other_order():
    mix = ycsb.load_traffic(os.path.join(PERF, "traffic", "ycsb-a.json"))
    a = ycsb.OpStream(mix, 1000, random.Random("ops:5:0"))
    b = ycsb.OpStream(mix, 1000, random.Random("ops:5:0"))
    c = ycsb.OpStream(mix, 1000, random.Random("ops:6:0"))
    sa, sb, sc = ([s.next() for _ in range(500)] for s in (a, b, c))
    assert sa == sb and sa != sc
    assert 0.4 < sum(u for u, _ in sa) / 500 < 0.6


def test_workload_c_is_read_only():
    mix = ycsb.load_traffic(os.path.join(PERF, "traffic", "ycsb-c.json"))
    s = ycsb.OpStream(mix, 1000, random.Random(1))
    assert not any(s.next()[0] for _ in range(500))


def test_traffic_file_is_validated(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"readproportion": 0.5, "updateproportion": 0.6,
                               "requestdistribution": "zipfian"}))
    with pytest.raises(ValueError):
        ycsb.load_traffic(str(bad))
    bad.write_text(json.dumps({"readproportion": 0.5, "updateproportion": 0.5,
                               "requestdistribution": "latest"}))
    with pytest.raises(ValueError):
        ycsb.load_traffic(str(bad))


def test_values_are_record_sized_unique_and_tagged():
    pool = ycsb.value_pool(9)
    values = {ycsb.make_value(pool, w, s) for w in (0, 1, ycsb.LOAD_WRITER) for s in range(200)}
    assert len(values) == 600
    assert all(len(v) == ycsb.VALUE_BYTES for v in values)
    assert ycsb.parse_tag(ycsb.make_value(pool, 5, 77)) == (5, 77)
    assert ycsb.parse_tag(b"BAD-WRITE2:abcdefgh") is None
    assert ycsb.parse_tag(None) is None
    assert ycsb.make_value(ycsb.value_pool(10), 5, 77) != ycsb.make_value(pool, 5, 77)


def test_a_failed_attempt_is_made_again_and_the_last_failure_is_raised(monkeypatch):
    import asyncio

    monkeypatch.setattr(ycsb, "RETRY_BACKOFF_S", 0.0)
    calls = []

    async def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("best agreement 2 < quorum 3")
        return "committed"

    retried: dict = {}
    assert asyncio.run(ycsb.with_retries(flaky, random.Random(1), retried)) == "committed"
    assert len(calls) == 3 and sum(retried.values()) == 2

    async def down():
        calls.append(1)
        raise TimeoutError("no reply")

    del calls[:]
    retried = {}
    with pytest.raises(TimeoutError):
        asyncio.run(ycsb.with_retries(down, random.Random(1), retried))
    assert len(calls) == ycsb.OP_ATTEMPTS and sum(retried.values()) == ycsb.OP_ATTEMPTS - 1


def test_the_sdk_counters_are_taken_over_the_window_by_caller_and_marks_by_replica():
    class Timer:
        def __init__(self, n):
            self.total_count = n

    class Metrics:
        def __init__(self, counters, timers):
            self.counters, self.timers = counters, {k: Timer(v) for k, v in timers.items()}

    class Client:
        def __init__(self, counters, timers):
            self.metrics = Metrics(counters, timers)

    clients = [Client({"suspect.no-response.server-1": 1, "client.checkpoints": 5}, {"read-transactions": 10}),
               Client({"client.checkpoints": 7}, {"read-transactions": 20})]
    before = ycsb._counters(clients)
    clients[0].metrics.counters.update({"suspect.no-response.server-1": 3, "fanout.straggler-timeout.server-1": 2,
                                        "suspect.tally-outvoted.server-0": 1, "client.checkpoints": 6})
    clients[0].metrics.timers["read-transactions"].total_count = 14
    clients[1].metrics.counters["suspect.grant-conflict.server-1"] = 4
    got = ycsb._counter_deltas(clients, before)
    assert got["sum"] == {"suspect.no-response.server-1": 2, "fanout.straggler-timeout.server-1": 2,
                          "suspect.tally-outvoted.server-0": 1, "client.checkpoints": 1,
                          "calls.read-transactions": 4, "suspect.grant-conflict.server-1": 4}
    assert got["callers"]["client.checkpoints"] == 1 and got["callers"]["calls.read-transactions"] == 1
    # what a caller's routing score of a replica adds up, caller by caller
    assert {k: sorted(v) for k, v in got["marks"].items()} == {"server-1": [4, 4], "server-0": [1]}
