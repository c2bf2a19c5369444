"""A kill and a restart as ``schedule.run`` records them, canned: what the
reference's checks and the recovery's readers are tested on (``test_schedule``,
``test_span_readers``; the second is collected by tier-1 beside a
``tests/test_schedule.py`` of the product's own, so it cannot import the first
by name)."""

import cluster

SERVICE0 = {"device_items": 0, "host_routed_items": 1000, "memo_hits": 50_000, "memo_misses": 20_000}
SERVICE1 = {"device_items": 768, "host_routed_items": 2232, "memo_hits": 86_000, "memo_misses": 24_000}


def replica(keys_live, replay=None, rpcs=0):
    return {"store": {"keys_live": keys_live},
            "storage": {"engine": "durable", "replay": replay or {"entries": 0, "convicted": 0, "ms": 0.0}},
            "verifier": {"type": "CoalescingVerifier", "calls": rpcs, "inner_calls": rpcs,
                         "inner": {"type": "RemoteVerifier", "remote_batches": rpcs, "fallback_batches": 0}},
            "counters": {k: 1 for k in cluster.ADDITIVE}}


def records(entries=9000, convicted=0, keys_before=8000):
    return [
        {"do": "kill_replica", "server_id": "server-2", "at_s": 5.0, "started_s": 5.01, "t_mono": 105.0,
         "seconds": 0.01, "timed": {},
         "before": {"service": SERVICE0, "replica": replica(keys_before), "process_cpu": 40.0},
         "after": {"service": SERVICE0, "replica": None, "process_cpu": None}},
        {"do": "restart_replica", "server_id": "server-2", "at_s": 10.0, "started_s": 10.02, "t_mono": 110.0,
         "seconds": 4.0, "timed": {"ready_s": 4.0},
         "before": {"service": SERVICE0, "replica": None, "process_cpu": None},
         "after": {"service": SERVICE1, "process_cpu": 3.0,
                   "replica": replica(keys_before, {"entries": entries, "convicted": convicted, "ms": 3200.0}, rpcs=72)}},
    ]
