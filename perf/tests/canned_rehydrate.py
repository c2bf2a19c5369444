"""A kill and a re-hydrating restart as ``schedule.run`` records them, canned:
``canned_faults.py``'s pair with the restart's verb and record replaced by what
``restart_replica_rehydrate`` leaves: nothing replayed, and the replica's
``storage.resync`` report (``mochi_tpu/server/stages.py``) beside it."""

import canned_faults as canned

REPORT = {
    "full": True, "complete": True, "ms": 17_300.0,
    "config_ms": 41.0, "digest_ms": 380.0, "pull_ms": 9_100.0, "verify_ms": 20_400.0,
    "verify_wait_ms": 6_250.5, "apply_ms": 3_300.0, "flush_ms": 120.0,
    "pages": 104, "digest_pages": 24, "entries_pulled": 71_940, "entries_adopted": 23_980,
    "entries_redundant": 47_960, "entries_unowned": 0, "bad_certificates": 0, "bytes_pulled": 108_000_000,
    "peers": 4, "by_peer": {f"server-{i}": {"pages": 26, "entries": 17_985, "adopted": 5_995, "abandoned": 0}
                            for i in (0, 1, 3, 4)},
}


def records(report=REPORT, ready_s=18.1, keys_before=24_000):
    kill, back = canned.records(entries=0, keys_before=keys_before)
    back.update(do="restart_replica_rehydrate", at_s=4.0, started_s=4.02, seconds=ready_s + 0.05,
                timed={"ready_s": ready_s})
    kill.update(at_s=2.0, started_s=2.01)
    if report is not None:
        back["after"]["replica"]["storage"]["resync"] = report
    return [kill, back]
