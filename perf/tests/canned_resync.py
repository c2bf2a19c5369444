"""A kill and the runbook's restart (own directory, ``--resync-on-boot``) as
``schedule.run`` records them, canned: ``canned_faults.py``'s pair with the
restart's verb and record replaced by what ``restart_replica_resync`` leaves:
the replay of the replica's own log, and the ``storage.resync`` report of the
one pass that followed (``mochi_tpu/server/stages.py``), digest counters and
all.  ``PARENT_REPORT`` is the same pass as the parent commit reports it: no
``began_epoch_us``, no ``digest_local_ms``, no digest counters."""

import canned_faults as canned

DIGEST = {"shards_compared": 820, "shards_matched": 240, "keys_compared": 13_600, "keys_matched": 11_600}
PEER = {"pages": 3, "entries": 500, "adopted": 400, "abandoned": 0}
REPORT = {
    "full": True, "complete": True, "began_epoch_us": 1_790_000_004_900_000, "ms": 1_900.0,
    "config_ms": 40.0, "digest_ms": 1_600.0, "digest_local_ms": 350.0, "pull_ms": 900.0, "verify_ms": 500.0,
    "verify_wait_ms": 20.5, "apply_ms": 120.0, "flush_ms": 30.0,
    "pages": 12, "digest_pages": 4, "entries_pulled": 2_000, "entries_adopted": 1_600,
    "entries_redundant": 400, "entries_unowned": 0, "bad_certificates": 0, "bytes_pulled": 3_000_000,
    **DIGEST,
    "peers": 4, "by_peer": {f"server-{i}": dict(PEER) for i in (0, 1, 3, 4)},
}
NEW_KEYS = ("began_epoch_us", "digest_local_ms", *DIGEST)
PARENT_REPORT = {k: v for k, v in REPORT.items() if k not in NEW_KEYS}


def records(report=REPORT, ready_s=7.2, keys_before=24_000, entries=24_000):
    kill, back = canned.records(entries=entries, keys_before=keys_before)
    back.update(do="restart_replica_resync", at_s=4.0, started_s=4.02, seconds=ready_s + 0.05,
                timed={"ready_s": ready_s})
    kill.update(at_s=2.0, started_s=2.01)
    if report is not None:
        back["after"]["replica"]["storage"]["resync"] = report
    return [kill, back]
