"""The digest stage's own time on the restarted replica's one thread:
``digest_local_ms`` of ``/status`` ``storage.resync`` at READY
(``mochi_tpu/server/stages.py``): its walks of its OWN digests,
``export_shard_digests()`` and ``store._iter_digests()`` over the shards that
differ, once each a peer.  They run one after the other on the replica's loop,
so the sum is wall time as it stands and lies inside ``resync.catchup_ms``;
the round trips to the peers (``digest_ms``) are summed over pulls that overlap
each other and these walks, and are left to the report.  It decides everything
that is pulled on a store the replay filled, and nothing on an empty one.  A
report without ``digest_local_ms`` (the parent commit's) gives nothing."""

import schedule

NAME = "resync.digest_ms"
UNIT = "ms"
LAYER = "store and storage"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    reports = [r["after"]["replica"]["storage"].get("resync") for r in back]
    if not back or not all(rep and "digest_local_ms" in rep for rep in reports):
        return None
    return float(sum(rep["digest_local_ms"] for rep in reports))
