"""The re-hydration, end to end: the ``restart_replica_rehydrate`` command
(the killed replica's storage directory already emptied) to its READY, which
after ``--resync-on-boot`` means "re-hydrated from its peers": process boot,
two config passes, shard and key digests, every owned entry pulled from the
peers that hold it, every certificate checked, every newer entry applied, one
flush.  What the verb itself timed (``timed.ready_s``), summed over the
schedule's restarts; a restart that timed no such thing gives nothing."""

import schedule

NAME = "rehydrate.ready_s"
UNIT = "s"
LAYER = "replica dispatch and auth"
MOVES = "ops_s"
SOURCE = "host_clock"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back:
        return None
    s = [(r.get("timed") or {}).get("ready_s") for r in back]
    return float(sum(s)) if all(x is not None for x in s) else None
