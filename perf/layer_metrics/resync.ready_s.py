"""The runbook's restart of a crashed replica, end to end: the
``restart_replica_resync`` command (the killed replica's storage directory
left as it was) to its READY, which after ``--resync-on-boot`` means "verified
replay of its own WAL and snapshot, then ONE digest-and-delta pass against its
peers": process boot, the replay's certificates through the service, two
config passes, shard and key digests against four moving peers, the delta
pulled, checked and applied, one flush.  What the verb itself timed
(``timed.ready_s``), read as ``rehydrate.ready_s`` reads the emptied restart's;
a restart that timed no such thing gives nothing."""

import os

from layer_reader import load

NAME = "resync.ready_s"
UNIT = "s"
LAYER = "replica dispatch and auth"
MOVES = "ops_s"
SOURCE = "host_clock"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "rehydrate.ready_s.py")).read(snap)
