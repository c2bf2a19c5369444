"""The killed replica comes back as a node that lost its disk: its storage
directory is removed and its process started again with ``--resync-on-boot``
(``ProcessCluster.restart_replica(server_id, resync=True)``), so READY means
"re-hydrated from its peers, every certificate checked as a Write2's is"
(the paper's UptoSpeed; docs/OPERATIONS.md section 3).  The command to READY
is in ``snap["end_to_end"]["recover_s"]`` and in the record's ``seconds``; the
per-layer reader ``rehydrate.ready_s`` reads it.

A product whose ``restart_replica`` cannot pass the flag is refused when the
schedule is checked, before anything boots."""

import inspect
import os
import shutil
import time

import schedule
from mochi_tpu.testing.process_cluster import ProcessCluster

RESTARTS = True
END_TO_END = "recover_s"

if "resync" not in inspect.signature(ProcessCluster.restart_replica).parameters:
    raise schedule.ScheduleError(
        "restart_replica_rehydrate: this product's ProcessCluster.restart_replica takes no "
        "'resync', so a replica cannot be started again with --resync-on-boot")


async def run(pc, event, state):
    directory = os.path.join(pc.storage_root, event["server_id"])
    assert os.path.isdir(directory) and os.listdir(directory), directory
    shutil.rmtree(directory)
    t0 = time.monotonic()
    await pc.restart_replica(event["server_id"], resync=True)
    return {"ready_s": time.monotonic() - t0}
