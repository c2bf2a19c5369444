"""Start the killed replica's process again with its exact original arguments
(``ProcessCluster.restart_replica``) and wait for its READY, which the product
defines as "recovered its committed state from its own WAL + snapshot
(verified replay)".  The command to READY is ``recover_s``: process boot, WAL
scan and the verify round trips."""

import time

RESTARTS = True
END_TO_END = "recover_s"


async def run(pc, event, state):
    t0 = time.monotonic()
    await pc.restart_replica(event["server_id"])
    return {"ready_s": time.monotonic() - t0}
