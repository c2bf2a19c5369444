"""SIGKILL the process that hosts the replica (``ProcessCluster.kill_replica``)
and wait until it is gone.  The schedule refuses a replica that shares its
process: the kill takes the whole process."""

import time

KILLS = True


async def run(pc, event, state):
    t0 = time.monotonic()
    pid = pc.kill_replica(event["server_id"])
    await pc.process_for(event["server_id"]).proc.wait()
    return {"pid": pid, "gone_s": time.monotonic() - t0}
