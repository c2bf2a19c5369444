"""The control's restart: the same emptied storage directory as the shipped
``restart_replica_rehydrate``, and a plain restart WITHOUT ``--resync-on-boot``.
The replica prints READY at once and serves; asked alone it has none of the
records it had acknowledged, so the direct read-back, and ``correct``, have to
fail: that is what shows that the re-hydration, and not a quorum behind the
answer, is what the cell's checks see."""

import os
import shutil
import time

RESTARTS = True
END_TO_END = "recover_s"


async def run(pc, event, state):
    directory = os.path.join(pc.storage_root, event["server_id"])
    assert os.path.isdir(directory) and os.listdir(directory), directory
    shutil.rmtree(directory)
    t0 = time.monotonic()
    await pc.restart_replica(event["server_id"])
    return {"ready_s": time.monotonic() - t0}
