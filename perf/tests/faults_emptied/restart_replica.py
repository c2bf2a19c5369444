"""The control's restart: the replica comes back with an EMPTIED storage
directory, as a node whose disk was lost, or a restart that skipped the replay,
would.  It prints READY and serves; asked alone it has none of the records it
had acknowledged, so the direct read-back, and ``correct``, have to fail."""

import os
import shutil

import schedule

_REAL = schedule.load_verb(
    os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "faults"),
    "restart_replica")
RESTARTS = True
END_TO_END = _REAL.END_TO_END


async def run(pc, event, state):
    directory = os.path.join(pc.storage_root, event["server_id"])
    assert os.path.isdir(directory) and os.listdir(directory), directory
    shutil.rmtree(directory)
    return await _REAL.run(pc, event, state)
