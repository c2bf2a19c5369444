"""The control's restart, under the cell's verb's name: the shipped PLAIN
restart (``perf/faults/restart_replica.py``, ``rf4-recover``'s verb: the killed
replica's process on its own storage directory WITHOUT ``--resync-on-boot``) at
this cell's size, and then the cell's own look at the replica's record
(``perf/faults/restart_replica_resync.py`` ``hold``).  READY follows the
verified replay alone, the replica serves behind its peers by what committed
while it was away, its ``/status`` has no resync pass to show, and the look has
to raise: that is what shows that the pass, and not the replay, is what the
cell's READY is held to."""

import os
import time

import schedule

_FAULTS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "faults")
_PLAIN = schedule.load_verb(_FAULTS, "restart_replica")
_CELL = schedule.load_verb(_FAULTS, "restart_replica_resync")
RESTARTS = True
END_TO_END = _PLAIN.END_TO_END


async def run(pc, event, state):
    asked_us = time.time_ns() // 1000
    timed = await _PLAIN.run(pc, event, state)
    print(f"[control plain] READY after {timed}", flush=True)
    _CELL.hold(pc, event["server_id"], asked_us)
    return timed
