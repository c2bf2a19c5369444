"""``recovery.device_busy_share`` over a RE-HYDRATION: share of the window's
trace in which an operation ran on the device (``perf/xplane.py``), where that
trace overlaps the verb's command-to-READY (in a cell whose schedule brings an
end-to-end time the profiler starts at that command).  Nothing where no
replica re-hydrated (a restart without a ``storage.resync`` report), off the
TPU, or where the trace is of another stretch of the window."""

import os

import schedule
from layer_reader import load

NAME = "rehydrate.device_busy_share"
UNIT = "%"
LAYER = "device programs"
MOVES = "ops_s"
SOURCE = "device_trace"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back or not all(r["after"]["replica"]["storage"].get("resync") for r in back):
        return None
    return load(os.path.join(os.path.dirname(__file__), "recovery.device_busy_share.py")).read(snap)
