"""Share of the recovery's trace in which an operation ran on the device:
the union of the device's op intervals over the traced seconds
(``perf/xplane.py``), where that trace overlaps a restart's command-to-READY
(in a cell with a fault schedule the profiler starts at the restart command).
A replay that the memo answers reads 0: the device did nothing for it."""

import schedule

NAME = "recovery.device_busy_share"
UNIT = "%"
LAYER = "device programs"
MOVES = "recover_s"
SOURCE = "device_trace"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    window = (snap.get("trace") or {}).get("window")
    if (not back or snap["platform"] != "tpu" or not window or window["window_s"] <= 0
            or "started_s" not in window):
        return None
    start, end = window["started_s"], window["started_s"] + window["window_s"]
    if not any(r["started_s"] < end and r["started_s"] + r["seconds"] > start for r in back):
        return None  # the trace is of some other stretch of the window
    return 100.0 * window["busy_s"] / window["window_s"]
