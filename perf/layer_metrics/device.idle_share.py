"""Share of the traced end of the window in which no operation ran on the
device: 1 - union of the device's op intervals / traced seconds, from the
profiler trace taken in the service (``perf/xplane.py``)."""

NAME = "device.idle_share"
UNIT = "%"
LAYER = "device programs"
MOVES = "update_p95_ms"
SOURCE = "device_trace"


def read(snap):
    window = snap["trace"].get("window")
    # off the TPU there is no device trace to read; on it, a trace with no
    # device event is a device that did nothing
    if snap["platform"] != "tpu" or not window or window["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - window["busy_s"] / window["window_s"])
