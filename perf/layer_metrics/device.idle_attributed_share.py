"""Share of the device's idle seconds in the traced end of the window that lie
under a ``mochi.*`` span of the service (``perf/hostspans.py``): the rest,
``no_span``, is time in which the service had nothing to do.  None where the
trace holds no span at all."""

import hostspans

NAME = "device.idle_attributed_share"
UNIT = "%"
LAYER = "device programs"
MOVES = "ops_s"
SOURCE = "device_trace"


def read(snap):
    window = hostspans.of(snap).get("window")
    if not window or not window["spans"] or not window["idle_s"]:
        return None
    return 100.0 * (1.0 - window["idle_by_cause_s"][hostspans.NO_SPAN] / window["idle_s"])
