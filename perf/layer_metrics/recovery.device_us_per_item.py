"""Microseconds a signature on the device route between the restart command
and READY: what the service's ``verifier.flush-device`` timer (one backend call
routed to the device: prepare, dispatch, device time, readback) gained over
the items its routing sent there, both from the harness's looks at the service
just before and just after the event.  The foreground's flushes are in both
beside the replay's.  Nothing where no item took the device route."""

import schedule

NAME = "recovery.device_us_per_item"
UNIT = "us"
LAYER = "verifier SPI and service queue"
MOVES = "recover_s"
SOURCE = "program_span"

TIMER = "verifier.flush-device"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back or not all(r[look].get("service_stages") for r in back for look in ("before", "after")):
        return None
    items = schedule.service_gain(back, "device_items")
    if items <= 0:
        return None

    def flushed_ms(look):  # a timer that never ticked is not listed
        return look["service_stages"]["timers"].get(TIMER, {}).get("sum_ms", 0.0)

    return 1e3 * sum(flushed_ms(r["after"]) - flushed_ms(r["before"]) for r in back) / items
