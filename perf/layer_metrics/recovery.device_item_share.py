"""Share of the signatures the service verified between the restart command
and READY that its routing sent to the device.  The foreground's RPCs of one
certificate (a quorum of grants each) are in both counts beside the replay's;
memo hits are in neither."""

import schedule

NAME = "recovery.device_item_share"
UNIT = "%"
LAYER = "verifier SPI and service queue"
MOVES = "recover_s"
SOURCE = "program_counter"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back:
        return None
    device, host = schedule.service_gain(back, "device_items"), schedule.service_gain(back, "host_routed_items")
    return 100.0 * device / (device + host) if device + host > 0 else None
