"""``recovery.device_item_share`` over the runbook's restart (replay, then one
resync pass): share of the signatures the service verified between the verb's
command and READY that its routing sent to the device (``device_items`` over
``device_items`` + ``host_routed_items``, the gains between the harness's two
looks: the replay's bulk and the delta's certificates together, the
foreground's RPCs in both counts, memo hits in neither).  Nothing where no
replica resynced (a restart without a ``storage.resync`` report)."""

import os

import schedule
from layer_reader import load

NAME = "resync.device_item_share"
UNIT = "%"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back or not all(r["after"]["replica"]["storage"].get("resync") for r in back):
        return None
    return load(os.path.join(os.path.dirname(__file__), "recovery.device_item_share.py")).read(snap)
