"""``device.idle_share`` in the cell ``n16-byz5-ycsb-a``: the device's idle share
of the traced seconds: whether any of the window's flushes (16 requests of 11
grants an update, thinned by a memo the load has filled) reaches the routing's
crossover.  Keyed to that cell alone (PR 46 changes no accepted entry's
``workloads``); it reads what ``device.idle_share.py`` reads and gives nothing
where that gives nothing."""

import os

from layer_reader import load

NAME = "byz5.device_idle_share"
UNIT = "%"
LAYER = "device programs"
MOVES = "ops_s"
SOURCE = "device_trace"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "device.idle_share.py")).read(snap)
