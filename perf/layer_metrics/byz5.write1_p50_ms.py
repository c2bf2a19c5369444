"""``client.write1_p50_ms`` in the cell ``n16-byz5-ycsb-a``: the SDK's Write1
phase, send to the eleventh consistent honest grant, median of the window's
samples.  Keyed to that cell alone (PR 46 changes no accepted entry's
``workloads``); it reads what ``client.write1_p50_ms.py`` reads and gives
nothing where that gives nothing."""

import os

from layer_reader import load

NAME = "byz5.write1_p50_ms"
UNIT = "ms"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "client.write1_p50_ms.py")).read(snap)
