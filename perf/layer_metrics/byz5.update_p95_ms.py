"""``tail.update_p95_ms`` in the cell ``n16-byz5-ycsb-a``: the update tail of a
cell that reports none end to end (32 callers against 11 of 11 honest grants:
attempts made again stretch it).  Keyed to that cell alone (PR 46 changes no
accepted entry's ``workloads``); it reads what ``tail.update_p95_ms.py`` reads
and gives nothing where that gives nothing."""

import os

from layer_reader import load

NAME = "byz5.update_p95_ms"
UNIT = "ms"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "host_clock"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "tail.update_p95_ms.py")).read(snap)
