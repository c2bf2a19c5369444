"""``tail.read_p95_ms`` in the cell ``n16-byz5-ycsb-a``: the read tail; a read
that fell back to all 16 pays two round trips.  Keyed to that cell alone (PR 46
changes no accepted entry's ``workloads``); it reads what
``tail.read_p95_ms.py`` reads and gives nothing where that gives nothing."""

import os

from layer_reader import load

NAME = "byz5.read_p95_ms"
UNIT = "ms"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "host_clock"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "tail.read_p95_ms.py")).read(snap)
