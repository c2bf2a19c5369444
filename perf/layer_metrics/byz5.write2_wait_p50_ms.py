"""``client.write2_wait_p50_ms`` in the cell ``n16-byz5-ycsb-a``: the SDK's wait
from the Write2 fan-out to the eleventh agreeing answer (sixteen replicas each
check an 11-grant certificate and log the write), median of the window's
samples.  Keyed to that cell alone (PR 46 changes no accepted entry's
``workloads``); it reads what ``client.write2_wait_p50_ms.py`` reads and gives
nothing where that gives nothing."""

import os

from layer_reader import load

NAME = "byz5.write2_wait_p50_ms"
UNIT = "ms"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "client.write2_wait_p50_ms.py")).read(snap)
