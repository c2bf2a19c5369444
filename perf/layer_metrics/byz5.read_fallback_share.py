"""``byz.read_fallback_share`` in the cell ``n16-byz5-ycsb-a``: reads that went
out a second time, to all 16, because the 11 a trimmed read asked did not
agree.  A caller that holds more than two marks against each of the five asks
exactly the eleven honest members.  Keyed to that cell alone (PR 46 changes no
accepted entry's ``workloads``); it reads what ``byz.read_fallback_share.py``
reads and gives nothing where that gives nothing."""

import os

from layer_reader import load

NAME = "byz5.read_fallback_share"
UNIT = "%"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "byz.read_fallback_share.py")).read(snap)
