"""``client.certificates_built_share`` in the cell ``n16-byz5-ycsb-a``:
certificates the SDK built over certificates its replies carried: about one in
eleven (9.1) where a read's eleven agreeing answers each carry one and the SDK
builds the returned one's.  Keyed to that cell alone (PR 46 changes no accepted
entry's ``workloads``); it reads what ``client.certificates_built_share.py``
reads and gives nothing where that gives nothing."""

import os

from layer_reader import load

NAME = "byz5.certificates_built_share"
UNIT = "%"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "client.certificates_built_share.py")).read(snap)
