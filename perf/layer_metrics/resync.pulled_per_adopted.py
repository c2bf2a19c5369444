"""Entries the restarted replica pulled, decoded and checked for each one it
adopted: ``entries_pulled`` / ``entries_adopted`` of its ``/status``
``storage.resync`` at READY (``mochi_tpu/server/stages.py``), read as
``rehydrate.pulled_per_adopted`` reads the emptied restart's.  Each of the
three peers that also hold a record names it in its delta where its digest
differs, so a record that moved while the replica was away is pulled up to
rf - 1 = 3 times, and a record in flight when compared once more for nothing.
A replica that keeps no such report gives nothing."""

import os

from layer_reader import load

NAME = "resync.pulled_per_adopted"
UNIT = "ratio"
LAYER = "store and storage"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "rehydrate.pulled_per_adopted.py")).read(snap)
