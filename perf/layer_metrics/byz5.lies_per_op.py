"""``byz.lies_per_op`` in the cell ``n16-byz5-ycsb-a``: what the five stated
members did, by their own count, for each operation answered.  Every member
sits in every replica set (rf = n) and changes every answer it gives, so it
reads about five times what a caller sends one member an operation.  Keyed to
that cell alone (PR 46 changes no accepted entry's ``workloads``); it reads
what ``byz.lies_per_op.py`` reads and gives nothing where that gives nothing."""

import os

from layer_reader import load

NAME = "byz5.lies_per_op"
UNIT = "count"
LAYER = "replica dispatch and auth"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "byz.lies_per_op.py")).read(snap)
