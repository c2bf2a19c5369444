"""``byz.callers_avoiding_member_share`` in the cell ``n16-byz5-ycsb-a``: callers
whose marks of suspicion against a stated member passed the SDK's threshold
inside the window, the mean over the five members: 100 where every caller
routes its trimmed reads around all five when the window closes.  Keyed to that
cell alone (PR 46 changes no accepted entry's ``workloads``); it reads what
``byz.callers_avoiding_member_share.py`` reads and gives nothing where that
gives nothing."""

import os

from layer_reader import load

NAME = "byz5.callers_avoiding_members_share"
UNIT = "%"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "byz.callers_avoiding_member_share.py")).read(snap)
