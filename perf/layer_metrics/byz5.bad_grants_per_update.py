"""``byz.bad_grants_per_update`` in the cell ``n16-byz5-ycsb-a``: grants of the
stated members that the callers' own check threw out
(``suspect.bad-grant.<member>``), summed over the five, for each update
acknowledged.  Three members forge every Write1 answer, two replay with VALID
signatures: about 3 where every forged grant lands before the eleventh honest
one, and the stale ones are ``byz5.stale_grants_dropped_per_update``'s.  Keyed
to that cell alone (PR 46 changes no accepted entry's ``workloads``); it reads
what ``byz.bad_grants_per_update.py`` reads and gives nothing where that gives
nothing."""

import os

from layer_reader import load

NAME = "byz5.bad_grants_per_update"
UNIT = "count"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    return load(os.path.join(os.path.dirname(__file__), "byz.bad_grants_per_update.py")).read(snap)
