"""Entries the re-hydrating replica pulled, decoded and checked for each one
it adopted: ``entries_pulled`` / ``entries_adopted`` of its ``/status``
``storage.resync`` at READY (``mochi_tpu/server/stages.py``).  An empty replica
finds every shard mismatched at every peer, so it pulls each record from every
peer that also holds it: rf - 1 = 3 at rf=4.  A replica that keeps no such
report (the parent commit's) gives nothing."""

import schedule

NAME = "rehydrate.pulled_per_adopted"
UNIT = "ratio"
LAYER = "store and storage"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    reports = [r["after"]["replica"]["storage"].get("resync") for r in back]
    if not back or not all(reports):
        return None
    adopted = sum(rep["entries_adopted"] for rep in reports)
    return sum(rep["entries_pulled"] for rep in reports) / adopted if adopted else None
