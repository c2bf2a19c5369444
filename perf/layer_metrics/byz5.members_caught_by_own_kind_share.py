"""Stated members that the callers caught by a kind of mark that the member's OWN
strategy produces (``perf/reference_members.py`` ``OWN_KINDS``: ``forge-cert``
by ``bad-grant``; ``stale-replay`` by ``grant-conflict`` or
``tally-outvoted``), over the stated members, in percent: 100 where each of the
five was caught for what it does, not for what another does.  A cell that
states no member, or a run without the SDK's counters, gives nothing."""

import reference_members

NAME = "byz5.members_caught_by_own_kind_share"
UNIT = "%"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    members = (snap.get("cluster") or {}).get("byzantine") or {}
    gained = (snap["generator"].get("sdk_counters") or {}).get("sum")
    if not members or gained is None:
        return None
    caught = reference_members.caught_by_own_kind(members, gained)
    return 100.0 * sum(1 for n in caught.values() if n > 0) / len(members)
