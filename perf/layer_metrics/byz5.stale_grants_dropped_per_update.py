"""Validly signed MultiGrants that fell outside the timestamp-consistent subset
(``client.grants-dropped-timestamp``: ``client.py`` ``_quorum_grant_subset``),
gained over the window and summed over the callers, for each update
acknowledged.  Two ``stale-replay`` members re-sign every grant at ``timestamp
% 1000`` with their real keys, so the grant check passes them and the subset
drops them: about 2, plus what honest replicas lose under contention.  A run
whose SDK keeps no grant counters (the parent's) gives nothing, and never a 0."""

NAME = "byz5.stale_grants_dropped_per_update"
UNIT = "count"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    gained = (snap["generator"].get("sdk_counters") or {}).get("sum") or {}
    if "client.grants-received" not in gained or not snap["updates_ok"]:
        return None
    return gained.get("client.grants-dropped-timestamp", 0) / snap["updates_ok"]
