"""Callers whose SDK routes its trimmed reads around a stated Byzantine
member when the window closes: those that gained more than
``SUSPICION_THRESHOLD`` marks of suspicion against it inside the window (the
SDK's score counts the marks of its last 60 s and avoids a replica ABOVE the
threshold, ``client.py`` ``_quorum_targets``; a window is shorter than that),
over all callers, in percent; with several members, the mean over them.  The
callers are counted as the most that any one counter moved in (every caller
encodes an envelope).  A cell that states no member, or a run without the
SDK's counters, gives nothing."""

NAME = "byz.callers_avoiding_member_share"
UNIT = "%"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"

SUSPICION_THRESHOLD = 2  # client.py's, copied: the SDK avoids a replica above it


def read(snap):
    members = (snap.get("cluster") or {}).get("byzantine") or {}
    sdk = snap["generator"].get("sdk_counters") or {}
    callers = max((sdk.get("callers") or {}).values(), default=0)
    if not members or not callers:
        return None
    avoiding = sum(sum(1 for n in (sdk.get("marks") or {}).get(sid, ()) if n > SUSPICION_THRESHOLD)
                   for sid in members)
    return 100.0 * avoiding / (callers * len(members))
