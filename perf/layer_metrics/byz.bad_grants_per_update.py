"""Grants of the stated Byzantine members that the callers' own check threw
out (``suspect.bad-grant.<member>``: a signature that does not verify, or a
transaction hash that is not the transaction's; ``client.py`` ``_grant_ok``),
gained over the window and summed over callers and members, for each update
acknowledged.  A member forging every Write1 answer reads the share of the
keys whose replica set holds it (four sets of five at rf=4 of n=5: ~0.8).  A
cell that states no member, or a run without the SDK's counters, gives
nothing."""

NAME = "byz.bad_grants_per_update"
UNIT = "count"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    members = (snap.get("cluster") or {}).get("byzantine") or {}
    gained = (snap["generator"].get("sdk_counters") or {}).get("sum")
    if not members or gained is None or not snap["updates_ok"]:
        return None
    return sum(gained.get(f"suspect.bad-grant.{sid}", 0) for sid in members) / snap["updates_ok"]
