"""What the stated Byzantine members did, by their own count, for each
operation the cluster answered: responses they changed and signed again plus
requests they swallowed (``testing/byzantine.py`` ``ByzantineReplica``'s two
counters, as ``cluster.byzantine_report`` finds them), gained over the window
and summed over the members, over the window's answered operations.  A cell
that states no member, or a member that reports no count, gives nothing."""

from reference import acts_gained

NAME = "byz.lies_per_op"
UNIT = "count"
LAYER = "replica dispatch and auth"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    members = (snap.get("cluster") or {}).get("byzantine") or {}
    gained = [acts_gained(snap["before"]["replicas"], snap["after"]["replicas"], sid) for sid in members]
    if not members or None in gained or not snap["ops_ok"]:
        return None
    return sum(gained) / snap["ops_ok"]
