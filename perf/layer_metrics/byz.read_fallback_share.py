"""Reads that went out a second time: a read first asks a quorum of its
key's replica set and, where those answers do not agree (one of them a lie, a
laggard or late), asks the whole set (``client.py`` ``_read_with_recovery``);
each round runs the SDK's ``read-transactions`` timer once, and the generator
counts its runs as ``calls.read-transactions``.  The runs beyond one a read,
over the window's answered reads, in percent; an attempt that the caller made
again (``OP_ATTEMPTS``) counts as a round too.  A run without the SDK's
counters, or a window without reads, gives nothing."""

NAME = "byz.read_fallback_share"
UNIT = "%"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    gained = (snap["generator"].get("sdk_counters") or {}).get("sum") or {}
    reads = snap["ops_ok"] - snap["updates_ok"]
    if "calls.read-transactions" not in gained or reads <= 0:
        return None
    return 100.0 * (gained["calls.read-transactions"] - reads) / reads
