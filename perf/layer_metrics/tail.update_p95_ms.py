"""95th percentile of update latency over all updates issued in the window (a
failed one counts as infinite).  In a cell that runs at capacity this tail
swings by a third from run to run, so there it is a per-layer reading and not
an end-to-end metric; cells below capacity report it end to end instead."""

NAME = "tail.update_p95_ms"
UNIT = "ms"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "host_clock"


def read(snap):
    return snap["latency"].get("update_p95_ms")
