"""95th percentile of read latency over all reads issued in the window (a
failed one counts as infinite).  A per-layer reading and no end-to-end metric:
at capacity (n=64) it swings by a third from run to run, and at rf=4, a third
of the update tail, it follows the host's state (a set of runs on a quiet host
spreads by 2%, the check's sets by 8%), so no one bound fits it."""

NAME = "tail.read_p95_ms"
UNIT = "ms"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "host_clock"


def read(snap):
    return snap["latency"].get("read_p95_ms")
