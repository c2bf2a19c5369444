"""Median read latency over all reads issued in the window.  It tells which of
the two levels a run of the 10,000-record rf=4 cells ran at (PERF.md section 7,
first): 3.5-7.5 ms at the fast one, 7.5-15 ms at the slow one, with `ops_s`
14-18% apart; on a ledger line it shows which level each side of a pair read
at.  A reading and no end-to-end metric: a bound on it would bound the state,
not the program."""

NAME = "tail.read_p50_ms"
UNIT = "ms"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "host_clock"


def read(snap):
    return snap["latency"].get("read_p50_ms")
