"""Mean time from a flushed chunk's backend call returning on the executor
thread to its calls being resolved on the service's loop (the hand-over to
the loop thread and the rest of the turn in progress there): over the
``mochi.verifier.resolve`` spans of the window trace
(``BatchingVerifier._resolve``), each span's ``wait_us`` (the return to the
loop having the chunk's slices written) plus the span's own length (the
resolution).  A service without the span (before PR 25) reports nothing."""

import hostspans

NAME = "verifier.resolve_wait_ms"
UNIT = "ms"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    row = hostspans.span_row(hostspans.of(snap), "window", "mochi.verifier.resolve")
    if not row or not row["count"]:
        return None
    return (row["sums"].get("wait_us", 0) / 1e3 + row["seconds"] * 1e3) / row["count"]
