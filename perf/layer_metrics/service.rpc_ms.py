"""Mean time of a verify RPC inside the service, envelope in hand to sealed
reply: over the ``mochi.service.rpc.reply`` spans of the window trace
(``VerifierService._handle``), each span's ``wait_us`` (the head and the
awaited verify, which no span may cross) plus the span's own length."""

import hostspans

NAME = "service.rpc_ms"
UNIT = "ms"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    row = hostspans.span_row(hostspans.of(snap), "window", "mochi.service.rpc.reply")
    if not row or not row["count"]:
        return None
    return (row["sums"].get("wait_us", 0) / 1e3 + row["seconds"] * 1e3) / row["count"]
