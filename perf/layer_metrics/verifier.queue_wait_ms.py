"""Mean wait of a flushed chunk in the service's batcher, its oldest item
enqueued to its backend call started (the 2 ms linger, the in-flight semaphore,
the executor hand-off): ``wait_us`` of the ``mochi.verifier.chunk`` spans
(``BatchingVerifier._flush_chunk``) in the window trace."""

import hostspans

NAME = "verifier.queue_wait_ms"
UNIT = "ms"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    row = hostspans.span_row(hostspans.of(snap), "window", "mochi.verifier.chunk")
    if not row or not row["count"]:
        return None
    return row["sums"].get("wait_us", 0) / 1e3 / row["count"]
