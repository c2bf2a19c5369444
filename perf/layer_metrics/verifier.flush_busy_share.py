"""Seconds inside backend calls (``mochi.verifier.flush`` spans,
``JaxBatchBackend.__call__``, either route) over the traced end of the window.
Up to four calls run at once, so this is a share of one thread, not of the
window; it is what the flusher's threads cost beside the loop."""

import hostspans

NAME = "verifier.flush_busy_share"
UNIT = "%"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    window = hostspans.of(snap).get("window")
    if not window or not window["spans"] or window["window_s"] <= 0:
        return None
    return 100.0 * sum(r["seconds"] for r in window["routes"].values()) / window["window_s"]
