"""Share of the traced end of the window that the service spent in Python's
collector: seconds under ``mochi.gc`` spans (``VerifierService._on_gc``, a
``gc.callbacks`` pair) over the window.  A trace with spans and no collection
reads 0."""

import hostspans

NAME = "service.gc_pause_share"
UNIT = "%"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    window = hostspans.of(snap).get("window")
    if not window or not window["spans"] or window["window_s"] <= 0:
        return None
    gc = window["spans"].get("mochi.gc")
    return 100.0 * (gc["seconds"] if gc else 0.0) / window["window_s"]
