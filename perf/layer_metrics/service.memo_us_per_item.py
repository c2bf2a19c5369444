"""Microseconds of the loop thread per item offered to the memo: seconds under
``mochi.verifier.memo`` spans (``CachingVerifier.verify_batch`` and
``verify_aggregate``: key build and lookup, before the first await) over the
items those spans carried, in the window trace."""

import hostspans

NAME = "service.memo_us_per_item"
UNIT = "us"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    row = hostspans.span_row(hostspans.of(snap), "window", "mochi.verifier.memo")
    if not row or not row["sums"].get("items"):
        return None
    return 1e6 * row["seconds"] / row["sums"]["items"]
