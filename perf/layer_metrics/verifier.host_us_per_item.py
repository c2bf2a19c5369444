"""Microseconds per signature on the host side of the 384-item crossover:
seconds of the backend calls routed to the host engine (``mochi.verifier.flush``
spans with ``route`` host) over their items, in the window trace."""

import hostspans

NAME = "verifier.host_us_per_item"
UNIT = "us"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    rows = hostspans.route_rows(hostspans.of(snap), "host", kinds=("window",))
    items = sum(r["items"] for r in rows)
    return 1e6 * sum(r["seconds"] for r in rows) / items if items else None
