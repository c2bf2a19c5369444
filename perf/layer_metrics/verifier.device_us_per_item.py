"""Microseconds per signature on the device side of the crossover: seconds of
the backend calls routed to the device (``mochi.verifier.flush`` spans with
``route`` device: prepare, dispatch, device time and readback) over their items,
over both of the run's traces.  The probe's flushes are counted with the
window's, so a cell whose traffic never crosses to the device still reads the
cost it would pay."""

import hostspans

NAME = "verifier.device_us_per_item"
UNIT = "us"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    rows = hostspans.route_rows(hostspans.of(snap), "device")
    items = sum(r["items"] for r in rows)
    return 1e6 * sum(r["seconds"] for r in rows) / items if items else None
