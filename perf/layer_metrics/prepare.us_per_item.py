"""Host packing per signature sent to the device: seconds under
``mochi.verifier.prepare`` spans (``batch_verify._prepare_padded`` and
``comb._prepare_comb``: SHA-512, mod L, limbs, padding) over the items of the
device-routed flushes, over both of the run's traces."""

import hostspans

NAME = "prepare.us_per_item"
UNIT = "us"
LAYER = "device programs"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    reduced = hostspans.of(snap)
    items = sum(r["items"] for r in hostspans.route_rows(reduced, "device"))
    rows = [hostspans.span_row(reduced, k, "mochi.verifier.prepare") for k in hostspans.KINDS]
    return 1e6 * sum(r["seconds"] for r in rows if r) / items if items and any(rows) else None
