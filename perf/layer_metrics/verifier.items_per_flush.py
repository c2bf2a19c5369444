"""Signatures per flushed batch of the service's batcher over the window:
what the 2 ms linger gathers from the whole cluster."""

NAME = "verifier.items_per_flush"
UNIT = "items"
LAYER = "verifier SPI and service queue"
MOVES = "update_p95_ms"
SOURCE = "program_counter"


def read(snap):
    a, b = snap["before"]["service"], snap["after"]["service"]
    flushed = b["batches_flushed"] - a["batches_flushed"]
    items = (b["device_items"] + b["host_routed_items"]) - (a["device_items"] + a["host_routed_items"])
    return items / flushed if flushed > 0 else None
