"""Share of the window's verified signatures that the service's routing sent
to the device and not to the host (the crossover, ``min_device_items``)."""

NAME = "verifier.device_item_share"
UNIT = "%"
LAYER = "verifier SPI and service queue"
MOVES = "update_p95_ms"
SOURCE = "program_counter"


def read(snap):
    a, b = snap["before"]["service"], snap["after"]["service"]
    device = b["device_items"] - a["device_items"]
    host = b["host_routed_items"] - a["host_routed_items"]
    return 100.0 * device / (device + host) if device + host > 0 else None
