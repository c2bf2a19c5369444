"""Mean frames a replica's transport took per drain of a connection, over the
replicas and the window (``/status`` ``batching`` ``transport.drain-frames``)."""

NAME = "transport.frames_per_drain"
UNIT = "frames"
LAYER = "transport and codec"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    a, b = snap["before"]["replicas"], snap["after"]["replicas"]
    drains = b["drain_count"] - a["drain_count"]
    return (b["drain_frames"] - a["drain_frames"]) / drains if drains > 0 else None
