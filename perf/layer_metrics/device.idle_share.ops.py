"""``device.idle_share`` for a cell that runs at capacity and so reports no update tail
(its tails swing from run to run): there the same quantity moves the rate."""

from layer_reader import load

NAME = "device.idle_share.ops"
UNIT = "%"
LAYER = "device programs"
MOVES = "ops_s"
SOURCE = "device_trace"


def read(snap):
    return load(__file__[:-len(".ops.py")] + ".py").read(snap)
