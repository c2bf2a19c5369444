"""``verifier.device_item_share`` for a cell that runs at capacity and so reports no update tail
(its tails swing from run to run): there the same quantity moves the rate."""

from layer_reader import load

NAME = "verifier.device_item_share.ops"
UNIT = "%"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    return load(__file__[:-len(".ops.py")] + ".py").read(snap)
