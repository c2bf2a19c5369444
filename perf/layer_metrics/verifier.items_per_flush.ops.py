"""``verifier.items_per_flush`` for a cell that runs at capacity and so reports no update tail
(its tails swing from run to run): there the same quantity moves the rate."""

from layer_reader import load

NAME = "verifier.items_per_flush.ops"
UNIT = "items"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    return load(__file__[:-len(".ops.py")] + ".py").read(snap)
