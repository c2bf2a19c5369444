"""``client.write1_p50_ms`` for a cell that runs at capacity and so reports no update tail
(its tails swing from run to run): there the same quantity moves the rate."""

from layer_reader import load

NAME = "client.write1_p50_ms.ops"
UNIT = "ms"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    return load(__file__[:-len(".ops.py")] + ".py").read(snap)
