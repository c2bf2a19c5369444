"""Device milliseconds per launch of the comb's program, by the name the
product pins for it (``comb.COMB_PROGRAM``), in the probe trace: the probe
sends one fixed size, the first device-routed ready bucket."""

import hostspans

NAME = "kernel.comb_ms"
UNIT = "ms"
LAYER = "device programs"
MOVES = "ops_s"
SOURCE = "device_trace"


def read(snap):
    return hostspans.program_ms_per_launch(hostspans.of(snap), "probe", hostspans.COMB_PROGRAM)
