"""How busy the load generator's own processes were: their CPU seconds over
the window times the number of processes.  Near 100% the generator, not the
system, sets ``ops_s``."""

NAME = "gen.busy_share"
UNIT = "%"
LAYER = "load generator"
MOVES = "ops_s"
SOURCE = "host_clock"


def read(snap):
    gen = snap["generator"]
    if not gen["processes"]:
        return None
    return 100.0 * gen["cpu_seconds"] / (snap["window_s"] * gen["processes"])
