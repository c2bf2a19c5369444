"""CPU milliseconds all replica processes spent per operation answered in the
window (``ProcessCluster.cpu_seconds()``, utime + stime from /proc)."""

NAME = "replica.cpu_ms_per_op"
UNIT = "ms"
LAYER = "replica dispatch and auth"
MOVES = "ops_s"
SOURCE = "host_clock"


def read(snap):
    if not snap["ops_ok"]:
        return None
    return 1e3 * (snap["after"]["replica_cpu"] - snap["before"]["replica_cpu"]) / snap["ops_ok"]
