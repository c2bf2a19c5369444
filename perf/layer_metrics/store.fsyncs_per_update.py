"""fsyncs of all replicas' storage engines per update acknowledged in the
window (``/status`` ``storage.fsyncs``): group commit's amortisation."""

NAME = "store.fsyncs_per_update"
UNIT = "count"
LAYER = "store and storage"
MOVES = "update_p95_ms"
SOURCE = "program_counter"


def read(snap):
    if not snap["updates_ok"]:
        return None
    a, b = snap["before"]["replicas"], snap["after"]["replicas"]
    return (b["fsyncs"] - a["fsyncs"]) / snap["updates_ok"]
