"""Attempts beyond the first, for each update acknowledged: how often the callers
ran the SDK's ``write-transactions`` timer (the generator counts its runs as
``calls.write-transactions``; an update a caller sent again, ``OP_ATTEMPTS``,
runs it again) less the updates acknowledged, over the updates acknowledged.
The price of 11 of 11 under contention: two writers of one record split the
eleven honest replicas and both go round again.  A failed update's six attempts
all count as beyond.  (The generator's own ``retried`` tally names what each
such attempt raised; the snapshot does not carry it.) A run without the SDK's
counters, or a window without updates, gives nothing."""

NAME = "byz5.attempts_again_per_update"
UNIT = "count"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    gained = (snap["generator"].get("sdk_counters") or {}).get("sum") or {}
    calls = gained.get("calls.write-transactions")
    if calls is None or not snap["updates_ok"]:
        return None
    return (calls - snap["updates_ok"]) / snap["updates_ok"]
