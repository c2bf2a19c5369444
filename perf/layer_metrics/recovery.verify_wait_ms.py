"""How long the restarted replica's replay was blocked on the verifier with
nothing left to apply: its ``/status`` ``storage.replay.verify_wait_ms`` at
READY (``storage/durable.py`` ``_ReplayPipeline``: the replay asks for the next
chunks' verdicts while it applies this one, so this is the part of
``verify_rtt_ms``, the requests' summed issue-to-verdict time beside it, that
decode and apply did NOT hide), summed over the schedule's restarts.  A replica
whose replay keeps no such counter gives nothing."""

import schedule

NAME = "recovery.verify_wait_ms"
UNIT = "ms"
LAYER = "store and storage"
MOVES = "recover_s"
SOURCE = "program_span"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back:
        return None
    ms = [r["after"]["replica"]["storage"].get("replay", {}).get("verify_wait_ms") for r in back]
    return float(sum(ms)) if all(m is not None for m in ms) else None
