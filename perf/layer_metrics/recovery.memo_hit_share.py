"""Share of the items the service was asked to verify between the restart
command and READY that its memo answered (hits / (hits + misses)).  The
foreground's RPCs are in it beside the replay's: its updates are new
signatures (misses), so the share understates the replay's own."""

import schedule

NAME = "recovery.memo_hit_share"
UNIT = "%"
LAYER = "verifier SPI and service queue"
MOVES = "recover_s"
SOURCE = "program_counter"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back:
        return None
    hits, misses = schedule.service_gain(back, "memo_hits"), schedule.service_gain(back, "memo_misses")
    return 100.0 * hits / (hits + misses) if hits + misses > 0 else None
