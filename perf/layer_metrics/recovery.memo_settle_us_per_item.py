"""Microseconds of the service's loop thread per signature the memo had to
have verified, between the restart command and READY: what the service's
``service.memo-settle`` timer (``CachingVerifier._settle``'s synchronous stretch
after the inner verifier answers: verdicts written, memo inserted, the oldest
entries evicted, single-flight released) gained over what ``memo_misses``
gained, both from the harness's looks at the service just before and just after
the event.  The foreground's calls are in both beside the replay's.  A service
without the timer (before PR 30) gives nothing."""

import schedule

NAME = "recovery.memo_settle_us_per_item"
UNIT = "us"
LAYER = "verifier SPI and service queue"
MOVES = "recover_s"
SOURCE = "program_span"

TIMER = "service.memo-settle"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back or not all(r[look].get("service_stages") for r in back for look in ("before", "after")):
        return None
    if any(TIMER not in r["after"]["service_stages"]["timers"] for r in back):
        return None
    misses = schedule.service_gain(back, "memo_misses")
    if misses <= 0:
        return None

    def settled_ms(look):  # a timer that never ticked is not listed
        return look["service_stages"]["timers"].get(TIMER, {}).get("sum_ms", 0.0)

    return 1e3 * sum(settled_ms(r["after"]) - settled_ms(r["before"]) for r in back) / misses
