"""Share of the traced end of the window in which the service's event-loop
thread was on a CPU: the thread's CPU seconds (``time.thread_time()``, carried
by the ``mochi.service.tick`` span that ``VerifierService._tick`` leaves once
a second) between the first and the last tick in the window trace, over the
time between them.  Near 100%, the loop is the serial stage."""

import hostspans

NAME = "service.loop_cpu_share"
UNIT = "%"
LAYER = "verifier SPI and service queue"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    ticks = (hostspans.of(snap).get("window") or {}).get("ticks") or []
    if len(ticks) < 2 or ticks[-1][0] <= ticks[0][0]:
        return None
    return 100.0 * (ticks[-1][1] - ticks[0][1]) * 1e3 / (ticks[-1][0] - ticks[0][0])
