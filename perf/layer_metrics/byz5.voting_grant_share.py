"""MultiGrants that voted over MultiGrants received, in percent: the SDK's
``client.grants-voting`` (in the timestamp-consistent subset a certificate is
cut from) over ``client.grants-received`` (every Write1 answer that carried
one, refusals too), gained over the window and summed over the callers.  With 5
of 16 members lying and every answer in before the eleventh honest grant it
reads 11 / 16 = 68.75; contention (refusals, rounds that found no subset) pulls
it down, an early quorum that leaves liars' answers behind pushes it up.  A run
whose SDK keeps no grant counters (the parent's) gives nothing."""

NAME = "byz5.voting_grant_share"
UNIT = "%"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    gained = (snap["generator"].get("sdk_counters") or {}).get("sum") or {}
    received = gained.get("client.grants-received", 0)
    return 100.0 * gained.get("client.grants-voting", 0) / received if received else None
