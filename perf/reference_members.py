"""The plain reference of a deployment with several Byzantine members of
several kinds: what a run of ``n16-byz5-ycsb-a`` must add up to.

It shares no code with ``mochi_tpu`` (nor with ``perf/reference.py``, which
decides ``correct``: it holds a multi-member map already).  Pure functions over
what a run records, for the ``byz5.*`` readers and the CPU tests:

* ``caught_by_own_kind``: per stated member, the marks the callers gained
  against it of the kinds that ITS strategy produces.  A member that forges is
  caught by the grant check; a member that replays validly signed old state is
  not, and is caught by the timestamp subset and the read tally.  A mark of
  another kind says another thing: ``tally-outvoted`` against a forger, or any
  mark an honest replica earns too, does not show that the defence against
  THIS lie works.
* ``arithmetic``: what the deployment stands on.  With ``members`` liars of
  ``rf`` replicas at quorum 2f+1, the honest members must still make a quorum;
  when ``members = f`` they make it exactly, so an acknowledged update's
  certificate is cut from all of them and no slack is left for a slow one.
* ``grant_identity``: the SDK's grant counters add up, exactly.
* ``StoreModel``: a dictionary that replays a seeded list of reads and updates
  and gives the answer every read must return.
"""

from __future__ import annotations

# the kinds of mark (``suspect.<kind>.<server id>``, the SDK's counters) that a
# strategy's own lies produce at a caller
OWN_KINDS = {
    "forge-cert": ("bad-grant",),
    "stale-replay": ("grant-conflict", "tally-outvoted"),
}

GRANT_COUNTERS = ("received", "voting", "dropped-signature", "dropped-timestamp",
                  "refused", "unused")


def caught_by_own_kind(stated: dict, sdk_gained: dict) -> dict:
    """{member: marks of its own kinds}.  ``stated``: {server id: strategy};
    ``sdk_gained``: {counter: what it gained}, summed over the callers.  A
    strategy without a row is caught by a mark of any kind."""
    out = dict.fromkeys(stated, 0)
    for name, gained in sdk_gained.items():
        parts = name.split(".", 2)
        if len(parts) != 3 or parts[0] != "suspect" or parts[2] not in stated:
            continue
        kind, sid = parts[1], parts[2]
        if kind in OWN_KINDS.get(stated[sid], (kind,)):
            out[sid] += gained
    return out


def arithmetic(rf: int, f: int, quorum: int, members: int) -> dict:
    """The numbers the deployment stands on, from the shape alone.

    ``honest``: replicas of a set that do not lie; ``holds``: the fault model
    covers the members stated (no more than f) and the honest ones still make
    a quorum; ``slack``: honest members an update can do without (0 when
    members = f: eleven of eleven); ``voting_share``: the MultiGrants in the
    subset of an update that found every honest member agreed (all of them,
    ``honest``) over the ``rf`` it received, in percent."""
    honest = rf - members
    return {
        "honest": honest,
        "holds": quorum == 2 * f + 1 and rf >= 3 * f + 1 and members <= f and honest >= quorum,
        "slack": honest - quorum,
        "voting_share": 100.0 * honest / rf,
    }


def grant_counts(sdk_gained: dict) -> dict:
    """The SDK's six grant counters (``client.grants-<what>``) out of its
    counters, 0 where one never moved."""
    return {what: sdk_gained.get(f"client.grants-{what}", 0) for what in GRANT_COUNTERS}


def grant_identity(sdk_gained: dict) -> int:
    """Received less everything a received MultiGrant can become: 0 where the
    counters add up."""
    c = grant_counts(sdk_gained)
    return c["received"] - (c["voting"] + c["dropped-signature"] + c["dropped-timestamp"]
                            + c["refused"] + c["unused"])


class StoreModel:
    """One value a key; a read returns the last value written, None before
    any.  ``replay`` takes a list of ``("update", key, value)`` and ``("read",
    key)`` in the order they were acknowledged, one at a time, and returns what
    each read must have returned, in order."""

    def __init__(self):
        self.values: dict = {}

    def update(self, key, value) -> None:
        self.values[key] = value

    def read(self, key):
        return self.values.get(key)

    def replay(self, operations) -> list:
        answers = []
        for op in operations:
            if op[0] == "update":
                self.update(op[1], op[2])
            else:
                answers.append(self.read(op[1]))
        return answers
