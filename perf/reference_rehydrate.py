"""The plain reference of a re-hydration (the paper's UptoSpeed): what a correct
pull from its peers leaves on a replica that came back EMPTY.

Shares no code with ``mochi_tpu`` (as ``perf/reference.py`` shares none): a
store is a plain ``{key: (timestamp, value bytes, grant count)}``, the ring is
a function from a key to the ids that own it.  On the chip ``correct`` is
decided by ``perf/reference.py`` (``check_recovery``, ``check_direct``: the
harness sees a replica's store only through its answers); this reference is
what ``tests/test_rehydrate.py`` holds ``MochiReplica.resync`` to, key for key,
where the stores themselves are in reach.
"""

from __future__ import annotations


def rehydrated(peers: dict, owners, me: str, quorum: int) -> dict:
    """``peers``: {peer id: its store}; ``owners(key)``: the ids that own
    ``key``.  For every key ``me`` owns, the entry of the highest timestamp
    that any peer holds under a certificate of at least ``quorum`` grants, as
    ``{key: (timestamp, value bytes)}``; nothing for a key it does not own,
    and nothing from a certificate under quorum (a Write2 with one is refused,
    so a pulled entry with one is too)."""
    out: dict = {}
    for peer, store in peers.items():
        if peer == me:
            continue
        for key, (timestamp, value, grants) in store.items():
            if grants < quorum or me not in owners(key):
                continue
            if key not in out or timestamp > out[key][0]:
                out[key] = (timestamp, bytes(value))
    return out


def differences(want: dict, got: dict) -> dict:
    """``got``: the re-hydrated replica's own store as ``{key: (timestamp,
    value bytes)}``, against ``want`` (``rehydrated``).  Counts, each 0 where
    the re-hydration is correct: keys it should hold and does not, keys it
    holds and should not (not its own, or held by no peer), keys it holds at
    an older timestamp, and keys whose bytes are not that timestamp's."""
    missing = sum(1 for key in want if key not in got)
    extra = sum(1 for key in got if key not in want)
    older = sum(1 for key, (ts, _) in want.items() if key in got and got[key][0] < ts)
    # a replica that serves during its re-hydration may hold something NEWER
    # than any peer did when the stores were read: only equal stamps compare bytes
    other_bytes = sum(1 for key, (ts, value) in want.items()
                      if key in got and got[key][0] == ts and bytes(got[key][1]) != value)
    return {"missing": missing, "extra": extra, "older": older, "other_bytes": other_bytes}
