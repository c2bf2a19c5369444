"""The plain reference of the runbook's restart (``--data-dir`` +
``--resync-on-boot``): what ONE correct resync pass leaves on a replica that
came back on its OWN disk, behind its peers by whatever committed while it was
away.

Shares no code with ``mochi_tpu`` (as ``perf/reference.py`` shares none); it
calls ``perf/reference_rehydrate.py`` for what the peers alone would give.  A
store is a plain ``{key: (timestamp, value bytes, grant count)}``, the ring is
a function from a key to the ids that own it.  The peers' stores are taken as
they stood when the pass began (the report's ``began_epoch_us``): the pass is
bounded by that instant, and what committed later is not asked of it.  On the
chip ``correct`` is decided by ``perf/reference.py`` (``check_recovery``,
``check_direct``); this reference is what ``tests/test_resync_boot.py`` holds
``MochiReplica.resync`` to, key for key, where the stores are in reach.
"""

from __future__ import annotations

import os

import layer_reader

_rehydrate = layer_reader.load(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "reference_rehydrate.py"), "perf_")
differences = _rehydrate.differences


def resynced(own: dict, peers: dict, owners, me: str, quorum: int) -> dict:
    """``own``: the restarted replica's store after its replay; ``peers``:
    {peer id: its store when the pass began}.  For every key ``me`` owns, the
    entry of the highest timestamp that it or any peer holds under a
    certificate of at least ``quorum`` grants, as ``{key: (timestamp, value
    bytes)}``; nothing for a key it does not own."""
    out = _rehydrate.rehydrated(peers, owners, me, quorum)
    for key, (timestamp, value, grants) in own.items():
        if grants < quorum or me not in owners(key):
            continue
        if key not in out or timestamp > out[key][0]:
            out[key] = (timestamp, bytes(value))
    return out


def behind(own: dict, want: dict) -> set:
    """The keys of ``want`` (``resynced``) that ``own`` lacks or holds at an
    older timestamp: what a restart WITHOUT the pass is behind on."""
    return {key for key, (timestamp, _) in want.items()
            if key not in own or own[key][0] < timestamp}
