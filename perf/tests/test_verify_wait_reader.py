"""PR 28's reader, ``recovery.verify_wait_ms``, on the canned kill and restart
that the other recovery readers are tested on (``canned_faults.py``): a value
where the restarted replica's ``storage.replay`` holds the key, nothing where
it does not (the parent commit's replica keeps no such counter)."""

import pytest

import canned_faults as canned
import test_span_readers as base

NAME = "recovery.verify_wait_ms"


def snapshot(**replay):
    """The canned restart, its replay's record extended by ``replay``."""
    faults = canned.records()
    faults[1]["after"]["replica"]["storage"]["replay"].update(replay)
    return dict(base.SNAP, faults=faults, cluster={"quorum": 3})


@pytest.mark.parametrize("cell", base.RECOVERY_CELLS)
@pytest.mark.parametrize("replay,expect", [
    # 9 requests, 6.9 s from issue to verdict, 0.41 s of it with nothing left to apply
    ({"verify_calls": 9, "verify_rtt_ms": 6900.0, "verify_wait_ms": 412.5}, 412.5),
    ({"verify_calls": 30, "verify_rtt_ms": 0.0, "verify_wait_ms": 0.0}, 0.0),  # hidden whole
    ({}, None),                                      # the parent's replica: no such key
    ({"verify_calls": 9, "verify_rtt_ms": 6900.0}, None),
])
def test_verify_wait_is_the_restarted_replicas_own_counter(cell, replay, expect):
    got = base.read(cell, snapshot(**replay))
    assert got.get(NAME) == expect
    # and the reader beside it reads what it read before
    assert got["recovery.replay_ms"] == 3200.0


@pytest.mark.parametrize("cell", base.RECOVERY_CELLS)
def test_verify_wait_says_nothing_where_nothing_was_restarted(cell):
    snap = snapshot(verify_wait_ms=412.5)
    assert NAME not in base.read(cell, dict(snap, faults=snap["faults"][:1]))  # killed, not back
    assert NAME not in base.read(cell, dict(snap, faults=[]))
    assert NAME not in base.read(cell, dict(base.SNAP))  # a cell without a schedule


def test_verify_wait_is_keyed_to_the_two_recovery_cells():
    entry = next(m for m in base.run.load_cell(base.REPO, "rf4-recover")["bench"]["per_layer"]
                 if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "ms", "better": "lower", "source": "program_span",
                     "layer": "store and storage", "moves": "recover_s",
                     "workloads": base.RECOVERY_CELLS}
