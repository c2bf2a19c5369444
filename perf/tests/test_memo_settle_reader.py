"""PR 30's reader, ``recovery.memo_settle_us_per_item``, on the canned kill and
restart that the other recovery readers are tested on (``canned_faults.py``):
what the service's ``service.memo-settle`` timer gained between the looks
before the restart command and after READY, over what ``memo_misses`` gained;
nothing where the service keeps no such timer (the parent commit's)."""

import pytest

import canned_faults as canned
import test_span_readers as base

NAME = "recovery.memo_settle_us_per_item"
TIMER = "service.memo-settle"
MISSES = canned.SERVICE1["memo_misses"] - canned.SERVICE0["memo_misses"]  # 4,000


def stages(**timers):
    """A service's ``stages`` as ``/status`` gives them, with the lookup's timer beside ``timers``."""
    return {"counters": {}, "histograms": {}, "timers": {
        "service.memo-lookup": {"count": 900, "sum_ms": 310.0},
        **{name: {"count": int(ms), "sum_ms": ms} for name, ms in timers.items()}}}


def snapshot(before, after):
    """The canned restart, the service's stage timers in its two looks."""
    faults = canned.records()
    for rec in faults:
        rec["before"]["service_stages"] = rec["after"]["service_stages"] = before
    faults[1]["after"]["service_stages"] = after
    return dict(base.SNAP, faults=faults, cluster={"quorum": 3})


@pytest.mark.parametrize("cell", base.RECOVERY_CELLS)
@pytest.mark.parametrize("before,after,expect", [
    # 4,000 misses settled in 8.8 ms of the loop: 2.2 us each
    (stages(**{TIMER: 150.0}), stages(**{TIMER: 158.8}), pytest.approx(1e3 * 8.8 / MISSES)),
    # a memo that filled before the restart and walks its dead slots: 33 us each
    (stages(**{TIMER: 4200.0}), stages(**{TIMER: 4332.0}), pytest.approx(1e3 * 132.0 / MISSES)),
    # the timer's first tick fell after the command: it was not listed before
    (stages(), stages(**{TIMER: 6.0}), pytest.approx(1e3 * 6.0 / MISSES)),
    # the parent's service: no such timer in either look
    (stages(), stages(), None),
    # a harness that kept no stages in its looks (before PR 27)
    (None, None, None),
])
def test_memo_settle_is_the_timers_gain_over_the_misses_gained(cell, before, after, expect):
    got = base.read(cell, snapshot(before, after))
    assert got.get(NAME) == expect
    # and the readers beside it read what they read before
    assert got["recovery.memo_hit_share"] == pytest.approx(90.0) and got["recovery.replay_ms"] == 3200.0


@pytest.mark.parametrize("cell", base.RECOVERY_CELLS)
def test_memo_settle_says_nothing_where_nothing_was_restarted_or_nothing_missed(cell):
    snap = snapshot(stages(**{TIMER: 150.0}), stages(**{TIMER: 158.8}))
    assert NAME not in base.read(cell, dict(snap, faults=snap["faults"][:1]))  # killed, not back
    assert NAME not in base.read(cell, dict(snap, faults=[]))
    assert NAME not in base.read(cell, dict(base.SNAP))  # a cell without a schedule
    quiet = snapshot(stages(**{TIMER: 150.0}), stages(**{TIMER: 150.0}))
    quiet["faults"][1]["after"]["service"] = dict(canned.SERVICE1, memo_misses=canned.SERVICE0["memo_misses"])
    assert NAME not in base.read(cell, quiet)  # the memo answered everything: no item to divide by


def test_memo_settle_is_keyed_to_the_two_recovery_cells():
    bench = base.run.load_cell(base.REPO, "rf4-recover")["bench"]
    # found by name: later PRs append their own entries
    assert next(m for m in bench["per_layer"] if m["name"] == NAME) == {
        "name": NAME, "unit": "us", "better": "lower", "source": "program_span",
        "layer": "verifier SPI and service queue", "moves": "recover_s", "workloads": base.RECOVERY_CELLS}
