"""The reference's arithmetic and its history check, on hand-made histories."""

import math
import zlib

import pytest

import reference as ref

LOAD = 0xFFFFFFFF


def crc(writer, seq):
    return zlib.crc32(b"%d:%d" % (writer, seq))


def update(rec, t0, t1, writer, seq, ok=1):
    return [ref.UPDATE, rec, t0, t1, ok, writer, seq, crc(writer, seq), 0]


def read(rec, t0, t1, writer, seq, grants=3, ok=1, bad_bytes=False):
    return [ref.READ, rec, t0, t1, ok, writer, seq, crc(writer, seq) ^ bad_bytes, grants]


def verdicts(ops, quorum=3):
    hist = ref.build_histories(ops, LOAD, crc)
    return {c.name: c for c in ref.check_window(ops, hist, quorum)}


@pytest.mark.parametrize("values,q,expect", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 50, 5),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 95, 10),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90, 9),
    (list(range(1, 101)), 95, 95),
    (list(range(1, 101)), 99, 99),
    ([7], 95, 7),
    ([3, 1, 2], 0, 1),
])
def test_percentile_is_nearest_rank(values, q, expect):
    assert ref.percentile(values, q) == expect


def test_percentile_of_nothing_and_of_failures():
    assert math.isnan(ref.percentile([], 95))
    # one failure in twenty is the 95th percentile's neighbour, two are over it
    assert ref.percentile([1.0] * 19 + [math.inf], 95) == 1.0
    assert ref.percentile([1.0] * 18 + [math.inf] * 2, 95) == math.inf


GOOD = [
    update(1, 10.0, 10.5, 0, 0),
    read(1, 9.0, 9.1, LOAD, 1),          # before any update: the load's record
    read(1, 10.2, 10.3, LOAD, 1),        # concurrent with the update: either
    read(1, 10.2, 10.4, 0, 0),
    read(1, 11.0, 11.1, 0, 0),           # after the ack: the update
    update(1, 12.0, 12.5, 1, 0),
    update(1, 12.1, 12.6, 0, 1),         # concurrent updates: either may win
    read(1, 13.0, 13.1, 1, 0),
    read(1, 13.0, 13.1, 0, 1),
    update(2, 5.0, 6.0, 0, 2, ok=0),     # outcome unknown
    read(2, 7.0, 7.1, 0, 2),             # ... may be read
    read(2, 7.0, 7.1, LOAD, 2),          # ... or not
]


def test_a_good_history_passes():
    assert all(c.ok for c in verdicts(GOOD).values())


@pytest.mark.parametrize("bad_op,failed", [
    (read(1, 11.0, 11.1, LOAD, 1), "window_stale_reads"),       # acked update lost
    (read(1, 13.0, 13.1, 0, 0), "window_stale_reads"),          # superseded twice over
    (read(1, 11.0, 11.1, 0, 5), "window_reads_of_no_known_write"),   # nobody wrote that
    (read(1, 11.0, 11.1, 0, 0, bad_bytes=True), "window_reads_of_no_known_write"),
    (read(2, 11.0, 11.1, 0, 0), "window_reads_of_no_known_write"),   # another key's record
    (read(1, 11.0, 11.1, 0, 0, grants=2), "window_reads_under_quorum_grants"),
])
def test_a_bad_read_fails_its_check_and_only_it(bad_op, failed):
    got = verdicts(GOOD + [bad_op])
    assert {name for name, c in got.items() if not c.ok} == {failed}
    assert got[failed].value == 1


def test_failed_reads_are_not_judged():
    assert all(c.ok for c in verdicts(GOOD + [read(1, 11.0, 11.1, -1, -1, ok=0, grants=0)]).values())


def test_readback_rules():
    hist = ref.build_histories(GOOD, LOAD, crc)

    def row(writer, seq, grants=3):
        return (writer, seq, crc(writer, seq), grants, 20.0)

    good = {1: row(0, 1), 2: row(LOAD, 2)}
    assert all(c.ok for c in ref.check_readback(good, hist, 3))
    also_good = {1: row(1, 0), 2: row(0, 2, grants=4)}
    assert all(c.ok for c in ref.check_readback(also_good, hist, 3))
    got = {c.name: c for c in ref.check_readback({1: row(0, 0), 2: good[2]}, hist, 3)}
    assert not got["readback_not_newest_acknowledged"].ok      # superseded
    got = {c.name: c for c in ref.check_readback({1: good[1]}, hist, 3)}
    assert got["readback_missing"].value == 1 and not got["readback_missing"].ok
    got = {c.name: c for c in ref.check_readback({1: row(0, 1, grants=2), 2: good[2]}, hist, 3)}
    assert not got["readback_under_quorum_grants"].ok
    got = {c.name: c for c in ref.check_readback({1: (-1, -1, 12345, 3, 20.0), 2: good[2]}, hist, 3)}
    assert not got["readback_not_newest_acknowledged"].ok      # a record nobody wrote


def test_probe_rules():
    sound = [{"kind": "altered-signature", "sent": 4, "accepted": 0, "unchanged": True},
             {"kind": "under-quorum", "sent": 4, "accepted": 0, "unchanged": True}]
    assert all(c.ok for c in ref.check_probe(sound))
    accepted = [dict(sound[0], accepted=4, unchanged=False), sound[1]]
    got = {c.name: c for c in ref.check_probe(accepted)}
    assert not got["bad_write2_accepted_by_replicas"].ok
    assert not got["bad_write2_changed_a_record"].ok
    unsent = [dict(sound[0], sent=0), sound[1]]   # a probe that sent nothing proves nothing
    assert not {c.name: c for c in ref.check_probe(unsent)}["bad_write2_sent"].ok


def test_summary_counts_all_work_of_the_window():
    ops = [update(1, 0.0, 0.5, 0, 0), read(1, 0.6, 0.7, 0, 0),
           update(1, 9.9, 10.4, 0, 1),            # issued inside, answered after the end
           read(1, 5.0, 65.0, -1, -1, ok=0)]      # failed
    s = ref.summarize(ops, seconds=10.0, t_end=10.0)
    assert s["attempted"] == 4 and s["failed"] == 1
    assert s["ops_s"] == pytest.approx(0.2)
    assert sorted(s["latency_ms"][ref.UPDATE]) == pytest.approx([500.0, 500.0])
    assert s["latency_ms"][ref.READ][1] == math.inf


def test_the_window_second_by_second_counts_what_was_answered_and_takes_the_reads_median():
    # [kind, record, t_issue, t_done, ok, ...]: a read and an update in the first
    # second, the update answered in the second one; a read that failed, and one
    # issued in the last second and answered after the window
    ops = [[ref.READ, 1, 10.2, 10.21, 1, 0, 0, 0, 3], [ref.UPDATE, 1, 10.5, 11.4, 1, 0, 0, 0, 0],
           [ref.READ, 2, 11.95, 11.96, 0, -1, -1, 0, 0], [ref.READ, 2, 12.4, 12.6, 1, 0, 0, 0, 3]]
    got = ref.by_second(ops, 10.0, 2.5)
    assert got["answered"] == [1, 1, 0]
    assert got["read_p50_ms"][0] == pytest.approx(10.0) and got["read_p50_ms"][1] is None
    assert got["read_p50_ms"][2] == pytest.approx(200.0)
