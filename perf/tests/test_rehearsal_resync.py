"""``rf4-30k-resync`` end to end on the CPU at the rehearsal's tiny shape, and
its control (the same restart WITHOUT ``--resync-on-boot``, which the verb's look at the
replica's record has to refuse).  Each boots a real
cluster; by hand, like ``test_rehearsal.py``."""

import os
import re

import pytest

from test_rehearsal import HERE, PERF, rehearse

CELL = "rf4-30k-resync"
READERS = {"resync.ready_s", "resync.catchup_ms", "resync.digest_ms", "resync.delta_share",
           "resync.pulled_per_adopted"}


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_restarts_a_killed_replica_on_its_own_directory_and_resyncs_it(trace):
    done, result = rehearse(os.path.join(PERF, "run.py"), "--workload", CELL,
                            "--seed", str(2**31 + 371 + trace), "--seconds", "12", "--trace", str(trace))
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0, done.stdout[-3000:]
    assert "fault kill_replica server-" in done.stdout
    assert "fault restart_replica_resync server-" in done.stdout
    if trace == 0:
        # on no keyed list: the rate and the set-up, as the issue states
        assert set(result["metrics"]) == {"ops_s", "setup_s"}
    else:
        assert "traced from 4." in done.stdout
        assert READERS <= set(result["metrics"])
        # 240 records: 192 owned; what moved in ~2.5 s is a few of them, each named by three peers
        assert 0.0 < result["metrics"]["resync.delta_share"]["value"] < 60.0
        assert 1.0 <= result["metrics"]["resync.pulled_per_adopted"]["value"] < 6.0
        assert 0.05 < result["metrics"]["resync.ready_s"]["value"] < 60
        assert result["metrics"]["resync.catchup_ms"]["value"] >= result["metrics"]["resync.digest_ms"]["value"] > 0
        assert not [m for m in result["metrics"] if m.startswith(("recovery.", "rehydrate.", "client.", "tail."))]
    checks = result["checks"]
    assert checks["replicas_restarted"]["value"] == 1 and checks["replay_entries_convicted"]["value"] == 0
    assert checks["replicas_back_with_fewer_keys_than_held_before_the_kill"]["value"] == 0
    assert checks["direct_reads_sent"]["value"] > 0
    for name in ("direct_reads_unanswered_or_empty", "direct_reads_of_no_known_write",
                 "direct_reads_under_quorum_grants", "direct_reads_older_than_acknowledged_before_the_kill"):
        assert checks[name]["value"] == 0


def test_the_same_restart_without_resync_on_boot_ends_without_a_result():
    done, result = rehearse(os.path.join(HERE, "control_plain.py"), "--workload", CELL,
                            "--seed", str(2**31 + 381), "--seconds", "12", "--trace", "0")
    assert done.returncode == 0, done.stderr[-2000:]
    assert result is None and re.search(r"\[control plain\] READY after \{'ready_s'", done.stdout)
    assert re.search(r"no result: the fault schedule did not run to its end: NotCaughtUp\('server-\d printed "
                     r"READY, but no resync pass on record", done.stderr)
    assert "[control plain] exit 3, refused by the verb's look at the record: True" in done.stderr
