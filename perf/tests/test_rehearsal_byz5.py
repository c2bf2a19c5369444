"""``n16-byz5-ycsb-a`` end to end on the CPU at the rehearsal's shape (sixteen
replicas in four processes, the five stated members answering throughout) on
three seeds, and its two controls: two members' strategies swapped, which the
deployment check counts since a replica's ``/status`` names its own strategy,
and every member booted honest.  Each boots a real cluster; by hand, like
``test_rehearsal_byzantine.py``."""

import os

import pytest

from test_byz5_readers import CELL, READERS
from test_rehearsal import HERE, PERF, rehearse
from test_rehearsal_byzantine import STRATEGY_CHECK, off_limit


@pytest.mark.parametrize("seed,trace", [(2**31 + 4601, 0), (2**31 + 4602, 1), (2**31 + 4603, 0)])
def test_a_rehearsal_with_five_members_of_two_kinds_is_correct_and_each_is_caught(seed, trace):
    done, result = rehearse(os.path.join(PERF, "run.py"), "--workload", CELL,
                            "--seed", str(seed), "--seconds", "6", "--trace", str(trace))
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0, done.stdout[-3000:]
    assert not off_limit(result) and result["checks"]["replicas_answering_status"]["value"] == 16
    assert result["checks"][STRATEGY_CHECK] == {"value": 0, "limit": 0, "rule": "<="}
    assert result["checks"]["stated_members_that_never_acted_in_the_window"]["value"] == 0
    assert result["checks"]["lies_the_callers_caught"]["value"] >= 1
    assert result["checks"]["honest_replicas_accused_by_typed_evidence"]["value"] == 0
    for sid, kind in (("server-1", "bad-grant"), ("server-7", "bad-grant"), ("server-10", "grant-conflict"),
                      ("server-13", "grant-conflict")):
        assert f'"suspect.{kind}.{sid}"' in done.stdout
    assert '"suspect.bad-grant.server-10"' not in done.stdout  # a replayer's grants are validly signed
    if trace == 0:
        assert set(result["metrics"]) == {"ops_s", "setup_s"}
    else:
        got = {k: v["value"] for k, v in result["metrics"].items()}
        assert set(READERS) - {"byz5.device_idle_share"} <= set(got)  # the CPU rehearsal prints no device metric
        assert 2.0 < got["byz5.bad_grants_per_update"] <= 3.0 and 1.5 < got["byz5.stale_grants_dropped_per_update"] < 2.5
        assert 60 < got["byz5.voting_grant_share"] < 80 and got["byz5.members_caught_by_own_kind_share"] == 100.0
        assert got["byz5.callers_avoiding_members_share"] == 100.0 and 0 <= got["byz5.read_fallback_share"] < 20
        assert 8.5 < got["byz5.certificates_built_share"] < 10 and 0 <= got["byz5.attempts_again_per_update"] < 1


@pytest.mark.parametrize("script,args,failed", [
    # server-1 (stated forge-cert) replays and server-10 (stated stale-replay) forges: both lie, and
    # not as stated; server-1 earns no bad-grant mark, so the callers did not catch it AS A FORGER either
    ("control_swapped.py", (), {STRATEGY_CHECK, "lies_the_callers_caught"}),
    ("control.py", ("--control", "honest-member"),
     {STRATEGY_CHECK, "stated_members_that_never_acted_in_the_window", "lies_the_callers_caught"}),
])
def test_a_cluster_whose_members_do_not_lie_as_stated_is_not_correct(script, args, failed):
    done, result = rehearse(os.path.join(HERE, script), *args, "--workload", CELL,
                            "--seed", str(2**31 + 4611), "--seconds", "6", "--trace", "0")
    assert result is not None and done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is False and result["failed"] == 0
    assert off_limit(result) == failed
    assert result["checks"][STRATEGY_CHECK]["value"] == (2 if script == "control_swapped.py" else 5)
