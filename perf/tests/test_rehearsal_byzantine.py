"""``rf4-byz1-ycsb-a`` end to end on the CPU at the rehearsal's tiny shape, and
its two controls (the stated member booted honest; an unstated member in the
cell without one), which have to come out as not correct on the checks named.
Each boots a real cluster; by hand, like ``test_rehearsal.py``."""

import os

import pytest

from test_rehearsal import HERE, PERF, rehearse

CELL = "rf4-byz1-ycsb-a"
STRATEGY_CHECK = "replicas_whose_strategy_differs_from_what_the_configuration_states"
BYZ = {"byz.lies_per_op", "byz.bad_grants_per_update", "byz.read_fallback_share",
       "byz.callers_avoiding_member_share", "client.certificates_built_share"}


def off_limit(result):
    return {k for k, c in result["checks"].items()
            if (c["value"] < c["limit"] if c["rule"] == ">=" else c["value"] > c["limit"])}


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_with_a_forging_member_is_correct_and_the_member_is_caught(trace):
    done, result = rehearse(os.path.join(PERF, "run.py"), "--workload", CELL,
                            "--seed", str(2**31 + 451 + trace), "--seconds", "6", "--trace", str(trace))
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0, done.stdout[-3000:]
    assert not off_limit(result) and result["checks"]["replicas_answering_status"]["value"] == 5
    assert result["checks"][STRATEGY_CHECK] == {"value": 0, "limit": 0, "rule": "<="}
    assert result["checks"]["stated_members_that_never_acted_in_the_window"]["value"] == 0
    assert result["checks"]["lies_the_callers_caught"]["value"] >= 1
    assert result["checks"]["honest_replicas_accused_by_typed_evidence"]["value"] == 0
    assert '"suspect.bad-grant.server-1"' in done.stdout
    if trace == 0:
        assert set(result["metrics"]) == {"ops_s", "setup_s"}
    else:
        got = {k: v["value"] for k, v in result["metrics"].items()}
        assert BYZ | {"tail.update_p95_ms", "tail.read_p95_ms", "tail.read_p50_ms", "client.write1_p50_ms.ops",
                      "client.write2_wait_p50_ms.ops"} <= set(got)
        # server-1 sits in about four replica sets of five and forges every Write1 answer there
        assert 0.5 < got["byz.bad_grants_per_update"] < 1.0 and 0.3 < got["byz.lies_per_op"] < 2.0
        assert got["byz.callers_avoiding_member_share"] == 100.0 and 0 <= got["byz.read_fallback_share"] < 20
        assert 25 < got["client.certificates_built_share"] < 40


@pytest.mark.parametrize("control,workload,failed", [
    # the configuration states the member and the cluster boots it honest: the cell would measure nothing
    ("honest-member", CELL, {STRATEGY_CHECK, "stated_members_that_never_acted_in_the_window",
                             "lies_the_callers_caught"}),
    # the cell without a member, with one injected: every guarantee holds, and the deployment is not the stated one
    ("unstated-member", "rf4-ycsb-a", {STRATEGY_CHECK}),
])
def test_a_cluster_whose_members_are_not_the_stated_ones_is_not_correct(control, workload, failed):
    done, result = rehearse(os.path.join(HERE, "control.py"), "--control", control, "--workload", workload,
                            "--seed", str(2**31 + 461), "--seconds", "6", "--trace", "0")
    assert result is not None and done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is False and result["failed"] == 0
    assert off_limit(result) == failed
    assert f"[control {control}] correct=False" in done.stderr
