"""``perf/run.py`` end to end on the CPU, at the rehearsal's tiny shape: a
sound run is ``correct``, and a run with a guarantee broken underneath is not.
Each rehearsal boots a real cluster and takes a minute or two."""

import json
import os
import subprocess
import sys

import pytest

from test_data_driven import add_throwaway

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
REPO = os.path.dirname(PERF)
CPU = dict(os.environ, JAX_PLATFORMS="cpu")


def rehearse(script, *args, timeout=900):
    done = subprocess.run([sys.executable, script, *args, "--rehearse"], cwd=REPO, env=CPU,
                          capture_output=True, text=True, timeout=timeout)
    lines = done.stdout.splitlines()
    return done, (json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None)


def test_no_tpu_no_result():
    done = subprocess.run([sys.executable, os.path.join(PERF, "run.py"), "--workload", "rf4-ycsb-a",
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=REPO, env=CPU, capture_output=True, text=True, timeout=60)
    assert done.returncode != 0 and done.stdout == ""


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(PERF, tmp_path / "perf", ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run([sys.executable, "perf/run.py", "--workload", "rf4-ycsb-a", "--seed", "1",
                           "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=dict(env, JAX_PLATFORMS="tpu,cpu"),
                          capture_output=True, text=True, timeout=60)
    assert done.returncode != 0 and done.stdout == ""


def test_a_sound_rehearsal_of_a_throwaway_cell_is_correct(tmp_path):
    # the cell, its configuration (which states the paged engine), its mix with
    # a fault schedule, one verb of that schedule and one metric exist only as
    # files added beside the committed ones (test_data_driven), and run end to end
    root = str(tmp_path / "checkout")
    add_throwaway(root)
    done, result = rehearse(os.path.join(PERF, "run.py"), "--root", root, "--workload", "n7-ycsb-b",
                            "--seed", str(2**31 + 5), "--seconds", "4", "--trace", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True, done.stdout[-3000:]
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device", "rehearsal", "tree", "checks"}
    assert list(result)[-1] == "checks"
    # what a checkout carries besides its code, on every line (ISSUE 43, step 4)
    assert {"path_len", "transport", "native_md5", "decode_env", "distinct_cores", "cpus"} <= set(result["tree"])
    assert result["tree"]["transport"] in ("uds", "tcp") and all(result["tree"]["native_md5"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    # the configuration's "storage_engine": "paged" was applied, and compared with /status
    assert result["checks"]["replicas_reporting_other_storage_engines"] == {"value": 0, "limit": 0, "rule": "<="}
    assert "fault drain_replica server-" in done.stdout and "fault restart_replica server-" in done.stdout
    assert result["checks"]["replicas_restarted"]["value"] == 1
    # each number compared is also among the last lines of standard error
    assert done.stderr.rstrip().splitlines()[-1].startswith("[perf] check cache_entries_gained_in_window: 0")
    assert "gen.ops_per_cpu_s" in result["metrics"] and "gen.busy_share" in result["metrics"]
    # a rehearsal prints no device metric
    assert "device.idle_share" not in result["metrics"]
    assert set(result["device"]) == {"platform", "kind", "count"} and result["device"]["platform"] == "cpu"
    assert not os.path.exists(os.path.join(PERF, "out", f"n7-ycsb-b-{2**31 + 5}"))


RECOVERY = {"recovery.replay_ms", "recovery.replay_entries", "recovery.boot_s", "recovery.items_per_rpc",
            "recovery.memo_hit_share"}


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_kills_and_restarts_a_replica_and_times_its_recovery(trace):
    done, result = rehearse(os.path.join(PERF, "run.py"), "--workload", "rf4-recover",
                            "--seed", str(2**31 + 11 + trace), "--seconds", "12", "--trace", str(trace))
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0, done.stdout[-3000:]
    assert "fault kill_replica server-" in done.stdout and "fault restart_replica server-" in done.stdout
    if trace == 0:
        # (its update tail left the end-to-end list with PR 43: PERF.md section 2)
        assert set(result["metrics"]) == {"ops_s", "recover_s", "setup_s"}
        assert 0.05 < result["metrics"]["recover_s"]["value"] < 60
    else:
        # (``recovery.device_item_share`` needs a signature verified between the
        # restart and READY, which a replay of memo hits at this size may not have)
        assert RECOVERY <= set(result["metrics"]) and "tail.read_p95_ms" in result["metrics"]
        # its update path is read under the ``.ops`` names, as rf4-50k-recover's, and the
        # read median that tells its level beside the tails
        assert {"client.write1_p50_ms.ops", "verifier.items_per_flush.ops", "store.fsyncs_per_update.ops",
                "tail.update_p95_ms", "tail.read_p50_ms"} <= set(result["metrics"])
        assert "client.write1_p50_ms" not in result["metrics"]
        assert result["metrics"]["recovery.replay_entries"]["value"] >= 96 * 4 / 5 * 0.5
    for name in ("replicas_restarted", "replay_entries_convicted", "direct_reads_sent",
                 "direct_reads_older_than_acknowledged_before_the_kill"):
        assert name in result["checks"]


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_of_the_cold_memo_cell_reports_its_recovery_and_no_update_tail_end_to_end(trace):
    # the plumbing only: at the rehearsal's 240 records the memo holds every certificate,
    # so nothing is offered before the load and the replay never leaves the memo
    done, result = rehearse(os.path.join(PERF, "run.py"), "--workload", "rf4-50k-recover",
                            "--seed", str(2**31 + 21 + trace), "--seconds", "12", "--trace", str(trace))
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0, done.stdout[-3000:]
    assert "records=240" in done.stdout and "offered warm-up batches" not in done.stdout
    if trace == 0:
        assert set(result["metrics"]) == {"ops_s", "recover_s", "setup_s"}
    else:
        # the profiler started at the restart command, 10 s into the window of 12
        assert "traced from 10." in done.stdout
        assert RECOVERY <= set(result["metrics"]) and "tail.read_p95_ms" in result["metrics"]
        # no update tail end to end, so the readers that move it report under ops_s
        assert {"tail.update_p95_ms", "client.write1_p50_ms.ops", "verifier.items_per_flush.ops",
                "store.fsyncs_per_update.ops"} <= set(result["metrics"])
        assert "client.write1_p50_ms" not in result["metrics"]
        # device readings are the chip's: a rehearsal prints none
        assert "recovery.device_busy_share" not in result["metrics"]
        assert result["metrics"]["recovery.replay_entries"]["value"] >= 240 * 4 / 5 * 0.5
    assert result["checks"]["replay_entries_convicted"] == {"value": 0, "limit": 0, "rule": "<="}


@pytest.mark.parametrize("control,failed_check", [
    ("accept-all", "bad_write2_accepted_by_replicas"),
    ("stale-reads", "window_stale_reads"),
])
def test_a_broken_run_is_not_correct(control, failed_check):
    done, result = rehearse(os.path.join(HERE, "control.py"), "--control", control,
                            "--workload", "rf4-ycsb-a", "--seed", "77", "--seconds", "4", "--trace", "0")
    assert_not_correct(done, result, failed_check)


def test_a_replica_restarted_with_an_emptied_directory_is_not_correct():
    # at the rehearsal's 96 records every record is written again after READY, so
    # what fails here is the replay's count; at the cell's own size the direct
    # read-back fails too (PERF.md section 2 has the chip's readings)
    done, result = rehearse(os.path.join(HERE, "control.py"), "--control", "emptied-storage",
                            "--workload", "rf4-recover", "--seed", "78", "--seconds", "12", "--trace", "0")
    assert_not_correct(done, result, "replicas_back_with_fewer_keys_than_held_before_the_kill")


def test_a_replica_restarted_on_a_log_with_forged_grants_is_not_correct():
    # the frames' CRCs are right and the replica comes back with every key: only
    # the replay's own verification of each certificate can tell
    done, result = rehearse(os.path.join(HERE, "control.py"), "--control", "forged-log",
                            "--workload", "rf4-recover", "--seed", "79", "--seconds", "12", "--trace", "0")
    assert_not_correct(done, result, "replay_entries_convicted")
    assert result["checks"]["replay_entries_convicted"]["value"] >= 3
    assert result["checks"]["replicas_back_with_fewer_keys_than_held_before_the_kill"]["value"] == 0


def assert_not_correct(done, result, failed_check):
    assert result is not None, done.stderr[-2000:]
    assert result["correct"] is False
    failed = [l for l in done.stdout.splitlines() if l.endswith("FAILED")]
    assert any(failed_check in l for l in failed), failed
    assert done.returncode == 0   # the control's own verdict: it failed as it must
