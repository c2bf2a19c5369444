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
    # the cell, its configuration, its mix and one metric exist only as files
    # added beside the committed ones (test_data_driven), and run end to end
    root = str(tmp_path / "checkout")
    add_throwaway(root)
    done, result = rehearse(os.path.join(PERF, "run.py"), "--root", root, "--workload", "n7-ycsb-b",
                            "--seed", str(2**31 + 5), "--seconds", "4", "--trace", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True, done.stdout[-3000:]
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "gen.ops_per_cpu_s" in result["metrics"] and "gen.busy_share" in result["metrics"]
    # a rehearsal prints no device metric
    assert "device.idle_share" not in result["metrics"]
    assert set(result["device"]) == {"platform", "kind", "count"} and result["device"]["platform"] == "cpu"
    assert not os.path.exists(os.path.join(PERF, "out", f"n7-ycsb-b-{2**31 + 5}"))


@pytest.mark.parametrize("control,failed_check", [
    ("accept-all", "bad_write2_accepted_by_replicas"),
    ("stale-reads", "window_stale_reads"),
])
def test_a_broken_run_is_not_correct(control, failed_check):
    done, result = rehearse(os.path.join(HERE, "control.py"), "--control", control,
                            "--workload", "rf4-ycsb-a", "--seed", "77", "--seconds", "4", "--trace", "0")
    assert result is not None, done.stderr[-2000:]
    assert result["correct"] is False
    failed = [l for l in done.stdout.splitlines() if l.endswith("FAILED")]
    assert any(failed_check in l for l in failed), failed
    assert done.returncode == 0   # the control's own verdict: it failed as it must
