"""The trace reduction on a small recorded trace (TPU v5 lite, PR 23, call 1):
two jitted programs, ``small_mul`` and ``small_add``, launched three times each
with 50 ms of host sleep between launches, 0.465 s traced."""

import os

import pytest

import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "two_programs.xplane.pb")
PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_file(TRACE, 0.465)


def test_launches_and_programs(reduced):
    assert reduced["device_planes"] == 1
    assert reduced["launches"] == 6
    assert set(reduced["programs"]) == {"jit_small_mul", "jit_small_add"}
    # the launches' durations as the trace has them, in picoseconds
    assert reduced["programs"]["jit_small_mul"] == pytest.approx(11.077e-6, rel=1e-3)
    assert reduced["programs"]["jit_small_add"] == pytest.approx(7.624e-6, rel=1e-3)


def test_busy_is_the_union_of_op_intervals(reduced):
    # the async copy overlaps nothing else here, so the union equals the sum
    assert reduced["busy_s"] == pytest.approx(sum(reduced["ops"].values()), rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(18.673e-6, rel=1e-3)
    assert reduced["busy_s"] <= sum(reduced["programs"].values())
    assert xplane.idle_share(reduced) == pytest.approx(100 * (1 - 18.673e-6 / 0.465), rel=1e-9)


def test_gaps_and_breakdown(reduced):
    # five sleeps of ~50 ms between six launches, the rest before and after
    assert sum(1 for g in reduced["gaps_s"] if 0.04 < g < 0.06) == 4
    assert reduced["gaps_s"][0] == pytest.approx(0.465 - 0.19985, abs=1e-3)
    b = xplane.breakdown([reduced])
    assert b["device_ops"][0][0] == "add_reduce_fusion"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(name == "unattributed" for name, _ in b["idle_gaps"])


def test_union_merges_overlaps():
    assert xplane.union([(0, 5), (3, 8), (10, 12), (12, 13), (20, 21)]) == [[0, 8], [10, 13], [20, 21]]


def test_reduce_dir_runs_in_a_child_and_agrees(tmp_path, reduced):
    run_dir = tmp_path / "plugins" / "profile" / "2026_01_01"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(open(TRACE, "rb").read())
    assert xplane.reduce_dir(str(tmp_path), 0.465) == reduced
    with pytest.raises(FileNotFoundError):
        xplane.reduce_dir(str(tmp_path / "nothing"), 1.0)


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    peaks = xplane.load_peaks(os.path.join(PERF, "peaks.json"), "TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["int32_vpu_ops_per_s"] is None
    with pytest.raises(KeyError):
        xplane.load_peaks(os.path.join(PERF, "peaks.json"), "TPU v9")
