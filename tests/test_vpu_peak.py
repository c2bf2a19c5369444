"""CPU dry-run of the VPU-peak measurement (scripts/vpu_peak.py).

The measured peak is the only denominator bench.py will print a utilisation
against (bench._measured_vpu_peak, keyed by device_kind).  Checks: the
record shape bench.py consumes, the round-trip-domination guard (a flagged
config must never set the headline), that a CPU run never writes
benchmarks/vpu_peak.json (a host-core number must not become the chip's
denominator), and that bench.py has NO peak — so no utilisation — for a
device kind nobody measured.
"""

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "vpu_peak", os.path.join(REPO, "scripts", "vpu_peak.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_vpu_peak_cpu_dryrun(tmp_path, monkeypatch):
    mod = _load()
    monkeypatch.setattr(mod, "_REPO", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # the explicit dry-run pin
    os.makedirs(tmp_path / "benchmarks")
    rec = mod.measure()
    assert rec["metric"] == "vpu_int32_madd_peak"
    assert rec["value"] > 0
    assert rec["platform"] == "cpu"
    assert rec["unit"] == "int_ops/sec"
    assert isinstance(rec["dispatch_rtt_ms"], float)
    for cfg in rec["table"].values():
        assert cfg["int_ops_per_sec_raw"] <= cfg["int_ops_per_sec"] * 1.001
    # bench.py's consumer contract: these are the keys it reads
    assert set(rec) >= {"value", "platform", "device_kind", "table"}
    # CPU runs must NOT write the file the utilisation accounting reads
    assert not os.path.exists(tmp_path / "benchmarks" / "vpu_peak.json")


def test_bench_has_no_peak_unless_measured_for_this_device_kind(
    tmp_path, monkeypatch
):
    import json

    import bench

    monkeypatch.setattr(bench, "_REPO", str(tmp_path))
    os.makedirs(tmp_path / "benchmarks")
    path = tmp_path / "benchmarks" / "vpu_peak.json"
    # no file -> no peak (and bench prints no utilisation): never an
    # assumed figure
    assert bench._measured_vpu_peak("TPU v5 lite") is None
    assert not hasattr(bench, "VPU_PEAK_INT_OPS")
    # measured on this device kind -> used
    with open(path, "w") as fh:
        json.dump(
            {"platform": "tpu", "device_kind": "TPU v5 lite", "value": 2.5e12}, fh
        )
    assert bench._measured_vpu_peak("TPU v5 lite") == 2.5e12
    # measured on ANOTHER kind -> not this device's denominator
    assert bench._measured_vpu_peak("TPU v4") is None
    # a cpu-platform file must be ignored
    with open(path, "w") as fh:
        json.dump(
            {"platform": "cpu", "device_kind": "TPU v5 lite", "value": 9.9e12}, fh
        )
    assert bench._measured_vpu_peak("TPU v5 lite") is None
