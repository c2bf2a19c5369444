"""CPU dry-run of the VPU-peak measurement (scripts/vpu_peak.py).

The measured peak is the only denominator a ``kernel.*`` roofline share may
be printed against (``perf/peaks.json`` holds a null until one is measured).
Checks: the record's shape, the round-trip-domination guard (a flagged config
must never set the headline), and that a CPU run never writes
``chiprun_out/vpu_peak.json`` (a host-core number must not become the chip's
denominator).
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
    rec = mod.measure()
    assert rec["metric"] == "vpu_int32_madd_peak"
    assert rec["value"] > 0
    assert rec["platform"] == "cpu"
    assert rec["unit"] == "int_ops/sec"
    assert isinstance(rec["dispatch_rtt_ms"], float)
    for cfg in rec["table"].values():
        assert cfg["int_ops_per_sec_raw"] <= cfg["int_ops_per_sec"] * 1.001
    # what a reader of the record keys on
    assert set(rec) >= {"value", "platform", "device_kind", "table"}
    # CPU runs must NOT write the file a roofline share would read
    assert not os.path.exists(tmp_path / "chiprun_out" / "vpu_peak.json")
