"""Tier-1's hold on the benchmark's fault schedule (``perf/schedule.py``, what
the reference and the readers make of a fault's records): the tests live with
the benchmark in ``perf/tests/test_schedule.py``.  ``tests/test_schedule.py`` is
the product's own module of that name, so this one is loaded by path under
another name, not star-imported as ``tests/test_perf_hostspans.py`` does it."""

import importlib.util
import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

_spec = importlib.util.spec_from_file_location(
    "perf_test_schedule", os.path.join(PERF, "tests", "test_schedule.py")
)
_mod = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = _mod
_spec.loader.exec_module(_mod)
globals().update({k: v for k, v in vars(_mod).items() if not k.startswith("_")})


def test_the_shipped_mixes_load_and_only_one_has_a_schedule(tmp_path):  # noqa: F811
    """Since PR 37 three shipped mixes have a schedule (``ycsb-a-kill1``,
    ``ycsb-a-kill1-rehydrate``, ``ycsb-a-kill1-resync``) and the verbs' files
    are four; ``perf/tests/test_schedule.py`` is a benchmark file and still
    says one, so tier-1 holds the rest of what it held here."""
    m = _mod
    assert len(m.schedule.validate(m.ycsb.load_traffic(m.mix_file(tmp_path, m.KILL1))["faults"], m.FAULTS)) == 2
    shipped = {n[:-5]: m.ycsb.load_traffic(os.path.join(PERF, "traffic", n))
               for n in os.listdir(os.path.join(PERF, "traffic"))}
    assert {k for k, v in shipped.items() if "faults" in v} == {
        "ycsb-a-kill1", "ycsb-a-kill1-rehydrate", "ycsb-a-kill1-resync"}
    # a verb is a file: no verb without one, no file without a cell that runs it
    verbs = {n[:-3] for n in os.listdir(m.FAULTS) if n.endswith(".py")}
    assert verbs == {ev["do"] for v in shipped.values() for ev in v.get("faults", ())} and len(verbs) == 4
