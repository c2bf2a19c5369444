"""Tier-1's hold on the recovery's per-layer readers that PRs 28 and 30 added
(``perf/layer_metrics/recovery.verify_wait_ms.py``,
``recovery.memo_settle_us_per_item.py``), collected from ``perf/tests`` the way
``tests/test_perf_resolve_reader.py`` collects PR 25's."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_memo_settle_reader import *  # noqa: E402,F401,F403
from test_verify_wait_reader import *  # noqa: E402,F401,F403


def test_memo_settle_is_keyed_to_the_two_recovery_cells():  # noqa: F811
    """PR 33 appended five entries to ``per_layer``: PR 30's is found by its
    name, as ``test_verify_wait_is_keyed_to_the_two_recovery_cells`` finds PR
    28's, not as the list's last (``perf/tests/test_memo_settle_reader.py`` is
    a benchmark file, and a PR that is not a ``benchmark`` PR edits none)."""
    import test_memo_settle_reader as m

    entry = next(e for e in m.base.run.load_cell(m.base.REPO, "rf4-recover")["bench"]["per_layer"]
                 if e["name"] == m.NAME)
    assert entry == {
        "name": m.NAME, "unit": "us", "better": "lower", "source": "program_span",
        "layer": "verifier SPI and service queue", "moves": "recover_s", "workloads": m.base.RECOVERY_CELLS}
