"""Project-native static analysis for mochi-tpu.

``python -m mochi_tpu.analysis [paths...]`` runs five AST checkers tuned to
this codebase's failure modes (see docs/ANALYSIS.md):

* ``async-blocking``       — blocking calls inside coroutine bodies
* ``cancellation-hygiene`` — handlers that swallow asyncio cancellation
* ``jax-trace-safety``     — host sync / Python branching in traced code
* ``constant-time``        — timing-oracle comparisons on authenticators
* ``protocol-invariants``  — payload registration + quorum-math locality

Programmatic entry point: :func:`mochi_tpu.analysis.core.run`.  The pass is
wired into tier-1 (``tests/test_static_analysis.py``) and
``scripts/lint.sh``, so a finding fails CI, not code review.
"""

from .core import Finding, RunResult, all_rules, run

__all__ = ["Finding", "RunResult", "all_rules", "run"]
