"""Shared helpers for the standalone device microbenchmark scripts."""

from __future__ import annotations

import os


def require_tpu(dev) -> None:
    """Refuse to print a CPU number as device evidence: a host where JAX
    found no chip falls back to XLA:CPU silently, and these scripts exist
    to measure the chip.  Explicit CPU validation runs set
    MOCHI_ALLOW_CPU=1 (with JAX_PLATFORMS=cpu).
    """
    if os.environ.get("MOCHI_ALLOW_CPU") == "1":
        return
    if dev.platform != "tpu":
        raise SystemExit(
            f"refusing to measure on platform={dev.platform!r}: this script "
            "measures the chip (MOCHI_ALLOW_CPU=1 to override for CPU "
            "validation)"
        )
