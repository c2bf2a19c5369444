"""verify_batch throughput probe — the shared body of the MAX_BUCKET sweep
and the kernel-formulation A/B legs (one implementation; env knobs select
the leg).

Output lines:

  MAX_BUCKET=8192: <rate> sigs/s (<ms> ms)          (bucket leg)
  MOCHI_SELECT_IMPL=stacked: best <rate> sigs/s ... (A/B leg, MOCHI_AB_LEG set)

Usage: [env knobs] python scripts/throughput_probe.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mochi_tpu.utils.runtime import device_info, enable_compile_cache  # noqa: E402

enable_compile_cache()

from mochi_tpu.crypto import batch_verify, keys  # noqa: E402
from mochi_tpu.verifier.spi import VerifyItem  # noqa: E402


def main() -> None:
    device = device_info(require_accelerator=True)
    if device["platform"] != "tpu":
        print("DRY RUN (JAX_PLATFORMS=cpu): not a device figure", flush=True)
    n = batch_verify.MAX_BUCKET
    kp = keys.generate_keypair()
    items = [
        VerifyItem(kp.public_key, b"tp%d" % i, kp.sign(b"tp%d" % i))
        for i in range(n)
    ]
    batch_verify.verify_batch(items)  # compile + warm
    best, best_dt, out = 0.0, float("inf"), None
    for _ in range(3):
        t0 = time.perf_counter()
        out = batch_verify.verify_batch(items)
        dt = time.perf_counter() - t0
        if dt < best_dt:
            best_dt, best = dt, n / dt
    assert all(out)
    leg = os.environ.get("MOCHI_AB_LEG")
    if leg:
        print(f"{leg}: best {best:.1f} sigs/s at batch {n}")
    else:
        print(f"MAX_BUCKET={n}: {best:.1f} sigs/s ({best_dt * 1e3:.1f} ms)")


if __name__ == "__main__":
    main()
