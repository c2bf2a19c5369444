"""Adversarial-load sweep: throughput vs forged-signature fraction.

The rejected random-linear-combination batch design
(batch_verify.py docstring) degrades under attack: one forged signature
fails the whole combined check and forces bisection retries, so an
attacker salting f% forgeries multiplies work by O(log n) per forgery.
This module's per-item-bitmap SIMD design does identical device work
regardless of verdicts — throughput must be FLAT across forged fractions.

This sweep proves that no-cliff property: batch 8192 at forged fractions
0 / 12.5 / 25 / 50 / 100%, same device program, verdict counts asserted.
Forgeries are signature bit-flips (pass the canonical prechecks, fail the
curve equation — the expensive kind; cheap non-canonical garbage is
rejected on host before the device sees it, measured separately).

Usage: python scripts/forgery_bench.py [batch]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mochi_tpu.utils.runtime import device_info, enable_compile_cache  # noqa: E402

enable_compile_cache()

from mochi_tpu.crypto import batch_verify, keys  # noqa: E402
from mochi_tpu.verifier.spi import VerifyItem  # noqa: E402


def main() -> None:
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    device = device_info(require_accelerator=True)
    kp = keys.generate_keypair()
    base = []
    for i in range(batch):
        msg = b"adv %d" % i
        base.append(VerifyItem(kp.public_key, msg, kp.sign(msg)))

    def forge(it: VerifyItem) -> VerifyItem:
        # Flip one bit in R: still a canonical encoding with overwhelming
        # probability, so it reaches the device and fails the curve check.
        sig = bytearray(it.signature)
        sig[3] ^= 0x10
        return VerifyItem(it.public_key, it.message, bytes(sig))

    batch_verify.verify_batch(base)  # compile + warm
    sweep = {}
    for frac in (0.0, 0.125, 0.25, 0.5, 1.0):
        k = int(batch * frac)
        items = [forge(it) if i < k else it for i, it in enumerate(base)]
        best = 0.0
        out = None
        for _ in range(3):
            t0 = time.perf_counter()
            out = batch_verify.verify_batch(items)
            best = max(best, batch / (time.perf_counter() - t0))
        n_bad = sum(1 for b in out if not b)
        assert n_bad == k, f"frac={frac}: {n_bad} rejected, expected {k}"
        sweep[str(frac)] = round(best, 1)

    # Cheap-garbage flood: non-canonical S >= L is rejected on HOST; the
    # device never runs, so this rate is the host precheck rate (higher is
    # fine, the point is no device-work amplification from garbage).
    garbage = [
        VerifyItem(it.public_key, it.message, it.signature[:32] + b"\xff" * 32)
        for it in base
    ]
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        out = batch_verify.verify_batch(garbage)
        best = max(best, batch / (time.perf_counter() - t0))
    assert not any(out)

    vals = list(sweep.values())
    rec = {
        "metric": "forged_fraction_throughput_sweep",
        "platform": device["platform"],
        "batch": batch,
        "sigs_per_sec_by_forged_fraction": sweep,
        "flatness_min_over_max": round(min(vals) / max(vals), 3),
        "noncanonical_flood_sigs_per_sec": round(best, 1),
        "claim": "per-item bitmap => no throughput cliff under forgery "
        "(batch_verify.py RLC-rejection argument)",
    }
    if device["platform"] != "tpu":
        rec["dry_run"] = True  # explicit CPU run: not a device figure
    print("FORGERY_JSON " + json.dumps(rec))


if __name__ == "__main__":
    main()
