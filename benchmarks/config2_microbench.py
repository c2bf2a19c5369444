"""Config 2: Ed25519 batch-verify microbench, 1k-64k msgs/batch.

Device throughput vs batch size, plus the same-host single-thread
CPU/OpenSSL baseline (the reference-analog BouncyCastle path) — the measured
denominator BASELINE.json's ">=100k ops/s, <5% CPU" targets need
(SURVEY.md §7 "no reference crypto numbers exist").
"""

from __future__ import annotations

import time
from typing import Dict, List


def run(batch_sizes=(1024, 2048, 4096, 8192), iters: int = 3,
        big_batch: int = 65536) -> Dict:
    import jax
    import numpy as np

    from mochi_tpu.crypto import batch_verify, keys
    from mochi_tpu.crypto.curve import verify_prepared
    from mochi_tpu.verifier.spi import VerifyItem

    dev = jax.devices()[0]
    fn = jax.jit(verify_prepared)

    # one keypair, distinct messages (hashing happens host-side in prepare)
    kp = keys.generate_keypair()

    points: List[Dict] = []
    items: List[VerifyItem] = []
    for b in batch_sizes:
        items = []
        for i in range(b):
            msg = b"micro %d" % i
            items.append(VerifyItem(kp.public_key, msg, kp.sign(msg)))
        prep = batch_verify.prepare(items)
        args = tuple(jax.device_put(a, dev) for a in prep[:6])
        out = jax.block_until_ready(fn(*args))  # compile + warmup
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            # np.asarray: the D2H readback is inside the timed region
            out = np.asarray(fn(*args))
            best = min(best, time.perf_counter() - t0)
        assert out.all()
        points.append(
            {"batch": b, "sigs_per_sec": round(b / best, 1), "ms": round(best * 1e3, 2)}
        )

    # 64k msgs via the production path (verify_batch chunks at the
    # MAX_BUCKET VMEM peak with every chunk launched before any readback —
    # raw 16k+/64k programs spill VMEM and regress, which is why the
    # chunking exists; BASELINE config 2 range still covered).
    big = big_batch
    if not big:  # --smoke harness pass: skip the 64k production-path leg
        return _record(points, items, keys, batch_sizes)
    items64 = []
    for i in range(big):
        msg = b"micro64k %d" % i
        items64.append(VerifyItem(kp.public_key, msg, kp.sign(msg)))
    # warm the production chunk bucket (the packed-scalar program is a
    # DIFFERENT executable from the bit-tensor one warmed above — without
    # this the 64k row times a cold compile)
    batch_verify.verify_batch(items64[: batch_verify.MAX_BUCKET], device=dev)
    t0 = time.perf_counter()
    bitmap = batch_verify.verify_batch(items64, device=dev)
    chunked_s = time.perf_counter() - t0
    assert all(bitmap)
    points.append(
        {
            "batch": big,
            "sigs_per_sec": round(big / chunked_s, 1),
            "ms": round(chunked_s * 1e3, 2),
            "path": "verify_batch (chunked, incl. host prepare)",
        }
    )

    return _record(points, items, keys, batch_sizes)


def _record(points, items, keys, batch_sizes) -> Dict:
    # CPU baseline (sampled)
    sample = items[:512]
    t0 = time.perf_counter()
    for it in sample:
        keys.verify(it.public_key, it.message, it.signature)
    cpu_rate = len(sample) / (time.perf_counter() - t0)

    peak = max(p["sigs_per_sec"] for p in points)
    return {
        "metric": "ed25519_batch_verify_peak_throughput",
        "value": peak,
        "unit": "sigs/sec",
        "vs_baseline": round(peak / cpu_rate, 2),
        "cpu_openssl_sigs_per_sec": round(cpu_rate, 1),
        "points": points,
    }


if __name__ == "__main__":
    import json

    print(json.dumps(run()))
