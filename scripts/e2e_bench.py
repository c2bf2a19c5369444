"""End-to-end vs pipelined-steady-state gap.

Round-2 measured 69.8k sigs/s end-to-end on 64k items against a 111k
pipelined steady state (63%); the prepare-thread overlap
(batch_verify._prep_pool) landed after that capture and has never run on
the chip.  This measures both rates in one process, same buffers:

* pipelined: D batches of MAX_BUCKET in flight over the SAME prepared
  arrays (device time + dispatch round trip only — the ceiling);
* end-to-end: ``verify_batch`` on a fresh 64k item list (host prepare +
  H2D + device + readback through the chunked pipeline — the real
  service path).

Goal: end-to-end >= 90% of pipelined.  If the gap persists, the
per-phase timings printed below name the residual.

Usage: python scripts/e2e_bench.py [n_items] [depth]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mochi_tpu.utils.runtime import device_info, enable_compile_cache  # noqa: E402

enable_compile_cache()

from mochi_tpu.crypto import batch_verify, keys  # noqa: E402
from mochi_tpu.verifier.spi import VerifyItem  # noqa: E402


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 65536
    depth = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    mb = batch_verify.MAX_BUCKET
    device = device_info(require_accelerator=True)
    dev = jax.devices()[0]
    # explicit CPU run: stamped, not a device figure
    stamp = {} if device["platform"] == "tpu" else {"dry_run": True}
    print(f"device: {device['platform']}, n={n}, MAX_BUCKET={mb}, depth={depth}")

    kp = keys.generate_keypair()
    t0 = time.perf_counter()
    items = [
        VerifyItem(kp.public_key, b"e2e %d" % i, kp.sign(b"e2e %d" % i))
        for i in range(n)
    ]
    print(f"signing {n} items: {time.perf_counter()-t0:.1f}s")

    # Phase timings on one chunk (names the residual if the gap persists)
    chunk = items[:mb]
    t0 = time.perf_counter()
    prepared = batch_verify._prepare_padded(chunk, None)
    prep_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    launched = batch_verify._dispatch(prepared)
    dispatch_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    batch_verify._readback(launched, mb)  # includes compile on first call
    first_readback_s = time.perf_counter() - t0

    # Pipelined ceiling: same prepared buffers, depth batches in flight.
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        outs = [batch_verify._dispatch(prepared) for _ in range(depth)]
        for o in outs:
            batch_verify._readback(o, mb)
        rates.append(depth * mb / (time.perf_counter() - t0))
    pipelined = max(rates)

    # End-to-end: the real verify_batch path (prepare thread + bounded
    # launch window).  Two runs; report the best (first may still warm).
    e2e_rates = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = batch_verify.verify_batch(items)
        e2e_rates.append(n / (time.perf_counter() - t0))
        assert all(out)
    e2e = max(e2e_rates)

    # Checkpoint the core record BEFORE the comb leg: a failure in the comb
    # compiles must not lose the ladder e2e measurement (the LAST E2E_JSON
    # line is the record).
    partial = {
        "metric": "e2e_vs_pipelined",
        "platform": device["platform"],
        **stamp,
        "n_items": n,
        "max_bucket": mb,
        "depth": depth,
        "pipelined_sigs_per_sec": round(pipelined, 1),
        "e2e_sigs_per_sec": round(e2e, 1),
        "e2e_fraction_of_pipelined": round(e2e / pipelined, 3),
        "phase_per_chunk_ms": {
            "prepare": round(prep_s * 1e3, 1),
            "dispatch": round(dispatch_s * 1e3, 1),
            "first_readback_incl_compile": round(first_readback_s * 1e3, 1),
        },
        "goal": ">=0.90 of pipelined",
    }
    print("E2E_JSON " + json.dumps(partial), flush=True)

    # Comb leg: the registered-signer end-to-end (the cluster's production
    # posture — host prepare + comb device path through the same chunked
    # pipeline).  Faster device -> the host/pipeline overhead matters MORE
    # here; the native batched-h prepare (native/hbatch.c) is what keeps
    # the host ahead.
    from mochi_tpu.crypto import comb as comb_mod

    reg = comb_mod.SignerRegistry(device=dev)
    if reg.register(kp.public_key) is None:
        raise RuntimeError("signer registration failed")
    t0 = time.perf_counter()
    out = batch_verify.verify_batch(items, registry=reg)  # compile + warm
    assert all(out)
    comb_warm_s = time.perf_counter() - t0
    comb_rates = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = batch_verify.verify_batch(items, registry=reg)
        comb_rates.append(n / (time.perf_counter() - t0))
        assert all(out)
    e2e_comb = max(comb_rates)
    print(f"comb e2e warm {comb_warm_s:.1f}s; {e2e_comb:.1f} sigs/s")

    rec = {
        "metric": "e2e_vs_pipelined",
        "platform": device["platform"],
        **stamp,
        "n_items": n,
        "max_bucket": mb,
        "depth": depth,
        "pipelined_sigs_per_sec": round(pipelined, 1),
        "e2e_sigs_per_sec": round(e2e, 1),
        "e2e_fraction_of_pipelined": round(e2e / pipelined, 3),
        "e2e_comb_sigs_per_sec": round(e2e_comb, 1),
        "e2e_comb_vs_ladder_e2e": round(e2e_comb / e2e, 2),
        "phase_per_chunk_ms": {
            "prepare": round(prep_s * 1e3, 1),
            "dispatch": round(dispatch_s * 1e3, 1),
            "first_readback_incl_compile": round(first_readback_s * 1e3, 1),
        },
        "goal": ">=0.90 of pipelined",
    }
    print("E2E_JSON " + json.dumps(rec))


if __name__ == "__main__":
    main()
