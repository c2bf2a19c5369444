"""Comb vs ladder A/B: known-signer verification throughput.

Measures the doubling-free comb path (crypto/comb.py) against the general
ladder at the headline bucket, with the signer-set size of the cluster
workloads (config 3: n=16; config 4: n=64) — every item signed by one of K
registered keys, which is exactly the cluster's verify traffic shape
(grant certificates and view-change votes come from replica identities).

Output lines:

  COMB K=16: <rate> sigs/s (<ms> ms)   vs LADDER: <rate> sigs/s -> <ratio>x

Every batch is read back (np.asarray) inside the timed region.

Usage: [COMB_BATCH=8192] [COMB_SIGNERS=16,64]
       python scripts/comb_bench.py
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mochi_tpu.utils.runtime import device_info, enable_compile_cache  # noqa: E402

enable_compile_cache()

from mochi_tpu.crypto import batch_verify, comb, keys  # noqa: E402
from mochi_tpu.verifier.spi import VerifyItem  # noqa: E402


def _items(kps, n):
    out = []
    for i in range(n):
        kp = kps[i % len(kps)]
        msg = b"comb-bench-%d" % i
        out.append(VerifyItem(kp.public_key, msg, kp.sign(msg)))
    return out


def _time_best(fn, reps=3):
    best = float("inf")
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()  # each fn ends in readback (np.asarray via verify_batch)
        dt = time.perf_counter() - t0
        best = min(best, dt)
    return best, out


def main() -> None:
    device = device_info(require_accelerator=True)
    n = int(os.environ.get("COMB_BATCH", str(batch_verify.MAX_BUCKET)))
    signer_counts = [
        int(k) for k in os.environ.get("COMB_SIGNERS", "16,64").split(",") if k
    ]

    # --- ladder baseline (same items as the K=first leg)
    kps = [keys.generate_keypair() for _ in range(max(signer_counts))]
    items = _items(kps[: signer_counts[0]], n)
    t0 = time.perf_counter()
    batch_verify.verify_batch(items)  # compile + warm
    print(f"ladder compile+warm {time.perf_counter() - t0:.1f}s", flush=True)
    ladder_dt, ladder_out = _time_best(lambda: batch_verify.verify_batch(items))
    assert all(ladder_out)
    ladder_rate = n / ladder_dt
    print(f"LADDER: {ladder_rate:.1f} sigs/s ({ladder_dt * 1e3:.1f} ms)", flush=True)
    results = {
        "platform": device["platform"],
        "batch": n,
        "ladder_sigs_per_sec": round(ladder_rate, 1),
        "comb_by_signers": {},
    }
    if device["platform"] != "tpu":
        results["dry_run"] = True  # explicit CPU run: not a device figure

    def checkpoint():
        # Cumulative record after EVERY milestone: the LAST COMB_JSON line
        # holds everything measured so far, whatever stops the run.
        import json as _json

        print("COMB_JSON " + _json.dumps(results), flush=True)

    checkpoint()

    for k in signer_counts:
        reg = comb.SignerRegistry()
        reg.register_all([kp.public_key for kp in kps[:k]])
        items = _items(kps[:k], n)
        t0 = time.perf_counter()
        batch_verify.verify_batch(items, registry=reg)  # compile + warm
        print(
            f"comb K={k} compile+warm {time.perf_counter() - t0:.1f}s", flush=True
        )
        dt, out = _time_best(
            lambda: batch_verify.verify_batch(items, registry=reg)
        )
        assert all(out)
        rate = n / dt
        print(
            f"COMB K={k}: {rate:.1f} sigs/s ({dt * 1e3:.1f} ms)   "
            f"vs LADDER: {ladder_rate:.1f} sigs/s -> {rate / ladder_rate:.2f}x",
            flush=True,
        )
        results["comb_by_signers"][str(k)] = {
            "sigs_per_sec": round(rate, 1),
            "speedup_vs_ladder": round(rate / ladder_rate, 3),
        }
        checkpoint()

    # ---- accumulation-formulation A/B at the kernel level ---------------
    # chain (default): 128 sequential madds, fewest muls.  tree: one-hot
    # MXU select + 7-level balanced reduction — ~40% more muls, ~18x
    # shallower critical path.  Decides MOCHI_COMB_IMPL for the regime the
    # chip actually is in (the roofline keeps saying schedule-bound).
    reg = comb.SignerRegistry()
    reg.register_all([kp.public_key for kp in kps[: signer_counts[0]]])
    items = _items(kps[: signer_counts[0]], n)
    key_idx = np.asarray(
        [reg.index_of(it.public_key) for it in items], dtype=np.int32
    )
    (ckey, y_r, sign_r, s_sc, h_sc), pre_ok = comb._prepare_comb(items, key_idx, None)
    assert pre_ok.all()
    table = reg.device_table()
    impl_rates = {}
    for impl in ("chain", "tree"):
        t0 = time.perf_counter()
        out = np.asarray(
            comb._verify_comb_jit(table, ckey, y_r, sign_r, s_sc, h_sc, impl=impl)
        )
        assert out.all()
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(
                comb._verify_comb_jit(
                    table, ckey, y_r, sign_r, s_sc, h_sc, impl=impl
                )
            )
            best = min(best, time.perf_counter() - t0)
        impl_rates[impl] = round(n / best, 1)
        print(
            f"COMB_IMPL={impl}: {n / best:.1f} sigs/s "
            f"({best * 1e3:.1f} ms, compile {compile_s:.1f}s)",
            flush=True,
        )
    results["impl_ab"] = impl_rates
    results["impl_winner"] = max(impl_rates, key=impl_rates.get)
    checkpoint()

    # ---- comb bucket sweep ----------------------------------------------
    # The ladder's 8192-lane peak was set by the PER-ITEM small-multiples
    # table spilling VMEM; the comb kernel keeps tables shared (HBM
    # gathers), so larger buckets may amortize further.  Sweep upward
    # until the rate drops.
    sweep = {}
    best_rate_so_far = 0.0
    for bucket in (n, 2 * n, 4 * n):  # n=8192 on chip -> 8192/16384/32768
        try:
            bitems = _items(kps[: signer_counts[0]], bucket)
            bkey = np.asarray(
                [reg.index_of(it.public_key) for it in bitems], dtype=np.int32
            )
            (k2, y2, s2, sb2, hb2), ok2 = comb._prepare_comb(bitems, bkey, None)
            assert ok2.all()
            t0 = time.perf_counter()
            out = np.asarray(
                comb._verify_comb_jit(table, k2, y2, s2, sb2, hb2)
            )
            compile_s = time.perf_counter() - t0
            assert out.all()
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(comb._verify_comb_jit(table, k2, y2, s2, sb2, hb2))
                best = min(best, time.perf_counter() - t0)
            rate = bucket / best
            sweep[str(bucket)] = round(rate, 1)
            print(
                f"COMB_BUCKET={bucket}: {rate:.1f} sigs/s "
                f"({best * 1e3:.1f} ms, compile {compile_s:.1f}s)",
                flush=True,
            )
            if rate < best_rate_so_far * 0.95:
                break  # regressing: stop burning chip time
            best_rate_so_far = max(best_rate_so_far, rate)
        except Exception as exc:  # OOM at a big shape must not kill the step
            sweep[str(bucket)] = f"error: {type(exc).__name__}"
            print(f"COMB_BUCKET={bucket}: {sweep[str(bucket)]}", flush=True)
            break
    if sweep:
        results["bucket_sweep"] = sweep
        checkpoint()

    # correctness spot check on-device: forgeries must still be caught
    bad = items[:64]
    bad = [
        VerifyItem(it.public_key, it.message, it.signature[:5] + bytes([it.signature[5] ^ 1]) + it.signature[6:])
        for it in bad
    ]
    reg = comb.SignerRegistry()
    reg.register_all([kp.public_key for kp in kps])
    assert not any(
        batch_verify.verify_batch(bad, registry=reg)
    ), "comb accepted forged signatures"
    print("forgery spot-check OK", flush=True)
    results["forgery_spot_check"] = "ok"
    checkpoint()


if __name__ == "__main__":
    main()
