"""Measure ladder fori_loop unrolling on-chip.

The 64-iteration ladder body is ~1700 small (17, B) VPU ops; unrolling
gives XLA a larger fusion scope per iteration at the cost of compile time.
Reports pipelined rate (depth 4) per unroll factor.

Usage: python scripts/unroll_bench.py [batch]   (default 8192)
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mochi_tpu.utils.runtime import device_info, enable_compile_cache  # noqa: E402

enable_compile_cache()

from mochi_tpu.crypto import batch_verify, curve, keys  # noqa: E402
from mochi_tpu.verifier.spi import VerifyItem  # noqa: E402


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    device = device_info(require_accelerator=True)
    dev = jax.devices()[0]
    print(f"device: {device['platform']}  batch={batch}")
    if device["platform"] != "tpu":
        print("DRY RUN (JAX_PLATFORMS=cpu): not a device figure", flush=True)
    kp = keys.generate_keypair()
    items = [
        VerifyItem(kp.public_key, b"u%d" % i, kp.sign(b"u%d" % i))
        for i in range(batch)
    ]
    y_a, sign_a, y_r, sign_r, s_bits, h_bits, pre_ok = batch_verify.prepare(items)
    args = tuple(
        jax.device_put(a, dev)
        for a in (y_a, sign_a, y_r, sign_r, s_bits, h_bits)
    )

    for unroll in (1, 2, 4):
        curve.LADDER_UNROLL = unroll
        fn = jax.jit(curve.verify_prepared)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        compile_s = time.perf_counter() - t0
        assert np.asarray(out).all(), f"unroll={unroll} WRONG RESULT"
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            for o in [fn(*args) for _ in range(4)]:
                np.asarray(o)  # true sync: D2H readback
            best = max(best, 4 * batch / (time.perf_counter() - t0))
        print(
            f"unroll={unroll}:  {best:10.1f} sigs/s pipelined-4   "
            f"(compile {compile_s:.1f}s)"
        )
    curve.LADDER_UNROLL = 1


if __name__ == "__main__":
    main()
