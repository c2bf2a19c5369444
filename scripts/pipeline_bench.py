"""Pipelined verify throughput: hide the host<->device round trip.

bench.py's per-batch numbers time sequential blocking calls, so each batch
pays the full dispatch + readback round trip on top of device time.  JAX
dispatch is async: submitting D batches before blocking overlaps the RTT
of batch k with device execution of batch k-1 — the steady-state rate a
loaded verifier service actually sustains.

Usage: python scripts/pipeline_bench.py [batch ...]   (default 8192 16384)
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

from mochi_tpu.crypto import batch_verify, keys  # noqa: E402
from mochi_tpu.crypto.curve import verify_prepared  # noqa: E402
from mochi_tpu.verifier.spi import VerifyItem  # noqa: E402


def main():
    batches = [int(a) for a in sys.argv[1:]] or [8192, 16384]
    device = device_info(require_accelerator=True)
    dev = jax.devices()[0]
    print(f"device: {device['platform']} {device['device_kind']}")
    if device["platform"] != "tpu":
        print("DRY RUN (JAX_PLATFORMS=cpu): not a device figure", flush=True)
    kp = keys.generate_keypair()
    fn = jax.jit(verify_prepared)

    for batch in batches:
        items = [
            VerifyItem(kp.public_key, b"p%d" % i, kp.sign(b"p%d" % i))
            for i in range(batch)
        ]
        y_a, sign_a, y_r, sign_r, s_bits, h_bits, pre_ok = batch_verify.prepare(items)
        args = tuple(
            jax.device_put(a, dev)
            for a in (y_a, sign_a, y_r, sign_r, s_bits, h_bits)
        )
        out = jax.block_until_ready(fn(*args))
        assert np.asarray(out).all()

        # sequential (bench.py's method): np.asarray = D2H readback inside
        # the timed region, as a verifier's caller reads its bitmap
        times = []
        for _ in range(4):
            t0 = time.perf_counter()
            np.asarray(fn(*args))
            times.append(time.perf_counter() - t0)
        seq = batch / min(times)

        # pipelined at depth D
        for depth in (2, 4, 8):
            t0 = time.perf_counter()
            for o in [fn(*args) for _ in range(depth)]:
                np.asarray(o)
            warm = time.perf_counter() - t0  # first window includes ramp
            t0 = time.perf_counter()
            for o in [fn(*args) for _ in range(depth)]:
                np.asarray(o)
            dt = time.perf_counter() - t0
            rate = depth * batch / dt
            print(
                f"batch {batch:6d} depth {depth}:  {rate:10.1f} sigs/s  "
                f"({dt / depth * 1e3:7.1f} ms/batch; seq {seq:.1f})"
            )


if __name__ == "__main__":
    main()
