"""On-chip microbenchmark of field-multiply variants.

The full verifier runs ~3,600 field muls per batch; at batch 4096 the
measured 175 ms/batch is consistent with the multiply being HBM-bound on
its materialized intermediates (the (17,17,B) partial-product tensor and
the pad/flatten/reshape column skew are fusion barriers), not VPU-bound.
This script times each candidate column-skew implementation and the
dedicated square on the real chip so the choice in
``mochi_tpu.crypto.field`` is a measurement, not a guess.

Usage:  python scripts/mul_microbench.py [B]   (default 4096)
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

import jax.numpy as jnp

from mochi_tpu.crypto import field as F

B = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
REPS = 200  # chained muls inside one jit, so dispatch cost amortizes

rng = np.random.default_rng(0)
a_np = rng.integers(0, F.LOOSE, size=(F.NLIMBS, B), dtype=np.int32)
b_np = rng.integers(0, F.LOOSE, size=(F.NLIMBS, B), dtype=np.int32)


def chain(mul_fn):
    def run(a, b):
        def body(i, ab):
            a, b = ab
            return (mul_fn(a, b), a)

        return jax.lax.fori_loop(0, REPS, body, (a, b))[0]

    return jax.jit(run)


def bench(name, mul_fn):
    fn = chain(mul_fn)
    a = jnp.asarray(a_np)
    b = jnp.asarray(b_np)
    t0 = time.perf_counter()
    out = fn(a, b)
    out.block_until_ready()
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn(a, b).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    per_mul_us = best / REPS * 1e6
    # effective HBM bytes if bound by 2 inputs + 1 output per mul
    min_bytes = 3 * F.NLIMBS * B * 4
    print(
        f"{name:28s} {per_mul_us:9.1f} us/mul   "
        f"{min_bytes / (best / REPS) / 1e9:7.1f} GB/s-eff   "
        f"(compile {compile_s:.1f}s)"
    )
    return out


def main():
    device = device_info(require_accelerator=True)
    print(f"device: {device['platform']} {device['device_kind']}  B={B}")
    if device["platform"] != "tpu":
        print("DRY RUN (JAX_PLATFORMS=cpu): not a device figure", flush=True)

    orig_skew = F.SKEW_IMPL
    ref = None
    for name in F.available_skews():
        F.SKEW_IMPL = name
        out = bench(f"mul skew={name}", F.mul)
        out_c = np.asarray(jax.jit(F.canonical)(out))
        if ref is None:
            ref = out_c
        else:
            assert np.array_equal(ref, out_c), f"skew={name} MISMATCH"
    F.SKEW_IMPL = orig_skew  # square comparison runs against the production mul

    sq = bench("square (dedicated)", lambda a, b: F.square(a))
    sq_ref = bench("square (via mul)", lambda a, b: F.mul(a, a))
    assert np.array_equal(
        np.asarray(jax.jit(F.canonical)(sq)), np.asarray(jax.jit(F.canonical)(sq_ref))
    ), "square MISMATCH"
    print("all variants agree")


if __name__ == "__main__":
    main()
