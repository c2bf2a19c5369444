"""Verifier cycle decomposition: where does a verify's 74 ms/batch go?

Times each building block of the ladder AT THE PRODUCTION SHAPE (batch
8192) in isolation — field mul, square, the 4x double run, full add,
madd_niels, both table selects, digit extraction — then the composed
per-iteration body and the full verify, and prints the accounting:

    sum(parts) * 64  vs  measured full verify

If the full program is much slower than the sum of its parts, the bound
is scheduling/fusion across the big graph (the round-2 hypothesis: 1.8%
MFU, schedule-bound); if the parts already add up, the bound is the parts
themselves and the table tells which one to attack.  Run on the chip:

    python scripts/roofline.py [batch]

Every timing reads back through np.asarray and uses marginal differencing
over a fori_loop rep chain so the dispatch round trip cancels out.
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
from jax import lax

from mochi_tpu.crypto import curve, field as F


def timed(fn, *args, reps_lo=50, reps_hi=400):
    """Marginal time per op: (t(hi) - t(lo)) / (hi - lo) over a rep chain."""

    def chain(n):
        @jax.jit
        def run(*a):
            def body(_, carry):
                out = fn(*carry)
                # keep the carry type stable: thread outputs back in where
                # shapes match, else keep originals (measurement only needs
                # the data dependence, not semantic iteration)
                if isinstance(out, tuple) and len(out) == len(carry):
                    return tuple(
                        o if o.shape == c.shape and o.dtype == c.dtype else c
                        for o, c in zip(out, carry)
                    )
                if not isinstance(out, tuple) and out.shape == carry[0].shape:
                    return (out,) + carry[1:]
                return carry

            return lax.fori_loop(0, n, body, args)

        return run

    run_lo, run_hi = chain(reps_lo), chain(reps_hi)
    np.asarray(jax.tree_util.tree_leaves(run_lo(*args))[0])  # compile
    np.asarray(jax.tree_util.tree_leaves(run_hi(*args))[0])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(jax.tree_util.tree_leaves(run_lo(*args))[0])
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(jax.tree_util.tree_leaves(run_hi(*args))[0])
        t_hi = time.perf_counter() - t0
        best = min(best, (t_hi - t_lo) / (reps_hi - reps_lo))
    return best


def main() -> None:
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.integers(0, 1 << 15, (F.NLIMBS, B), dtype=np.int32))
    b = jnp.asarray(rng.integers(0, 1 << 15, (F.NLIMBS, B), dtype=np.int32))
    pt = curve.Point(a, b, F.one((B,)), a)
    idx = jnp.asarray(rng.integers(0, 9, (B,), dtype=np.int32))
    device = device_info(require_accelerator=True)
    dev = jax.devices()[0]
    print(f"device: {device['platform']}, batch {B}")
    if device["platform"] != "tpu":
        print("DRY RUN (JAX_PLATFORMS=cpu): not a device figure", flush=True)

    parts = {}
    parts["mul"] = timed(F.mul, a, b)
    parts["square"] = timed(F.square, a)
    parts["double_x4"] = timed(
        lambda *p: tuple(curve.double(curve.double(curve.double(curve.double(curve.Point(*p)))))),
        *pt,
    )
    parts["add_full"] = timed(
        lambda x, y, z, t: tuple(curve.add(curve.Point(x, y, z, t), curve.Point(x, y, z, t))),
        *pt,
    )
    b_tab = tuple(
        jnp.asarray(t)[..., None] for t in (curve._B_TAB_YPX, curve._B_TAB_YMX, curve._B_TAB_XY2D)
    )

    # The select benchmarks must thread the carry through the index (a
    # constant idx makes the lookup loop-invariant and XLA deletes the
    # body — observed as negative marginal time on the first cut).
    def select_bench(tab):
        def body(acc, i):
            j = (i + acc[0].astype(jnp.int32)) % curve.N_TABLE
            sel = curve.select_entry(tab, j, curve.N_TABLE)
            total = sel[0]
            for coord in sel[1:]:  # keep EVERY coordinate's select live
                total = total + coord
            return acc + total, i

        return body

    parts["select_b(9x3)"] = timed(select_bench(b_tab), a, idx)
    a_tab = curve._small_multiples_table(pt)
    parts["select_a(9x4)"] = timed(select_bench(a_tab), a, idx)
    parts["madd_niels"] = timed(
        lambda x, y, z, t: tuple(
            curve.madd_niels(curve.Point(x, y, z, t), b_tab[0][0], b_tab[1][0], b_tab[2][0])
        ),
        *pt,
    )

    # full verify at the same batch for the composition check
    from mochi_tpu.crypto import batch_verify, keys
    from mochi_tpu.verifier.spi import VerifyItem

    kp = keys.generate_keypair()
    items = [VerifyItem(kp.public_key, b"r%d" % i, kp.sign(b"r%d" % i)) for i in range(B)]
    batch_verify.verify_batch(items)  # compile
    t_full = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        batch_verify.verify_batch(items)
        t_full = min(t_full, time.perf_counter() - t0)

    print(f"\n{'part':>14}  us/op   est us/iter (x count)")
    # per ladder iteration: 1x double_x4, 1x select_a, 1x add_full,
    # 1x select_b, 1x madd — mul/square are INSIDE those, listed for context
    iter_parts = {
        "double_x4": 1,
        "select_a(9x4)": 1,
        "add_full": 1,
        "select_b(9x3)": 1,
        "madd_niels": 1,
    }
    est_iter = 0.0
    for name, us in sorted(parts.items(), key=lambda kv: -kv[1]):
        line = f"{name:>14}  {us*1e6:7.2f}"
        if name in iter_parts:
            est_iter += us * iter_parts[name]
            line += f"   {us*1e6*iter_parts[name]:7.2f}"
        print(line)
    est_ladder = est_iter * 64
    print(f"\nsum-of-parts ladder estimate: {est_ladder*1e3:.2f} ms")
    print(f"measured full verify:         {t_full*1e3:.2f} ms  ({B/t_full:.0f} sigs/s)")
    ratio = t_full / est_ladder if est_ladder else float("nan")
    print(
        f"full/parts ratio: {ratio:.2f}  "
        f"({'schedule/fusion-bound: the composed graph is slower than its parts' if ratio > 1.5 else 'parts-bound: attack the biggest row above'})"
    )

    # ---- comb decomposition (crypto/comb.py) ----------------------------
    # Per comb iteration: 1x signer-row slice (of the upfront gather), 2x
    # madd, 1x select_b — no doublings.  The gather is timed whole (64
    # windows at once, as the kernel issues it) then amortized per window.
    from mochi_tpu.crypto import comb as comb_mod

    reg = comb_mod.SignerRegistry()
    if reg.register(kp.public_key) is None:
        raise RuntimeError("registration failed")
    table = reg.device_table(dev)
    kidx = jnp.zeros((B,), jnp.int32)
    hmag = jnp.asarray(rng.integers(0, 9, (64, B), dtype=np.int32))

    def gather_bench(acc, i):
        win = jnp.arange(comb_mod.N_WINDOWS, dtype=jnp.int32)[:, None]
        # thread the carry into the indices so the gather stays live
        fi = (kidx + acc[0, :1].astype(jnp.int32))[None, :] * (
            comb_mod.N_WINDOWS * comb_mod.N_ENTRIES
        ) + win * comb_mod.N_ENTRIES + hmag
        rows = jnp.take(table, fi, axis=0, mode="clip")
        return acc + rows.sum(axis=0).T.astype(jnp.int32)[: F.NLIMBS], i

    t_gather = timed(gather_bench, a, idx, reps_lo=10, reps_hi=60)
    print(f"\ncomb upfront gather (64 windows): {t_gather*1e6:.2f} us "
          f"({t_gather*1e6/64:.2f} us/window)")
    est_comb = 64 * (
        2 * parts["madd_niels"] + parts["select_b(9x3)"]
    ) + t_gather
    print(f"sum-of-parts comb estimate: {est_comb*1e3:.2f} ms "
          f"(+ decompress, shared with the ladder)")
    t_comb = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = batch_verify.verify_batch(items, registry=reg)
        t_comb = min(t_comb, time.perf_counter() - t0)
    assert all(out)
    print(f"measured full comb verify:  {t_comb*1e3:.2f} ms  ({B/t_comb:.0f} sigs/s)")
    cratio = t_comb / est_comb if est_comb else float("nan")
    print(f"comb full/parts ratio: {cratio:.2f}")


if __name__ == "__main__":
    main()
