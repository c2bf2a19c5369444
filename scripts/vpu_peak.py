"""Measure the device's sustained int32 multiply-add peak.

No int32 VPU peak is published for the chip (``perf/peaks.json`` holds a
null), so a ``kernel.*`` roofline share has no denominator until one is
measured on the device itself.  This microbenchmark measures it:

- workload: ``x = x * m + c`` on a VMEM-resident int32 block, iterated
  inside one compiled program via ``lax.fori_loop`` with an 8-deep unrolled
  body (amortizes loop/control overhead to <1%).  Both the multiply and the
  add are independent int32 VPU lane ops -> 2 ops/element/unroll-step.
- the loop value is data-dependent (x feeds back), so XLA cannot fold or
  strength-reduce the chain; m is chosen odd so the values never collapse.
- per-call work is sized to tens of milliseconds, and the measured
  dispatch + readback floor (:func:`dispatch_rtt_ms`) is SUBTRACTED from
  the timed region; both raw and corrected rates are reported, and a call
  that is mostly round trip is flagged instead of inflating the peak.
- shapes: a small sweep (elements x iterations held ~constant-work) because
  the true peak depends on how XLA vectorizes the loop body; we report the
  max and the full table.
- timing: np.asarray readback of a 128-element checksum slice inside the
  timed region.

Writes ``chiprun_out/vpu_peak.json`` (keyed by ``device_kind``; the
directory a chip call brings back) and prints one ``VPU_PEAK_JSON`` line.

Usage: python scripts/vpu_peak.py
Needs the chip.  Under an explicit ``JAX_PLATFORMS=cpu`` it runs a tiny dry
run and writes nothing: a host-core number must never become the chip's
denominator.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

UNROLL = 8  # madds per fori_loop step: control overhead /8


def dispatch_rtt_ms(dev) -> float:
    """Median tiny-op device round trip, ms: the dispatch + readback floor
    under every timed call."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    x = jax.device_put(jnp.zeros((8,), jnp.int32), dev)
    f = jax.jit(lambda v: v + 1)
    np.asarray(f(x))  # compile outside the timed region
    times = []
    for _ in range(21):
        t0 = time.perf_counter()
        np.asarray(f(x))
        times.append(time.perf_counter() - t0)
    times.sort()
    return round(times[len(times) // 2] * 1e3, 3)


def _make_kernel(iters: int):
    import jax
    from jax import lax

    @functools.partial(jax.jit, static_argnums=())
    def kernel(x, m, c):
        def body(_, v):
            for _ in range(UNROLL):
                v = v * m + c
            return v

        return lax.fori_loop(0, iters, body, x)

    return kernel


def measure() -> dict:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from mochi_tpu.utils.runtime import device_info, enable_compile_cache

    enable_compile_cache()
    device_info(require_accelerator=True)  # needs the chip (or an explicit CPU pin)
    dev = jax.devices()[0]

    rtt_ms = dispatch_rtt_ms(dev)
    print(f"[vpu_peak] dispatch + readback floor: {rtt_ms} ms", flush=True)

    # (elements, fori_loop iters): each config does 2 * el * iters * UNROLL
    # int ops per call — ~3.4e10, tens of milliseconds on a v5e, so the
    # round trip is a small correction (and it IS corrected).
    # Elements kept VMEM-resident (<= 2 MiB of int32); several shapes
    # because the loop-carried dependence chain limits ILP at small widths
    # and the vector register allocation shifts with shape.
    configs = [
        (16 * 1024, 131072),
        (64 * 1024, 32768),
        (256 * 1024, 8192),
        (512 * 1024, 4096),
    ]
    if dev.platform != "tpu":  # CPU dry-run (tests): keep it fast
        configs = [(16 * 1024, 64)]

    table = {}
    for el, iters in configs:
        kern = _make_kernel(iters)
        x = jax.device_put(jnp.arange(el, dtype=jnp.int32), dev)
        m = jax.device_put(jnp.int32(1103515245), dev)  # odd -> no collapse
        c = jax.device_put(jnp.int32(12345), dev)
        t0 = time.perf_counter()
        out = kern(x, m, c)
        np.asarray(out[:128])
        compile_s = time.perf_counter() - t0
        ops_per_call = 2 * el * iters * UNROLL
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(kern(x, m, c)[:128])
            times.append(time.perf_counter() - t0)
        t_raw = min(times)
        # Subtract the RTT floor, but never trust a call that is mostly
        # round trip: if compute doesn't dominate, flag instead of inflate.
        t_comp = t_raw - rtt_ms / 1e3
        rtt_dominated = t_comp <= t_raw / 2
        if t_comp <= 0:
            t_comp = t_raw
        rate = ops_per_call / t_comp
        table[f"{el}x{iters}"] = {
            "int_ops_per_sec": rate,
            "int_ops_per_sec_raw": ops_per_call / t_raw,
            "ms": round(t_raw * 1e3, 2),
            "rtt_dominated": rtt_dominated,
            "compile_s": round(compile_s, 1),
        }
        print(
            f"[vpu_peak] {el}x{iters}: {rate/1e12:.3f} Tint-op/s "
            f"({t_raw*1e3:.1f} ms/call raw{' RTT-DOMINATED' if rtt_dominated else ''})",
            flush=True,
        )

    usable = [v["int_ops_per_sec"] for v in table.values() if not v["rtt_dominated"]]
    peak = max(usable) if usable else max(v["int_ops_per_sec_raw"] for v in table.values())
    rec = {
        "metric": "vpu_int32_madd_peak",
        "value": peak,
        "unit": "int_ops/sec",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "unroll": UNROLL,
        "dispatch_rtt_ms": rtt_ms,
        "all_configs_rtt_dominated": not usable,
        "table": table,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if dev.platform == "tpu" and usable:
        out_path = os.path.join(_REPO, "chiprun_out", "vpu_peak.json")
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(rec, fh, indent=1)
        os.replace(tmp, out_path)
        print(f"[vpu_peak] wrote {out_path}", flush=True)
    print("VPU_PEAK_JSON " + json.dumps(rec), flush=True)
    return rec


if __name__ == "__main__":
    measure()
