"""Kernel microbenchmark: batched Ed25519 verify throughput on the JAX device.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

This measures ONE kernel, not the served path (ROADMAP A0 builds the
benchmark of the served path; ``chip_smoke.py`` proves that path starts on
the chip).  The metric is device signature-verification throughput
(sigs/sec), peak over several batch sizes, then re-measured with 4-8
batches in flight at the best size (async dispatch overlaps the launch
round trip with device execution, as the loaded BatchingVerifier does).
``vs_baseline`` is the speedup over one host Ed25519 verify per signature
on this host, single-thread; the all-cores denominator is also reported.

It runs in this one process, which therefore owns the chip, and it FAILS
(non-zero exit, no record) when JAX finds no accelerator.  The one exception
is a caller who exported ``JAX_PLATFORMS=cpu`` on purpose: that run is
stamped ``"dry_run": true`` and its numbers are not device figures.

Utilisation: ops/signature come from XLA's ``cost_analysis`` of the compiled
executable; a share of the VPU peak is printed only against a peak MEASURED
on this ``device_kind`` (``scripts/vpu_peak.py`` ->
``benchmarks/vpu_peak.json``), never against an assumed one.

Comb leg (every platform): the known-signer comb program — the engine the
replica hot path routes to by default — is measured alongside the ladder
with its own cost-analysis ops/sig (``ops_per_sig_comb_cost_analysis``),
an interleaved paired A/B vs the ladder, and the chain-vs-tree COMB_IMPL
comparison.  See the ``comb`` key of the record.
"""

from __future__ import annotations

import json
import os
import time

_REPO = os.path.dirname(os.path.abspath(__file__))


def _measured_vpu_peak(device_kind: str):
    """The int32 VPU peak ``scripts/vpu_peak.py`` MEASURED on a device of
    this kind (``benchmarks/vpu_peak.json``), or None.  The Ed25519
    verifier is pure int32 VPU work, so that is the right denominator — and
    without a measurement for this ``device_kind`` there is none: no
    utilisation is printed against an assumed peak."""
    try:
        with open(os.path.join(_REPO, "benchmarks", "vpu_peak.json")) as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return None
    if (
        doc.get("platform") == "tpu"
        and doc.get("device_kind") == device_kind
        and doc.get("value", 0) > 0
    ):
        return float(doc["value"])
    return None


def dispatch_rtt_ms(dev) -> float:
    """Median tiny-op device round trip, ms — the dispatch + readback floor
    every sequential rate divides by (exec + this)."""
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


def time_rates(call, batch, depths=(4, 8)):
    """(sequential rate, {depth: pipelined rate}); every batch is read
    back (np.asarray) inside the timed region, as a verifier's caller reads
    its bitmap.  ONE implementation shared by the headline and comb legs."""
    import numpy as np

    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(call())
        times.append(time.perf_counter() - t0)
    seq = batch / min(times)
    pipe = {}
    for depth in depths:
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            outs = [call() for _ in range(depth)]
            for o in outs:
                np.asarray(o)
            rates.append(depth * batch / (time.perf_counter() - t0))
        pipe[depth] = round(max(rates), 1)
    return seq, pipe


def cost_analysis_ops_per_item(jitted, n_items, *args, **static_kwargs):
    """flops/item from XLA's cost analysis of the compiled executable, or
    None when the backend doesn't expose it — the one extraction shared by
    the ladder, comb and tree legs."""
    try:
        cost = jitted.lower(*args, **static_kwargs).compile().cost_analysis()
        return float(cost.get("flops", 0.0)) / n_items
    except Exception:
        return None


def _measure() -> dict:
    import numpy as np

    import jax

    from mochi_tpu.crypto import batch_verify, keys
    from mochi_tpu.crypto.curve import verify_prepared
    from mochi_tpu.verifier.spi import VerifyItem

    dev = jax.devices()[0]
    kp = keys.generate_keypair()

    def prepared(batch):
        items = []
        for i in range(batch):
            msg = b"bench message %d" % i
            items.append(VerifyItem(kp.public_key, msg, kp.sign(msg)))
        y_a, sign_a, y_r, sign_r, s_bits, h_bits, pre_ok = batch_verify.prepare(items)
        assert pre_ok.all()
        args = tuple(
            jax.device_put(a, dev)
            for a in (y_a, sign_a, y_r, sign_r, s_bits, h_bits)
        )
        return items, args

    # Sweep around batch_verify.MAX_BUCKET (8192); 16384 documents what the
    # production path avoids by chunking.  An explicit CPU dry run keeps
    # batches small: it checks control flow, its numbers mean nothing.
    batches = (1024, 2048, 4096, 8192, 16384) if dev.platform == "tpu" else (256, 1024)
    impls = {}

    # ---- XLA path -------------------------------------------------------
    fn = jax.jit(verify_prepared)
    xla = {"per_batch": {}}
    flops_per_sig = None
    for batch in batches:
        items, args = prepared(batch)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))  # compile + warmup
        compile_s = time.perf_counter() - t0
        assert np.asarray(out).all()
        if flops_per_sig is None:
            flops_per_sig = cost_analysis_ops_per_item(fn, batch, *args) or 0.0
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(fn(*args))  # the readback is inside the timed region
            times.append(time.perf_counter() - t0)
        rate = batch / min(times)
        xla["per_batch"][batch] = {
            "sigs_per_sec": round(rate, 1),
            "ms": round(min(times) * 1e3, 2),
            "compile_s": round(compile_s, 1),
        }
    xla["best"] = max(
        ((b, v["sigs_per_sec"]) for b, v in xla["per_batch"].items()),
        key=lambda kv: kv[1],
    )
    impls["xla"] = xla

    # ---- Pallas kernel --------------------------------------------------
    # Shelved after measurement: Mosaic compile exceeded 15 min vs XLA's
    # 40 s for a slower-or-equal program (pallas_verify.py docstring has the
    # full verdict).  Re-enable explicitly to re-test on newer toolchains.
    if dev.platform == "tpu" and os.environ.get("MOCHI_BENCH_PALLAS") == "1":
        try:
            from mochi_tpu.crypto.pallas_verify import verify_prepared_pallas

            pal = {"per_batch": {}}
            for batch in batches:
                items, args = prepared(batch)
                t0 = time.perf_counter()
                out = jax.block_until_ready(verify_prepared_pallas(*args))
                compile_s = time.perf_counter() - t0
                assert np.asarray(out).all()
                times = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    np.asarray(verify_prepared_pallas(*args))
                    times.append(time.perf_counter() - t0)
                rate = batch / min(times)
                pal["per_batch"][batch] = {
                    "sigs_per_sec": round(rate, 1),
                    "ms": round(min(times) * 1e3, 2),
                    "compile_s": round(compile_s, 1),
                }
            pal["best"] = max(
                ((b, v["sigs_per_sec"]) for b, v in pal["per_batch"].items()),
                key=lambda kv: kv[1],
            )
            impls["pallas"] = pal
        except Exception as exc:  # prove-or-kill: record, don't crash
            impls["pallas"] = {"error": f"{type(exc).__name__}: {exc}"[:500]}
    elif dev.platform == "tpu":
        impls["pallas"] = {
            "skipped": "shelved after measurement: Mosaic compile >15min at "
            "block 128/256 vs 40s XLA compile; XLA path already uses the "
            "limbs-on-lanes layout (pallas_verify.py docstring)"
        }

    best_impl, (best_batch, best_rate) = max(
        ((name, i["best"]) for name, i in impls.items() if "best" in i),
        key=lambda kv: kv[1][1],
    )

    # ---- pipelined steady-state at the best batch -----------------------
    # Sequential timing charges every batch the full dispatch + readback
    # round trip; a loaded verifier keeps several batches in flight (JAX
    # dispatch is async), overlapping it with device execution.  This is
    # the rate the BatchingVerifier/service sustains under load.
    _time_rates = time_rates  # module-level shared helper (see its docstring)

    pipeline = None
    if best_impl == "xla" and dev.platform == "tpu":
        _, args = prepared(best_batch)
        jax.block_until_ready(fn(*args))
        _, pipeline = _time_rates(lambda: fn(*args), best_batch)
        pipe_best = max(pipeline.values())
        if pipe_best > best_rate:
            best_rate = pipe_best

    # ---- known-signer comb path at the best batch -----------------------
    # The cluster's production verify traffic is signed by REGISTERED
    # identities (crypto/comb.py: doubling-free per-signer tables, ~3x
    # fewer field muls than the ladder), and since the comb-first routing
    # landed it IS the engine that carries the replica hot path — so this
    # leg runs on every backend, with:
    #   * XLA cost-analysis ops/sig for the comb program, published next to
    #     the ladder's figure (ops_per_sig_xla_cost_analysis) — the op-count
    #     claim made auditable;
    #   * an INTERLEAVED same-host paired A/B vs the ladder (alternating
    #     launches, per-pair ratios, median) so drift cannot masquerade as
    #     speedup;
    #   * the chain-vs-tree COMB_IMPL A/B (fewest-ops vs shallowest-chain
    #     accumulation) at the same batch.
    # The headline `value` stays the general-path (arbitrary-key) rate.
    comb_rec = None
    comb_flops_per_sig = None
    try:
        from mochi_tpu.crypto import comb as comb_mod

        reg = comb_mod.SignerRegistry(device=dev)
        # no side effects inside asserts: python -O strips them, and a
        # stripped register() would time an empty zero table
        registered = reg.register(kp.public_key)
        if registered is None:
            raise RuntimeError("signer registration failed")
        items, largs = prepared(best_batch)  # same workload as the headline
        (ckey, cy_r, csign_r, cs_sc, ch_sc), cpre_ok = comb_mod._prepare_comb(
            items, np.zeros(len(items), np.int32), None
        )
        if not cpre_ok.all():
            raise RuntimeError("comb prechecks rejected bench items")
        table = reg.device_table(dev)
        cargs = tuple(
            jax.device_put(a, dev)
            for a in (ckey, cy_r, csign_r, cs_sc, ch_sc)
        )
        t0 = time.perf_counter()
        out = np.asarray(comb_mod._verify_comb_jit(table, *cargs))
        comb_compile_s = time.perf_counter() - t0
        if not out.all():
            raise RuntimeError("comb verdicts wrong on valid signatures")
        comb_flops_per_sig = cost_analysis_ops_per_item(
            comb_mod._verify_comb_jit, best_batch, table, *cargs
        )
        comb_seq, cpipe = _time_rates(
            lambda: comb_mod._verify_comb_jit(table, *cargs), best_batch
        )
        comb_best = max(comb_seq, max(cpipe.values()))
        # Interleaved paired A/B against the general ladder: alternating
        # launches in one process on one host, per-pair time ratios —
        # the same discipline the config-1 cluster A/B uses, so thermal /
        # scheduler drift shows up as ratio variance, not as a bogus win.
        ratios = []
        for _ in range(7):
            t0 = time.perf_counter()
            np.asarray(fn(*largs))
            t_ladder = time.perf_counter() - t0
            t0 = time.perf_counter()
            np.asarray(comb_mod._verify_comb_jit(table, *cargs))
            t_comb = time.perf_counter() - t0
            ratios.append(t_ladder / t_comb)
        ranked = sorted(ratios)
        paired = {
            "pairs": len(ratios),
            # launch order, NOT sorted: a monotone drift (host heating,
            # background load) must stay visible in the committed record
            "per_pair_speedup": [round(r, 3) for r in ratios],
            "median_speedup": round(ranked[len(ranked) // 2], 3),
            "discipline": "interleaved same-process launches, per-batch "
            "np.asarray readback, ladder/comb time ratio per pair "
            "(published in launch order; median over the sorted copy)",
        }
        # chain-vs-tree accumulation A/B (static `impl` jit arg — distinct
        # compiled programs; see crypto/comb.py COMB_IMPL).
        tree_rec = None
        try:
            t0 = time.perf_counter()
            tout = np.asarray(
                comb_mod._verify_comb_jit(table, *cargs, impl="tree")
            )
            tree_compile_s = time.perf_counter() - t0
            if not tout.all():
                raise RuntimeError("tree verdicts wrong on valid signatures")
            tree_flops = cost_analysis_ops_per_item(
                comb_mod._verify_comb_jit, best_batch, table, *cargs, impl="tree"
            )
            tree_times = []
            for _ in range(5):
                t0 = time.perf_counter()
                np.asarray(comb_mod._verify_comb_jit(table, *cargs, impl="tree"))
                tree_times.append(time.perf_counter() - t0)
            tree_rec = {
                "sigs_per_sec_sequential": round(best_batch / min(tree_times), 1),
                "ops_per_sig_xla_cost_analysis": (
                    round(tree_flops) if tree_flops else None
                ),
                "compile_s": round(tree_compile_s, 1),
            }
        except Exception as exc:
            tree_rec = {"error": f"{type(exc).__name__}: {exc}"[:300]}
        comb_rec = {
            "sigs_per_sec_sequential": round(comb_seq, 1),
            "pipelined_sigs_per_sec_by_depth": cpipe,
            "best_sigs_per_sec": round(comb_best, 1),
            "speedup_vs_ladder": round(comb_best / best_rate, 2),
            "paired_ab_vs_ladder": paired,
            "impl": comb_mod.COMB_IMPL,
            "ops_per_sig_xla_cost_analysis": (
                round(comb_flops_per_sig) if comb_flops_per_sig else None
            ),
            "ops_per_sig_ladder": round(flops_per_sig or 0.0),
            "tree_impl": tree_rec,
            "compile_s": round(comb_compile_s, 1),
            # single signer = best-case gather locality; the K=16/64
            # cluster-shaped sweep is scripts/comb_bench.py (battery 3f)
            "registered_signers": 1,
            "posture": "registered-signer (cluster cert traffic; the "
            "replica hot path routes here by default since the comb-first "
            "engine landed)",
        }
    except Exception as exc:  # never let the extra leg break the headline
        comb_rec = {"error": f"{type(exc).__name__}: {exc}"[:300]}

    # ---- CPU baselines --------------------------------------------------
    items, _ = prepared(1024)
    sample = items[:256]
    t0 = time.perf_counter()
    for it in sample:
        assert keys.verify(it.public_key, it.message, it.signature)
    cpu_rate = len(sample) / (time.perf_counter() - t0)

    ncores = os.cpu_count() or 1
    cpu_allcores = _allcores_baseline(sample, ncores)

    vpu_peak = _measured_vpu_peak(dev.device_kind)
    mfu = None
    if flops_per_sig and vpu_peak:
        mfu = best_rate * flops_per_sig / vpu_peak
    rtt_ms = dispatch_rtt_ms(dev)

    return {
        "metric": "ed25519_batch_verify_throughput",
        "value": round(best_rate, 1),
        "unit": "sigs/sec",
        "vs_baseline": round(best_rate / cpu_rate, 3),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
        # Which HOST engine produced cpu_*_sigs_per_sec (and every replica-
        # inline verify on this machine): openssl / native-c / pure-python.
        # Machine-readable provenance for the standing wheel-less-host
        # caveat — the "CPU baseline" of a record is not comparable across
        # engines (ISSUE 5 satellite).
        "host_crypto_engine": keys.host_crypto_engine(),
        "impl": best_impl,
        "best_batch": best_batch,
        "pipelined_sigs_per_sec_by_depth": pipeline,
        "comb": comb_rec,
        "impls": impls,
        "cpu_openssl_sigs_per_sec": round(cpu_rate, 1),
        "cpu_allcores_sigs_per_sec": round(cpu_allcores, 1),
        "vs_cpu_allcores": round(best_rate / cpu_allcores, 3) if cpu_allcores else None,
        "cpu_cores": ncores,
        "ops_per_sig_xla_cost_analysis": round(flops_per_sig or 0.0),
        # the comb program's op count published NEXT TO the ladder's: the
        # known-signer engine the replica hot path routes to by default
        "ops_per_sig_comb_cost_analysis": (
            round(comb_flops_per_sig) if comb_flops_per_sig else None
        ),
        "mfu_vs_vpu_peak": round(mfu, 4) if mfu is not None else None,
        "vpu_peak_int_ops": vpu_peak,
        "vpu_peak_source": (
            "measured (benchmarks/vpu_peak.json)" if vpu_peak
            else f"not measured for {dev.device_kind!r}"
        ),
        "dispatch_rtt_ms": rtt_ms,
    }


def _allcores_baseline(sample, ncores: int) -> float:
    """OpenSSL verify rate with every host core busy (process pool)."""
    import multiprocessing as mp

    payload = [(it.public_key, it.message, it.signature) for it in sample]
    try:
        ctx = mp.get_context("fork")
        with ctx.Pool(ncores) as pool:
            t0 = time.perf_counter()
            pool.map(_verify_chunk, [payload] * ncores)
            dt = time.perf_counter() - t0
        return len(payload) * ncores / dt
    except Exception:
        return 0.0


def _verify_chunk(payload):
    from mochi_tpu.crypto import keys

    for pk, msg, sig in payload:
        keys.verify(pk, msg, sig)
    return len(payload)


def main() -> None:
    from mochi_tpu.utils.runtime import device_info, enable_compile_cache

    enable_compile_cache()
    # exits non-zero, printing no record, when JAX found no accelerator and
    # JAX_PLATFORMS=cpu was not exported on purpose
    device = device_info(require_accelerator=True)
    result = _measure()
    if device["platform"] != "tpu":
        result["dry_run"] = True  # explicit CPU run: not a device figure
    print(json.dumps(result))


if __name__ == "__main__":
    main()
