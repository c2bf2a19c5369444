"""Run all BASELINE.json benchmark configs; one JSON line each.

Usage:
    python -m benchmarks.run_all [config-number ...]
    python -m benchmarks.run_all --publish    # also commit artifacts:
        writes benchmarks/results_r<N>.json and fills BASELINE.json
        "published" (VERDICT r1 task 6)

Each config runs in a FRESH subprocess with the persistent XLA compile
cache wired in (``utils.runtime.enable_compile_cache``), so one failed
config can't poison the rest and repeat runs skip the per-bucket compiles.
This parent never imports JAX: at any moment the one config child is the
only process that can hold the chip (the cluster children some configs
spawn are pinned to the CPU — ``testing/process_cluster.py``).

A run whose records are not all ``"platform": "tpu"`` exits 3 — unless the
caller exported ``JAX_PLATFORMS=cpu`` on purpose, in which case every
record is stamped ``"dry_run": true`` (host-only configs and the tier-1
``--smoke`` pass run this way).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG_NAMES = {
    "1": "config1_cluster",
    "2": "config2_microbench",
    "3": "config3_ycsb",
    "4": "config4_viewchange",
    "5": "config5_multichip",
    "6": "config6_bigcluster",
    "7": "config7_wan",
    "8": "config8_scaleout",
    "9": "config9_overload",
    "10": "config10_byzantine",
    "11": "config11_byzclient",
    "12": "config12_durability",
    "13": "config13_scenario",
    "14": "config14_pagedstore",
}

# --smoke: tiny-count kwargs per config — a seconds-scale pass whose only
# purpose is catching benchmark-harness rot at PR time (import errors,
# schema drift, APIs the benchmarks call that the tree no longer has).
# Wired into tier-1 as tests/test_bench_smoke.py; numbers produced under
# these counts are MEANINGLESS and are never published (main() refuses
# --smoke --publish).
SMOKE_KWARGS = {
    "1": dict(n_clients=2, keys_per_client=2, sweeps=1, verifier="cpu"),
    "2": dict(batch_sizes=(256,), iters=1, big_batch=0),
    "3": dict(n=4, f=1, n_ops=64, batch=256),
    "4": dict(n=4, f=1, rounds=1, wan_rounds=1, wan_clients=1, wan_keys=2),
    "5": dict(batch_per_device=256, n_groups=8, iters=1),
    "6": dict(writers=2, writes_per_writer=1, verifier="cpu", shapes=(4,)),
    "7": dict(n_clients=2, keys_per_client=2, sweeps=1, ab_pairs=0),
    # 2 real server processes, 1 interleaved pair: exercises the whole
    # ProcessCluster spawn/READY/drain surface in seconds (the children
    # run the REAL engines — the parent's smoke stubs don't cross the
    # process boundary, and don't need to: child boot cost is import, not
    # XLA compiles, with the inline cpu verifier).
    "8": dict(
        n_servers=4, rf=4, process_counts=(1, 2), n_clients=2,
        keys_per_client=4, sweeps=1, pairs=1, ops_per_txn=2,
    ),
    # the whole open-loop harness in seconds: a handful of sessions, one
    # short knee rung + one overload leg, invariants + table bounds — the
    # numbers are noise; the surface (ramp, generator, knee pick, record
    # schema) is what smoke pins
    "9": dict(
        n_sessions=24, leg_s=0.8, probe_s=0.5, probe_workers=8,
        ladder=(0.6, 1.0), overload_factors=(1.5,), rtt_ms=2.0,
        jitter_ms=0.5, timeout_s=2.0, ramp_batch=12,
    ),
    # one honest + one adversarial leg end-to-end (live ByzantineReplica,
    # invariant checker, evidence aggregation): the whole config-10
    # harness surface in seconds
    "10": dict(
        n_clients=1, keys_per_client=2, sweeps=1, attacks=("silent",),
        timeout_s=1.0, loss_attacks=(), trim_ab=False,
    ),
    # one honest + one byzantine-CLIENT leg + a tiny wedge duel: the whole
    # config-11 harness surface (ByzantineClient driver, defense knobs,
    # wedge probe, record schema) in seconds — a 24-seed sweep can't
    # actually wedge, so the probe numbers are noise by construction
    "11": dict(
        n_clients=1, keys_per_client=2, sweeps=1, attacks=("withhold",),
        timeout_s=1.0, ttl_ms=300.0, wedge_trials=1, wedge_ttl_ms=300.0,
        wedge_deadline_s=2.0, wedge_seeds=24, wedge_seeds_cost=16,
    ),
    # the whole durability surface in seconds: one real-process SIGKILL ->
    # restart -> readback pass (the children run the real engines), a
    # 2-point recovery curve, all three tamper-conviction legs, and one
    # fsync policy vs the memory baseline — curve/latency numbers at these
    # counts are noise; the record schema + acceptance booleans are what
    # smoke pins
    "12": dict(
        min_acked=6, curve_sizes=(6, 12), gap_writes=2,
        fsync_policies=("group",), fsync_writes=6, timeout_s=4.0,
    ),
    # the whole scenario-engine surface in seconds: 2 drawn seeds soaked
    # in-process, the ×2 determinism probe on a cheap seed, and the full
    # injected-violation detect→replay→minimize arc — soak numbers at
    # this count are meaningless; the generator/engine/minimizer APIs and
    # the record schema are what smoke pins
    "13": dict(
        count=2, start=0, workers=1, determinism_seed=4,
        determinism_runs=2, violation_seed=4,
    ),
    # the whole paged-engine surface in seconds: one tiny direct-engine
    # rung per engine (load -> flush -> fault-in reads -> cold recovery),
    # one real-process SIGKILL -> restart -> readback pass on the paged
    # engine, and the page-tamper conviction leg — curve numbers at these
    # counts are noise; the record schema + acceptance booleans are what
    # smoke pins
    "14": dict(
        rungs=(64,), ab_rungs=(64,), value_bytes=64, reads=32,
        min_acked=6, timeout_s=4.0,
    ),
}


def _run_child(key: str) -> None:
    import importlib

    import jax

    from mochi_tpu.utils.runtime import cpu_requested, enable_compile_cache

    enable_compile_cache()

    smoke = os.environ.get("MOCHI_BENCH_SMOKE") == "1"
    if smoke:
        # tiny-count harness-rot pass: never publish full-evidence legs
        os.environ["MOCHI_BENCH_FULL"] = ""
        # Tracing rides every smoke leg (round 15) at sample rate 1.0 —
        # FORCED, not defaulted, and full-rate rather than the 5% default:
        # at smoke's tiny counts a 5% head sample could legitimately mint
        # zero traces, and an inherited MOCHI_TRACE_SAMPLE=0 must not
        # silently hollow out the probe.  The trace_summary stamp below is
        # the tier-1 check that the tracer plumbing still reaches the
        # cluster paths each config drives (tests/test_bench_smoke.py
        # asserts spans were actually recorded on the cluster configs).
        os.environ["MOCHI_TRACE_SAMPLE"] = "1.0"
    else:
        os.environ.setdefault("MOCHI_BENCH_FULL", "1")  # battery: full evidence
    mod = importlib.import_module(f"benchmarks.{CONFIG_NAMES[key]}")
    if smoke:
        # Two levers make "all 7 configs in seconds" possible on a fresh
        # host: (1) jax.disable_jit() — jitted wrappers run their python
        # bodies, so nothing pays an XLA:CPU compile; (2) the DEVICE
        # Ed25519 program (curve.verify_prepared*) is stubbed to all-true
        # — its eager evaluation is a ~100k-dispatch curve ladder, and
        # smoke is a harness-rot detector (imports, prepare packing,
        # quorum plumbing, record schema), not a verdict test: the real
        # engines are differentially tested in tier-1 proper
        # (tests/test_native_ed25519.py, test_crypto_jax.py).
        import jax.numpy as jnp

        from mochi_tpu.crypto import comb, curve

        def _stub_verify(*args, **kwargs):
            return jnp.ones((args[0].shape[0],), dtype=jnp.bool_)

        def _stub_comb(table_flat, key_idx, *args, **kwargs):
            return jnp.ones((key_idx.shape[0],), dtype=jnp.bool_)

        curve.verify_prepared = _stub_verify
        curve.verify_prepared_packed = _stub_verify
        comb.verify_comb_prepared = _stub_comb
        comb._verify_comb_jit = _stub_comb  # the import-time jit wrapper
        with jax.disable_jit():
            rec = mod.run(**SMOKE_KWARGS.get(key, {}))
    else:
        rec = mod.run()
    rec["config"] = key
    # Host-crypto provenance on EVERY record (ISSUE 5 satellite): which
    # engine served host-side Ed25519 during this run — the difference
    # between a comparable write row and a ~20x-inflated one is no longer
    # a prose caveat.
    try:
        from mochi_tpu.crypto.keys import host_crypto_engine

        rec["host_crypto_engine"] = host_crypto_engine()
    except Exception:
        pass
    try:
        rec["platform"] = jax.devices()[0].platform
    except Exception:
        pass
    if cpu_requested():
        rec["dry_run"] = True  # explicit CPU run: no number is a device figure
    # Causal-tracing provenance on EVERY record (round 15): the aggregate
    # over this child's tracers — posture knobs plus span/trace counters.
    # Always a non-empty dict (the knobs are always known), so a missing
    # or empty key means the obs plumbing itself rotted — exactly what
    # tests/test_bench_smoke.py pins.
    try:
        from mochi_tpu.obs.trace import global_summary

        rec["trace_summary"] = global_summary()
    except Exception:
        pass
    print("RESULT_JSON " + json.dumps(rec), flush=True)


def run_one(key: str, timeout_s: float = 1500.0) -> dict:
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run_all", "--child", key],
            cwd=_REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return {"config": key, "metric": CONFIG_NAMES[key], "error": "timeout"}
    out = proc.stdout.decode(errors="replace")
    for line in reversed(out.splitlines()):
        if line.startswith("RESULT_JSON "):
            return json.loads(line[len("RESULT_JSON "):])
    return {
        "config": key,
        "metric": CONFIG_NAMES[key],
        "error": f"rc={proc.returncode} tail={out[-800:]}",
    }


def _wire_taint_preflight() -> None:
    """Harness-rot pin (PR 16): --smoke runs in tier-1, so a fast-path PR
    that renames or bypasses a sanctioned verifier edge without updating
    the wire-taint registry fails HERE at PR time — the registry-rot
    finding (or a fresh unverified flow) turns the smoke leg red before
    any benchmark child spawns.  Same escape hatch as the standing-rules
    lint gate: MOCHI_SKIP_LINT=1 for forensic re-runs."""
    if os.environ.get("MOCHI_SKIP_LINT"):
        return
    sys.path.insert(0, _REPO)
    from mochi_tpu.analysis import core as analysis_core

    result = analysis_core.run(
        [os.path.join(_REPO, "mochi_tpu")], rules=["wire-taint"]
    )
    if not result.clean:
        for finding in result.new:
            print(" !", finding.render(), file=sys.stderr)
        print(
            f"--smoke: wire-taint pass failed ({len(result.new)} finding(s))"
            " — a fast path must register its verifier edge "
            "(mochi_tpu/analysis/wire_taint.py; MOCHI_SKIP_LINT=1 overrides)",
            file=sys.stderr,
        )
        sys.exit(4)


def main(argv) -> None:
    if argv and argv[0] == "--child":
        _run_child(argv[1])
        return
    publish = "--publish" in argv
    if "--smoke" in argv:
        if publish:
            print("--smoke numbers are meaningless; refusing --publish",
                  file=sys.stderr)
            sys.exit(2)
        os.environ["MOCHI_BENCH_SMOKE"] = "1"  # children read it
        argv = [a for a in argv if a != "--smoke"]
        _wire_taint_preflight()
    wanted = [a for a in argv if a != "--publish"] or list(CONFIG_NAMES)
    results = []
    for key in wanted:
        rec = run_one(str(key))
        results.append(rec)
        print(json.dumps(rec), flush=True)
    if publish:
        round_n = os.environ.get("MOCHI_BENCH_ROUND", "02")
        out_path = os.path.join(_REPO, "benchmarks", f"results_r{round_n}.json")
        # merge by config key — a partial invocation (e.g. "run_all 1 2")
        # must not clobber the other configs' records in the results file
        merged = {}
        if os.path.exists(out_path):
            try:
                with open(out_path) as fh:
                    merged = {r.get("config"): r for r in json.load(fh)}
            except (ValueError, OSError):
                merged = {}
        merged.update({r.get("config"): r for r in results})
        with open(out_path, "w") as fh:
            json.dump(
                [merged[k] for k in sorted(merged, key=str)], fh, indent=2
            )
        baseline_path = os.path.join(_REPO, "BASELINE.json")
        with open(baseline_path) as fh:
            baseline = json.load(fh)
        for skipped in merge_published(baseline, results, round_n):
            print(skipped, file=sys.stderr)
        with open(baseline_path, "w") as fh:
            json.dump(baseline, fh, indent=2)
        print(f"published -> {out_path} and BASELINE.json", file=sys.stderr)
    from mochi_tpu.utils.runtime import cpu_requested

    if not cpu_requested():
        bad = [r.get("config") for r in results if r.get("platform") != "tpu"]
        if bad:
            print(
                f"configs {bad} did not run on the TPU (no accelerator, or an "
                "error) and JAX_PLATFORMS=cpu was not set: failing",
                file=sys.stderr,
            )
            sys.exit(3)


def merge_published(baseline: dict, results: list, round_n: str) -> list:
    """Merge run records into ``baseline["published"]``; returns skip notes.

    Merge, don't replace: re-publishing one config must not erase the
    others' entries.  Two guards (round-4 lesson — the scoreboard is what
    the driver and judge read):

    * an errored run never overwrites a good entry;
    * a CPU-fallback run never overwrites a live TPU capture — configs
      2-5 are device benchmarks and the honest best is the committed TPU
      number until a fresh chip run replaces it.
    """
    skipped = []
    published = baseline.setdefault("published", {})
    for r in results:
        prev = published.get(r["config"])
        if r.get("error") and prev and not prev.get("error"):
            skipped.append(
                f"config {r['config']}: errored run NOT published over "
                f"existing good entry"
            )
            continue
        if prev and prev.get("platform") == "tpu" and r.get("platform") != "tpu":
            skipped.append(
                f"config {r['config']}: CPU-fallback run NOT published "
                f"over TPU capture"
            )
            continue
        entry = {
            k: v
            for k, v in r.items()
            if k in ("metric", "value", "unit", "vs_baseline", "error",
                     "platform", "host_crypto_engine",
                     "read_p50_ms", "write_p50_ms")
            and v is not None
        }
        entry["source"] = f"benchmarks/results_r{round_n}.json"
        published[r["config"]] = entry
    return skipped


if __name__ == "__main__":
    main(sys.argv[1:])
