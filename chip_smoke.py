#!/usr/bin/env python3
"""chip_smoke.py — the served path, end to end, on the attached chip.

One deployment, the one ``BASELINE.json`` names: n=64 replicas, rf=64, f=21,
quorum 43, hosted as OS processes through the normal entry points:

* ``python -m mochi_tpu.server`` x (cores - 2) processes, replicas packed onto
  them, ``--verifier remote:...``, ``--storage-dir`` (default WAL engine),
  admission and the fast path on — all defaults;
* ONE ``python -m mochi_tpu.verifier.service --backend tpu --signers-file ...
  --warmup 64,8192``: the only process that imports JAX, so the only owner of
  the chip.  It runs with ``MOCHI_DEVICE_MIN_BATCH=0``: the question here is
  whether the device path works, not whether the routing threshold is right;
* this driver, a plain SDK client that never imports ``jax``.

Phases — each fails the run:

1. cold boot: service READY with both programs (ladder, comb) compiled at
   buckets 64 and 8192, all replicas READY;
2. load + query: >= 1,024 signed 1 KiB PUTs from >= 8 concurrent clients, then
   a seeded sample of reads (value equal, certificate >= quorum grants);
3. differential probe: four seeded batches (64 and 8,192 items; registered
   signers -> comb, unregistered -> ladder; a quarter of each forged, wrong-key
   or malformed) must equal the host engine item for item;
4. SIGKILL every replica, stop the service and wait for its exit, start both
   again: every acked write is read back with a >= quorum-grant certificate,
   no replay entry is convicted, and the re-verification ran on the device;
5. warm boot: the second service boot found its programs in the compile cache.

Standard output is two JSON lines: the full record (phases, counts, set-up
seconds, verifier counters), then — last, and with exactly these keys, which
is what the chip check parses — ``{"ok": true, "device": {"platform": "tpu",
"kind": "...", "count": 1}}``.  Exit code 0 only when every phase held on a
TPU.  Without an accelerator it exits non-zero and prints no result.  The one
exception is a debugging run: ``JAX_PLATFORMS=cpu python chip_smoke.py --tiny``
(n=4, bucket 16, 8 writes), stamped ``"dry_run": true`` — none of its numbers
is a device figure.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import hashlib
import importlib.metadata
import json
import os
import random
import re
import shutil
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

ADMIN_BASE_PORT = 24000  # replica /status ports count up from here
SDK_TIMEOUT_S = 60.0  # per request
# ROADMAP C10: a snapshot's WAL rotation closes the segment under the group
# tick's fsync.  Every n=64 run logs it; it is counted in the result, and it
# is the only ERROR a child may log until C10 is done and this set is empty.
KNOWN_LOG_ERRORS = {"mochi_tpu.storage.durable storage background tick failed"}


class SmokeFailure(Exception):
    """A phase did not hold; the message says which check."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def build_native() -> dict:
    """Drop any ``_mcode``/``_hbatch`` build left on disk (git would not have
    committed it), let the lazy ``cc`` build run on THIS machine, and fail if
    either module is missing: a silent pure-Python codec is not this path."""
    for stale in glob.glob(os.path.join(REPO, "mochi_tpu", "native", "_*.so")):
        os.unlink(stale)
    from mochi_tpu.crypto.keys import host_crypto_engine
    from mochi_tpu.native import get_hbatch, get_mcode

    built = {"mcode": get_mcode() is not None, "hbatch": get_hbatch() is not None}
    check(all(built.values()), f"native modules did not build here: {built}")
    return {"native_built": built, "host_crypto_engine": host_crypto_engine()}


def http_json(port: int, path: str = "/status") -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return json.loads(r.read())


def cache_entries(cache_dir: str) -> int:
    """Compiled programs in the persistent cache (one file each, flat)."""
    try:
        return sum(1 for e in os.scandir(cache_dir) if e.is_file())
    except FileNotFoundError:
        return 0


def child_log_errors(pc) -> dict:
    """ERROR and CRITICAL records in every child's log (both lifetimes: a
    restarted child appends to its log), counted by logger and message."""
    record = re.compile(r"^\d{4}-\d\d-\d\d \S+ (\S+) (?:ERROR|CRITICAL) (.*)$")
    counts: dict = {}
    for sp in [pc.service_process, *pc.processes]:
        with open(sp.log_path, errors="replace") as fh:
            for m in filter(None, map(record.match, fh)):
                what = f"{m.group(1)} {m.group(2)}"
                counts[what] = counts.get(what, 0) + 1
    return counts


def chain_sum(stats: dict, key: str) -> int:
    """Sum an integer counter down a verifier_stats ``inner`` chain."""
    total = 0
    while stats:
        total += int(stats.get(key, 0))
        stats = stats.get("inner")
    return total


def service_summary(status: dict) -> dict:
    """The counters this run asserts on, from the service's /status."""
    v = status["verifier"]  # CachingVerifier -> (Sharded)TpuBatchVerifier
    tpu = v["inner"]
    dev, comb = tpu["device"], tpu["comb"]
    return {
        "requests": status["requests"],
        "items": status["items"],
        "memo_hits": v["hits"],
        "memo_misses": v["misses"],
        "batches_flushed": tpu["batches_flushed"],
        "fallback_batches": chain_sum(v, "fallback_batches"),
        "device_items": dev["device_items"],
        "host_routed_items": dev["host_routed_items"],
        "min_device_items": dev["min_device_items"],
        "ready_buckets": dev["ready_buckets"],
        "failed_buckets": dev["failed_buckets"],
        "comb_ready_buckets": comb["ready_buckets"],
        "comb_failed_buckets": dev["comb_failed_buckets"],
        "registered_signers": comb["registered_signers"],
        "comb_routed_items": comb["items_comb_routed_process_total"],
        "ladder_routed_items": comb["items_ladder_routed_process_total"],
        "comb_dispatches": comb["device_dispatches_process_total"],
        "warmup_seconds": status["device"]["warmup_seconds"],
    }


def value_for(seed: int, i: int, size: int = 1024) -> bytes:
    return random.Random(f"{seed}:value:{i}").randbytes(size)


def key_for(i: int) -> str:
    return f"smoke-{i:05d}"


async def per_client(pc, indices, n_clients: int, op) -> list:
    """``indices`` split over ``n_clients`` concurrent SDK clients, each a
    closed loop of ``await op(client, i)``.  Returns the failed operations:
    any ``op`` that raised — every one of them fails the run."""
    failures = []

    async def worker(share):
        client = pc.client(timeout_s=SDK_TIMEOUT_S)
        for i in share:
            try:
                await op(client, i)
            except Exception as exc:
                failures.append(f"{key_for(i)}: {type(exc).__name__}: {exc}")

    indices = list(indices)
    await asyncio.gather(*(worker(indices[c::n_clients]) for c in range(n_clients)))
    return failures


async def write_all(pc, seed: int, indices, n_clients: int):
    """Single-key signed 1 KiB PUTs.  Returns (acked indices, failures)."""
    from mochi_tpu.client import TransactionBuilder

    acked = []

    async def put(client, i):
        await client.execute_write_transaction(
            TransactionBuilder().write(key_for(i), value_for(seed, i)).build()
        )
        acked.append(i)

    failures = await per_client(pc, indices, n_clients, put)
    return sorted(acked), failures


async def read_all(pc, seed: int, indices, n_clients: int, quorum: int):
    """Read back ``indices``: value equal and a >= quorum-grant certificate.
    Returns (ok count, grant signatures in those certificates, failures)."""
    from mochi_tpu.client import TransactionBuilder

    grants = []

    async def get(client, i):
        res = await client.execute_read_transaction(
            TransactionBuilder().read(key_for(i)).build()
        )
        op = res.operations[0]
        cert = op.current_certificate
        n_grants = len(cert.grants) if cert is not None else 0
        check(op.value == value_for(seed, i), "value differs")
        check(n_grants >= quorum, f"{n_grants} grants < {quorum}")
        grants.append(n_grants)

    failures = await per_client(pc, indices, n_clients, get)
    return len(grants), sum(grants), failures


def probe_batches(pc, seed: int, sizes, tag: str):
    """Seeded probe batches: for each size, one signed by the replica
    identities (registered -> comb) and one by unregistered keys (-> ladder).
    Of every 12 items one is forged (message altered), one carries another
    key's signature, one is malformed — a quarter in all; the public key of a
    registered item stays a registered one, so the routing stays exact."""
    from mochi_tpu.crypto.keys import keypair_from_seed
    from mochi_tpu.verifier.spi import VerifyItem

    registered = list(pc.keypairs.values())
    strangers = [
        keypair_from_seed(hashlib.sha256(f"{seed}:stranger:{k}".encode()).digest())
        for k in range(len(registered))
    ]
    batches = []
    for size in sizes:
        for label, signers in (("comb", registered), ("ladder", strangers)):
            items = []
            for i in range(size):
                kp = signers[i % len(signers)]
                msg = f"probe:{seed}:{tag}:{label}:{size}:{i}".encode()
                sig = kp.sign(msg)
                kind = i % 12
                if kind == 0:  # forged: a valid signature over other bytes
                    msg += b"!"
                elif kind == 1:  # wrong key: signed by the next identity
                    sig = signers[(i + 1) % len(signers)].sign(msg)
                elif kind == 2:  # malformed, alternating flavours
                    sig = sig[:32] + b"\xff" * 32 if i % 24 == 2 else sig[:63]
                items.append(VerifyItem(kp.public_key, msg, sig))
            batches.append((f"{label}-{size}", items))
    return batches


async def run_probe(pc, seed: int, sizes, tag: str) -> dict:
    """Ship the probe batches through the existing RemoteVerifier RPC client
    while the cluster is idle; every bitmap must equal the host engine's
    (``crypto.keys.verify``) item for item."""
    from mochi_tpu.crypto import keys
    from mochi_tpu.verifier.service import RemoteVerifier

    class _NoFallback:
        async def verify_batch(self, items):
            raise SmokeFailure("probe RPC failed (no local fallback in the smoke)")

        async def close(self):
            pass

    registered = {kp.public_key for kp in pc.keypairs.values()}
    rv = RemoteVerifier(
        "127.0.0.1", pc.service_port, timeout_s=120.0, fallback=_NoFallback()
    )
    report = {"batches": {}, "unregistered_items": 0, "items": 0}
    try:
        for name, items in probe_batches(pc, seed, sizes, tag):
            expect = [keys.verify(it.public_key, it.message, it.signature) for it in items]
            got = await rv.verify_batch(items)
            report["batches"][name] = {
                "items": len(items),
                "host_valid": sum(expect),
                # (RemoteVerifier already refused a bitmap of another length)
                "mismatches": sum(1 for a, b in zip(got, expect) if a != b),
            }
            report["items"] += len(items)
            report["unregistered_items"] += sum(
                1 for it in items if it.public_key not in registered
            )
    finally:
        await rv.close()
    report["ok"] = all(b["mismatches"] == 0 for b in report["batches"].values())
    return report


async def run(args, out: dict) -> None:
    from mochi_tpu.testing.process_cluster import ProcessCluster
    from mochi_tpu.utils.runtime import compile_cache_dir

    n, rf = (4, 4) if args.tiny else (64, 64)
    writes, reads, n_clients = (8, 8, 2) if args.tiny else (1024, 128, 8)
    buckets = [16] if args.tiny else [64, 8192]
    probe_sizes = [8, 16] if args.tiny else [64, 8192]
    cores = os.cpu_count() or 1
    n_processes = max(1, min(n, 2 if args.tiny else cores - 2))
    rng = random.Random(args.seed)
    seconds = out["setup_seconds"] = {}
    # the service is started with this process's environment, so this is the
    # directory it will cache in; counted before anything compiles
    out["compile_cache_dir"] = cache_dir = compile_cache_dir()
    out["cache_entries_before"] = cache_entries(cache_dir)

    pc = ProcessCluster(
        n_servers=n,
        rf=rf,
        n_processes=n_processes,
        verifier="service",
        service_backend=args.service_backend,
        service_warmup=",".join(map(str, buckets)),
        admin_base_port=ADMIN_BASE_PORT,
        storage_dir=True,
        seed=args.seed,
        ready_timeout_s=900.0,
        env={
            # the smoke forces the crossover to 0: its question is whether the
            # device path works, not whether the threshold is right (ROADMAP A1)
            "MOCHI_DEVICE_MIN_BATCH": "0",
            # every program the service compiles is cached, however quick its
            # compile, so "the second boot adds no entry" is exact
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        },
    )
    out["min_device_batch_forced"] = 0

    def replica_statuses() -> list:
        return [
            http_json(ADMIN_BASE_PORT + sp.index * n + j)
            for sp in pc.processes
            for j in range(len(sp.server_ids))
        ]

    def phase(name: str) -> dict:
        print(f"[chip_smoke] phase {name}", file=sys.stderr, flush=True)
        out["phases"][name] = {"ok": False}
        return out["phases"][name]

    try:
        # ------------------------------------------------ 1. cold boot
        ph = phase("cold_boot")
        t0 = time.monotonic()
        await pc.start()
        seconds["cold_boot_all_ready"] = round(time.monotonic() - t0, 1)
        status = http_json(pc.service_admin_port)
        entries_boot1 = cache_entries(cache_dir)
        dev = status["device"]
        check(dev is not None, "service reports no device (cpu backend?)")
        out["device"] = {
            "platform": dev["platform"],
            "kind": dev["device_kind"],
            "count": dev["n_devices"],
        }
        check(dev["compile_cache_dir"] == cache_dir, "the service caches somewhere else")
        if args.tiny:
            check(dev["platform"] == "cpu", "--tiny is the CPU dry run")
        else:
            check(dev["platform"] == "tpu", f"platform is {dev['platform']!r}, not tpu")
        cfg = pc.config
        out["shape"] = {
            "n": cfg.n_servers, "rf": cfg.rf, "f": cfg.f, "quorum": cfg.quorum,
            "replica_processes": n_processes, "host_cores": cores,
            "value_bytes": 1024, "writes": writes, "clients": n_clients,
        }
        check(cfg.quorum == 2 * cfg.f + 1, "quorum is not 2f+1")
        if not args.tiny:
            check((cfg.f, cfg.quorum) == (21, 43), f"shape is f={cfg.f} q={cfg.quorum}")
        boot1 = service_summary(status)
        out["cold_compile_seconds"] = boot1["warmup_seconds"]
        check(boot1["registered_signers"] == n, "signer registry is not the cluster")
        check(set(buckets) <= set(boot1["ready_buckets"]), f"ladder not ready at {buckets}")
        check(set(buckets) <= set(boot1["comb_ready_buckets"]), f"comb not ready at {buckets}")
        check(boot1["min_device_items"] == 0, "MOCHI_DEVICE_MIN_BATCH=0 did not reach the service")
        ph.update(ok=True, replicas_ready=n, ready_buckets=boot1["ready_buckets"],
                  comb_ready_buckets=boot1["comb_ready_buckets"])

        # ------------------------------------------------ 2. load + query
        ph = phase("load_query")
        t0 = time.monotonic()
        acked, failures = await write_all(pc, args.seed, range(writes), n_clients)
        seconds["load"] = round(time.monotonic() - t0, 1)
        sample = sorted(rng.sample(acked, min(reads, len(acked))))
        read_ok, _sigs, read_failures = await read_all(
            pc, args.seed, sample, n_clients, cfg.quorum
        )
        failures += read_failures
        ph.update(acked=len(acked), failed=len(failures), reads=len(sample), read_ok=read_ok)
        out["acked"], out["failed_operations"] = len(acked), len(failures)
        check(not failures, f"{len(failures)} failed operations, first: {failures[:3]}")
        check(len(acked) == writes and read_ok == len(sample), "not every operation was acked")
        ph["ok"] = True

        # ------------------------------------------------ 3. differential probe
        ph = phase("probe")
        probe = await run_probe(pc, args.seed, probe_sizes, "first")
        ph.update(probe)
        check(probe["ok"], f"device verdicts differ from the host engine: {probe['batches']}")
        life1 = service_summary(http_json(pc.service_admin_port))
        check(
            life1["ladder_routed_items"] == probe["unregistered_items"],
            f"ladder carried {life1['ladder_routed_items']} items, the probe's "
            f"unregistered items are {probe['unregistered_items']}",
        )

        # ------------------------------------------------ 4. kill, restart, recover
        ph = phase("kill_restart_recover")
        for sp in pc.processes:
            sp.proc.kill()  # SIGKILL: no drain, no final snapshot
        await asyncio.gather(*(sp.proc.wait() for sp in pc.processes))
        rc = await pc.stop_service()
        check(rc == 0, f"service exited {rc} on SIGTERM")
        entries_life1 = cache_entries(cache_dir)
        t0 = time.monotonic()
        await pc.start_service()
        seconds["warm_boot_service_ready"] = round(time.monotonic() - t0, 1)
        entries_boot2 = cache_entries(cache_dir)
        boot2 = service_summary(http_json(pc.service_admin_port))
        check(set(buckets) <= set(boot2["ready_buckets"]), "ladder not ready after restart")
        check(set(buckets) <= set(boot2["comb_ready_buckets"]), "comb not ready after restart")
        t0 = time.monotonic()
        await asyncio.gather(*(pc.restart_replica(sp.server_ids[0]) for sp in pc.processes))
        seconds["replicas_recovered"] = round(time.monotonic() - t0, 1)
        probe2 = await run_probe(pc, args.seed, probe_sizes, "second")
        check(probe2["ok"], f"after restart, verdicts differ: {probe2['batches']}")
        read_ok, grant_sigs, failures = await read_all(
            pc, args.seed, acked, n_clients, cfg.quorum
        )
        out["read_back"], out["distinct_grant_signatures"] = read_ok, grant_sigs
        out["failed_operations"] += len(failures)
        check(not failures, f"{len(failures)} acked writes not read back: {failures[:3]}")
        replicas = replica_statuses()
        replay = [r["storage"]["replay"] for r in replicas]
        out["replay"] = {
            "replicas": len(replicas),
            "entries": sum(int(r["entries"]) for r in replay),
            "convicted": sum(int(r["convicted"]) for r in replay),
        }
        out["replica_fallback_batches"] = sum(
            chain_sum(r["verifier"], "fallback_batches") for r in replicas
        )
        out["replica_remote_batches"] = sum(
            chain_sum(r["verifier"], "remote_batches") for r in replicas
        )
        out["replicas_with_jax_loaded"] = sum(1 for r in replicas if r["jax_loaded"])
        life2 = service_summary(http_json(pc.service_admin_port))
        out["verifier"] = {"first_lifetime": life1, "second_lifetime": life2}
        out["probe"] = {"first_lifetime": probe["batches"], "second_lifetime": probe2["batches"]}
        out["child_log_errors"] = child_log_errors(pc)
        ph.update(read_back=read_ok, service_exit_code=rc, replay_convicted=out["replay"]["convicted"])
        check(len(replicas) == n, "not every replica answered /status")
        check(out["replay"]["convicted"] == 0, f"replay convicted entries: {out['replay']}")
        check(out["replay"]["entries"] > 0, "no replica replayed anything")
        check(out["replicas_with_jax_loaded"] == 0, "a replica process imported jax")
        check(set(out["child_log_errors"]) <= KNOWN_LOG_ERRORS,
              f"a child logged an error: {out['child_log_errors']}")
        check(out["replica_fallback_batches"] == 0, "a replica verified locally instead of on the chip")
        for name, life in (("first", life1), ("second", life2)):
            check(life["host_routed_items"] == 0, f"{name} lifetime: items routed to the host")
            check(life["fallback_batches"] == 0, f"{name} lifetime: service fell back to the CPU")
            check(not life["failed_buckets"] and not life["comb_failed_buckets"],
                  f"{name} lifetime: a bucket failed to compile: {life}")
        check(
            life2["device_items"] - probe2["items"] >= grant_sigs,
            f"second lifetime verified {life2['device_items']} items on the device "
            f"({probe2['items']} of them the probe's), fewer than the "
            f"{grant_sigs} grant signatures written",
        )
        check(life2["ladder_routed_items"] == probe2["unregistered_items"],
              "second lifetime: the ladder carried more than the probe")
        ph["ok"] = True

        # ------------------------------------------------ 5. warm boot
        ph = phase("warm_boot")
        out["warm_compile_seconds"] = boot2["warmup_seconds"]
        ph.update(
            cache_entries_before=out["cache_entries_before"],
            cache_entries_after_first_boot=entries_boot1,
            cache_entries_after_first_lifetime=entries_life1,
            cache_entries_after_second_boot=entries_boot2,
        )
        check(entries_life1 > 0, f"no compile cache entries under {cache_dir}")
        check(entries_boot2 == entries_life1,
              f"second boot added {entries_boot2 - entries_life1} compile cache entries")
        # A first boot that started from an empty cache really compiled, and
        # on the chip the boot that loads must be clearly cheaper.  It is not
        # free: a cache hit still traces and lowers every program (first chip
        # run: 98.8 s warm of 243.3 s cold).  On the CPU dry run, where a
        # compile is little more than that, the two are only reported.
        if out["cache_entries_before"] == 0 and not args.tiny:
            check(
                out["warm_compile_seconds"] < 0.75 * out["cold_compile_seconds"],
                f"warm boot took {out['warm_compile_seconds']}s of the cold "
                f"boot's {out['cold_compile_seconds']}s",
            )
        ph["ok"] = True
    finally:
        save_logs(pc)
        await pc.close()


def save_logs(pc) -> None:
    """Keep the children's logs (the cluster's tmpdir dies with it)."""
    dest = os.path.join(REPO, "chiprun_out", "chip_smoke_logs")
    try:
        os.makedirs(dest, exist_ok=True)
        for sp in [pc.service_process, *pc.processes]:
            if sp is not None and os.path.exists(sp.log_path):
                shutil.copy(sp.log_path, dest)
    except OSError as exc:
        print(f"[chip_smoke] logs not saved: {exc}", file=sys.stderr)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=21)
    parser.add_argument("--tiny", action="store_true",
                        help="CPU dry run (needs an explicit JAX_PLATFORMS=cpu)")
    parser.add_argument("--service-backend", default="tpu",
                        choices=("tpu", "tpu-sharded"))
    args = parser.parse_args()

    pinned = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if args.tiny and pinned != "cpu":
        print("--tiny is the CPU dry run: export JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    if not args.tiny and pinned and "tpu" not in pinned.split(","):
        print(f"JAX_PLATFORMS={pinned}: no accelerator for chip_smoke.py "
              "(the CPU dry run is --tiny)", file=sys.stderr)
        return 2

    def version(dist: str):
        try:
            return importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            return None

    out: dict = {
        "ok": False,
        "dry_run": args.tiny,
        "seed": args.seed,
        "jax": version("jax"),
        "jaxlib": version("jaxlib"),
        "libtpu": version("libtpu"),
        "phases": {},
    }
    t0 = time.monotonic()
    try:
        out.update(build_native())
        asyncio.run(run(args, out))
        check("jax" not in sys.modules, "the driver imported jax")
        out["ok"] = all(p["ok"] for p in out["phases"].values()) and len(out["phases"]) == 5
    except SmokeFailure as exc:
        out["error"] = str(exc)
    except Exception as exc:  # a crash in a phase is a failed phase, with its reason
        out["error"] = f"{type(exc).__name__}: {exc}"
    out["elapsed_seconds"] = round(time.monotonic() - t0, 1)
    out["claim"] = None
    if "error" in out:
        print(f"[chip_smoke] FAILED: {out['error']}", file=sys.stderr)
    if "device" not in out:
        return 1  # no device was ever reported: no result to print
    print(json.dumps(out))
    # the chip check's line: these keys and no others, last on stdout
    print(json.dumps({"ok": out["ok"], "device": out["device"]}), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
