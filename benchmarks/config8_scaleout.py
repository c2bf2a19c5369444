"""Config 8: shard-per-core scale-out — aggregate signed-PUT ops/s vs processes.

Every cluster number before round 10 came out of ONE process (the
``VirtualCluster`` posture: all replicas + clients time-sliced over one
event loop, i.e. one core).  This config runs the deployment the token-ring
L2 layer exists for: the same cluster spread over 1, 2, ..., N real server
processes (``testing/process_cluster.ProcessCluster`` →
``python -m mochi_tpu.server``), a signed PUT-only workload driven from the
parent process, and the aggregate throughput ladder measured per rung.

Measurement discipline (the committed-A/B house rules since r06, tightened
for this container's measured minute-scale tenancy swings of ±2-3x):

* the acceptance A/B boots BOTH postures once — the single-process
  VirtualCluster (replicas + clients on one event loop: the posture of
  every previously published cluster number) and the N = cores-1 process
  deployment — and alternates timed one-sweep chunks between them with
  order flipping per round, so each per-round ratio compares the postures
  inside the same ~2-second host window;
* the ladder curve (single-process rung + every process rung, including
  the oversubscribed N = cores one) runs as separated full legs, several
  rounds, medians reported with all samples;
* per-process CPU (utime+stime from /proc) is read across each timed
  window, so the record carries the cluster-wide replica CPU cost per
  transaction — the constant the 100k-ops/s extrapolation scales from;
* every sequence is preceded by a full-core warmup spin: this container's
  effective CPU speed ramps ~3x over the first ~10 s of sustained load.

The ``scaling_refit`` section re-derives the BASELINE.json scaling model
with every host-side constant from this run (dedicated 1-op-txn legs in
the sidecar posture split replica base+sign from memoized verify work).
It sizes the verify pool in host cores only: no sigs/s has been taken on
the attached chip, and the record says so.
"""

from __future__ import annotations

import asyncio
import os
import statistics
import time
from typing import Dict, List, Optional, Sequence

SEED = 10
NORTH_STAR = {"n": 64, "rf": 64, "f": 21, "quorum": 43, "target_ops_s": 100_000}


def _host_warmup(seconds: float = 8.0) -> None:
    """Spin every core for a few seconds before the timed sequence: this
    container's effective CPU speed ramps ~3x over the first ~10 s of
    sustained load (burst scheduling / frequency ramp, measured r10), so
    an unwarmed first leg measures the slow ramp, not the system."""
    if os.environ.get("MOCHI_BENCH_SMOKE") == "1":
        return  # smoke is a harness-rot pass; its numbers are meaningless
    import subprocess
    import sys as _sys

    body = (
        "import time\nt = time.perf_counter()\n"
        f"while time.perf_counter() - t < {seconds}:\n"
        "    x = sum(i * i for i in range(1000))\n"
    )
    procs = [
        subprocess.Popen([_sys.executable, "-c", body])
        for _ in range(os.cpu_count() or 1)
    ]
    for p in procs:
        p.wait()


def _crypto_microbench(iters: int = 64) -> Dict[str, float]:
    """Fresh-input host Ed25519 verify/sign cost (us/op) on THIS host —
    the crypto constants of the refit, measured in the same run they
    parameterize (lru caches defeated by distinct messages)."""
    from mochi_tpu.crypto import keys

    kp = keys.generate_keypair()
    msgs = [b"c8-%d" % i for i in range(iters)]
    sigs = [kp.sign(m) for m in msgs]
    t0 = time.perf_counter()
    for m, s in zip(msgs, sigs):
        keys.verify(kp.public_key, m, s)
    verify_us = (time.perf_counter() - t0) / iters * 1e6
    fresh = [b"c8s-%d" % i for i in range(iters)]
    t0 = time.perf_counter()
    for m in fresh:
        kp.sign(m)
    sign_us = (time.perf_counter() - t0) / iters * 1e6
    return {"verify_us": round(verify_us, 1), "sign_us": round(sign_us, 1)}


def _shard_local_keys(
    config, n_clients: int, keys_per_client: int, seed: int
) -> List[List[str]]:
    """Per-client key lists, every client's keys inside ONE token-ring
    replica set, clients dealt round-robin over the distinct shards.

    This is the shard-aware batching shape the L2 layer rewards: a
    client's batched PUT touches exactly its shard's rf replicas (one
    certificate, quorum MultiGrant signatures REGARDLESS of batch size),
    while different clients' shards land on different replica subsets —
    so aggregate load spreads over every server and per-op cost amortizes
    identically in both postures.  Keys are found by probing the stable
    hash (a few sha512s per key), so the assignment is deterministic
    given the seed."""
    shards: List[frozenset] = []
    seen = set()
    for t in range(len(config.token_owners)):
        s = frozenset(config.replica_set_for_token(t))
        if s not in seen:
            seen.add(s)
            shards.append(s)
    out: List[List[str]] = []
    for ci in range(n_clients):
        target = shards[ci % len(shards)]
        keys: List[str] = []
        probe = 0
        while len(keys) < keys_per_client:
            k = f"c8-{seed}-{ci}-{probe}"
            probe += 1
            if frozenset(config.replica_set_for_key(k)) == target:
                keys.append(k)
        out.append(keys)
    return out


async def _drive_puts(
    clients, keys_by_client: List[List[str]], sweeps: int, ops_per_txn: int,
    on_timed_start=None,
) -> Dict:
    """The shared signed-PUT workload: every transaction is a batched PUT
    of ``ops_per_txn`` shard-local keys; warm sweep off the clock, then
    the timed sweeps.  Returns ops (PUTs)/wall/p50; callers add their
    posture's CPU view."""
    from mochi_tpu.client.txn import TransactionBuilder
    from mochi_tpu.utils.runtime import reset_gc_debt

    write_lat: List[float] = []

    def txns_for(ci: int, val: bytes):
        keys = keys_by_client[ci]
        for i in range(0, len(keys), ops_per_txn):
            tb = TransactionBuilder()
            for k in keys[i : i + ops_per_txn]:
                tb.write(k, val)
            yield tb.build()

    # Warm off the clock: sessions, connections, first-contact key
    # material, and the keys' first certificates.
    async def warm(ci: int):
        for txn in txns_for(ci, b"warm"):
            await clients[ci].execute_write_transaction(txn)

    await asyncio.gather(*[warm(i) for i in range(len(clients))])
    reset_gc_debt()  # GC over the live graph must not land in the window
    if on_timed_start is not None:
        on_timed_start()  # CPU baselines read here exclude the warm phase

    async def worker(ci: int):
        client = clients[ci]
        for s in range(sweeps):
            val = b"v%d" % s
            for txn in txns_for(ci, val):
                t0 = time.perf_counter()
                await client.execute_write_transaction(txn)
                write_lat.append(time.perf_counter() - t0)

    t0 = time.perf_counter()
    await asyncio.gather(*[worker(i) for i in range(len(clients))])
    wall = time.perf_counter() - t0
    ops = sum(len(k) for k in keys_by_client) * sweeps
    write_lat.sort()
    return {
        "ops": ops,
        "txns": len(write_lat),
        "ops_per_txn": ops_per_txn,
        "wall_s": round(wall, 3),
        "put_ops_s": round(ops / wall, 1),
        "txn_p50_ms": round(write_lat[len(write_lat) // 2] * 1e3, 2)
        if write_lat
        else None,
    }


async def _single_process_leg(
    n_servers: int, rf: int, n_clients: int, keys_per_client: int, sweeps: int,
    ops_per_txn: int, seed: int = SEED,
) -> Dict:
    """The historical posture every published cluster number ran in: ONE
    process, one event loop, replicas AND clients time-sliced together
    (VirtualCluster).  This is the A/B baseline the scale-out acceptance
    is judged against."""
    from mochi_tpu.testing import VirtualCluster

    self0 = time.process_time()
    async with VirtualCluster(n_servers, rf=rf) as vc:
        clients = [vc.client(timeout_s=20.0) for _ in range(n_clients)]
        keys = _shard_local_keys(vc.config, n_clients, keys_per_client, seed)
        rec = await _drive_puts(clients, keys, sweeps, ops_per_txn)
    rec.update(
        {
            "posture": "single-process (VirtualCluster: replicas + clients "
            "on one event loop)",
            "processes": 0,  # ladder x-axis: 0 = everything in this process
            "process_cpu_s": round(time.process_time() - self0, 3),
        }
    )
    return rec


async def _put_leg(
    n_processes: int,
    n_servers: int,
    rf: int,
    n_clients: int,
    keys_per_client: int,
    sweeps: int,
    ops_per_txn: int,
    seed: int = SEED,
    verifier: str = "cpu",
) -> Dict:
    """One process-posture ladder leg: boot the cluster at ``n_processes``
    server processes, drive signed PUTs from this (client) process, return
    aggregate ops/s + per-process CPU over the timed window.

    ``verifier="cpu"`` (ladder default) keeps verification inline on each
    replica's core — the best-aggregate deployment on a small host.
    ``verifier="service"`` adds the shared memoizing sidecar process (the
    production verifier-pool topology): the refit legs use it because its
    CPU split measures the offload model's constants directly — replica
    processes then carry base+sign (+RPC client cost, real in the sidecar
    posture), the service carries the memoized unique-signature work."""
    from mochi_tpu.testing import ProcessCluster

    async with ProcessCluster(
        n_servers=n_servers, rf=rf, n_processes=n_processes, verifier=verifier,
        pin_cores=True,
    ) as pc:
        clients = [pc.client(timeout_s=20.0) for _ in range(n_clients)]
        keys = _shard_local_keys(pc.config, n_clients, keys_per_client, seed)
        window = {}

        def mark():  # CPU baseline at the timed window's start, post-warm
            window["cpu0"] = pc.cpu_seconds()
            window["self0"] = time.process_time()

        rec = await _drive_puts(clients, keys, sweeps, ops_per_txn, on_timed_start=mark)
        cpu0, self0 = window["cpu0"], window["self0"]
        cpu1 = pc.cpu_seconds()
        client_cpu = time.process_time() - self0
        pc.check_alive()
    txns = rec["txns"]
    deltas = {k: round(cpu1[k] - cpu0.get(k, 0.0), 3) for k in cpu1}
    service_cpu = deltas.pop("verifier-service", None)
    rec.update(
        {
            "posture": f"process (client process + server processes, {verifier})",
            "processes": n_processes,
            "verifier": verifier,
            "replica_cpu_s": deltas,
            "replica_cpu_s_total": round(sum(deltas.values()), 3),
            "replica_cpu_us_per_txn_cluster": round(
                sum(deltas.values()) / txns * 1e6, 1
            ),
            "client_cpu_s": round(client_cpu, 3),
            "client_cpu_us_per_txn": round(client_cpu / txns * 1e6, 1),
        }
    )
    if service_cpu is not None:
        rec["service_cpu_s"] = service_cpu
        rec["service_cpu_us_per_txn"] = round(service_cpu / txns * 1e6, 1)
    return rec


def _refit(
    ladder: List[Dict],
    ab: Dict,
    crypto: Dict[str, float],
    rf: int,
    quorum: int,
    ab_n: int,
) -> Dict:
    """Re-derive the 100k-ops/s scaling model with every constant from
    THIS run (the r10 acceptance: no inherited anchors on the host side).

    The measured deployment is the sidecar posture: replica processes
    keep base protocol work + their own grant SIGN on-core, and all grant
    VERIFICATION rides the shared memoizing service — so the ladder's CPU
    deltas measure the two model constants directly:

    * ``replica_cpu_us_per_txn_cluster`` (service posture) = rf x
      (base + sign), with no verify inside — the per-replica core cost
      the n=64 projection scales;
    * ``service_cpu_us_per_txn`` = the memoized unique-signature work of
      one transaction (expected ~quorum verifies + RPC framing; the
      measured/verify_us ratio is the in-record memoization evidence).

    The n=64 projection keeps the replica base per-process (each replica
    still handles two requests per txn; the certificate's size growth
    with quorum is NOT modeled — the projection is a floor and says so),
    swaps in the n=64 quorum for verification demand, and de-rates ideal
    cores by the parallel efficiency this ladder actually measured.
    """
    max_legs = [r for r in ladder if r.get("processes") == ab_n]
    t_cluster_us = statistics.median(
        r["replica_cpu_us_per_txn_cluster"] for r in max_legs
    )
    service_us = statistics.median(r["service_cpu_us_per_txn"] for r in max_legs)
    replica_us = t_cluster_us / rf  # base + sign, verify offloaded
    base_us_per_replica = max(0.0, replica_us - crypto["sign_us"])
    ns = NORTH_STAR
    # Parallel efficiency: measured aggregate speedup over the ideal —
    # the process posture can use every host core where the single-process
    # posture had one, so ideal = host cores / 1.
    ideal_speedup = min(ab_n + 1, os.cpu_count() or (ab_n + 1))
    efficiency = min(1.0, ab["median_speedup"] / ideal_speedup)

    def cores_for(us: float) -> Dict[str, float]:
        cluster_us = ns["rf"] * us
        ideal = ns["target_ops_s"] * cluster_us / 1e6
        return {
            "replica_us_per_txn_n64_floor": round(us, 1),
            "ideal_replica_cores_at_100k": round(ideal, 0),
            "replica_cores_at_100k_derated": round(
                ideal / max(efficiency, 1e-9), 0
            ),
        }

    # Posture A — verifier-offload (MEASURED here; roadmap items 1+3
    # composed): replica cores carry base + sign only.  Verification
    # demand is protocol arithmetic (unique sigs/s = rate x quorum); the
    # verification pool is sized in host cores from THIS run's verify_us.
    # A pool in chips needs a measured device rate (ROADMAP A0/A4); until
    # there is one the record says "not measured".
    offload = cores_for(replica_us)
    offload["device_unique_sigs_per_s_at_100k"] = (
        ns["target_ops_s"] * ns["quorum"]
    )
    offload["verify_pool_host_cores_at_100k"] = round(
        ns["target_ops_s"] * ns["quorum"] * crypto["verify_us"] / 1e6, 0
    )
    offload["verify_pool_chips_at_100k"] = (
        "not measured: no sigs/s has been taken on the attached chip "
        "(ROADMAP A0/A4), so the verify pool is sized in host cores only"
    )
    # Posture B — host-inline verify (no sidecar): each replica pays
    # quorum-1 foreign grant verifies on its own core (own grant is the
    # _own_grant_sigs byte-compare), and the cluster re-verifies each
    # signature at every replica (no cross-process memo).
    inline = cores_for(
        base_us_per_replica
        + crypto["sign_us"]
        + (ns["quorum"] - 1) * crypto["verify_us"]
    )
    return {
        "shape": dict(ns),
        "anchors": {
            "replica_cpu_us_per_txn_cluster_rf4": round(t_cluster_us, 1),
            "replica_us_per_txn": round(replica_us, 1),
            "base_us_per_replica": round(base_us_per_replica, 1),
            "service_cpu_us_per_txn_rf4": round(service_us, 1),
            "service_effective_verifies_per_txn": round(
                service_us / crypto["verify_us"], 2
            ),
            "host_verify_us": crypto["verify_us"],
            "host_sign_us": crypto["sign_us"],
            "parallel_efficiency_measured": round(efficiency, 3),
            "source": "this record (benchmarks/results_r10.json): every "
            "constant measured in-run, multi-process posture",
        },
        "formula": "replica cores = 100k txn/s x rf x replica_us / 1e6 / "
        "efficiency; verify pool = 100k x quorum x verify_us / 1e6 host "
        "cores; inline posture adds "
        "(quorum-1) x verify_us to replica_us instead",
        "posture_verifier_offload_measured": offload,
        "posture_host_inline": inline,
        "limitation": "certificate size grows with quorum (43 grants ~9.8KB "
        "at n=64): codec/framing growth is not in the base constant, so "
        "replica-core projections are floors",
    }


async def _interleaved_ab(
    n_servers: int,
    rf: int,
    ab_n: int,
    n_clients: int,
    keys_per_client: int,
    rounds: int,
    ops_per_txn: int,
) -> Dict:
    """The acceptance A/B at second-scale pairing: BOTH postures boot once
    and stay up — the single-process VirtualCluster (in this process) and
    the N=ab_n ProcessCluster — then timed one-sweep chunks alternate
    between them, order flipping every round.  Only one posture carries
    traffic at a time (the other's processes sleep at zero CPU), so each
    per-round ratio compares the two postures inside the same ~2-second
    host window — the separated-leg design measured this container's
    minute-scale tenancy swings (±2x) instead of the architecture."""
    from mochi_tpu.testing import ProcessCluster, VirtualCluster
    from mochi_tpu.utils.runtime import reset_gc_debt

    async with VirtualCluster(n_servers, rf=rf) as vc:
        async with ProcessCluster(
            n_servers=n_servers, rf=rf, n_processes=ab_n, pin_cores=True
        ) as pc:
            vc_clients = [vc.client(timeout_s=20.0) for _ in range(n_clients)]
            pc_clients = [pc.client(timeout_s=20.0) for _ in range(n_clients)]
            vc_keys = _shard_local_keys(vc.config, n_clients, keys_per_client, SEED)
            pc_keys = _shard_local_keys(
                pc.config, n_clients, keys_per_client, SEED + 1
            )

            all_cores = (
                os.sched_getaffinity(0) if hasattr(os, "sched_getaffinity") else None
            )
            # ProcessCluster pinned its server processes to cores 0..ab_n-1;
            # the complement is the driver's territory during process
            # chunks (a floating driver preempts the pinned replicas —
            # the separation a real deployment gets from distinct hosts).
            driver_cores = (
                {c for c in all_cores if c >= ab_n} or all_cores
                if all_cores
                else None
            )

            async def chunk(clients, keys_by_client, val: bytes, pin=None) -> float:
                async def worker(ci: int):
                    from mochi_tpu.client.txn import TransactionBuilder

                    keys = keys_by_client[ci]
                    for i in range(0, len(keys), ops_per_txn):
                        tb = TransactionBuilder()
                        for k in keys[i : i + ops_per_txn]:
                            tb.write(k, val)
                        await clients[ci].execute_write_transaction(tb.build())

                if pin:
                    try:
                        os.sched_setaffinity(0, pin)
                    except OSError:
                        pass
                try:
                    t0 = time.perf_counter()
                    await asyncio.gather(*[worker(i) for i in range(n_clients)])
                    ops = sum(len(k) for k in keys_by_client)
                    return ops / (time.perf_counter() - t0)
                finally:
                    if pin and all_cores:
                        try:
                            os.sched_setaffinity(0, all_cores)
                        except OSError:
                            pass

            # Warm both postures off the clock (sessions, certificates).
            await chunk(vc_clients, vc_keys, b"warm")
            await chunk(pc_clients, pc_keys, b"warm")
            reset_gc_debt()
            rows = []
            for r in range(rounds):
                single_first = r % 2 == 0
                val = b"v%d" % r
                if single_first:
                    s = await chunk(vc_clients, vc_keys, val)
                    p = await chunk(pc_clients, pc_keys, val, pin=driver_cores)
                else:
                    p = await chunk(pc_clients, pc_keys, val, pin=driver_cores)
                    s = await chunk(vc_clients, vc_keys, val)
                rows.append(
                    {
                        "order": "single-first" if single_first else "max-first",
                        "single_process_ops_s": round(s, 1),
                        "max_ops_s": round(p, 1),
                        "speedup": round(p / s, 4),
                    }
                )
                pc.check_alive()
    speedups = sorted(x["speedup"] for x in rows)
    return {
        "rounds": rounds,
        "per_round": rows,
        "median_speedup": round(statistics.median(speedups), 4),
        "min_speedup": speedups[0],
        "acceptance_ge_1p6": statistics.median(speedups) >= 1.6,
    }


def run(
    n_servers: int = 6,
    rf: int = 4,
    process_counts: Optional[Sequence[int]] = None,
    n_clients: int = 32,
    keys_per_client: int = 32,
    sweeps: int = 2,
    pairs: int = 7,
    ops_per_txn: int = 32,
) -> Dict:
    """The ladder + paired A/B.  ``process_counts`` defaults to every
    power of two up to min(host cores, n_servers), plus both endpoints —
    on a 2-core host that is (1, 2); on a 64-core host (1, 2, 4, ...)."""
    from mochi_tpu.utils.runtime import tune_gc_for_server

    tune_gc_for_server()
    cores = os.cpu_count() or 1
    max_local = min(cores, n_servers)
    if process_counts is None:
        counts = sorted(
            {1, max_local}
            | {n for n in (2, 4, 8, 16, 32) if n < max_local}
        )
    else:
        counts = sorted(set(int(c) for c in process_counts))
    if len(counts) < 2:
        counts = sorted(set(counts) | {1})
    # The acceptance rung: cores-1 server processes — the client driver
    # needs the remaining core.  Rungs beyond it oversubscribe the host
    # (3+ runnable processes on `cores` cores; the driver preempts pinned
    # replicas) and are kept in the curve as oversubscription evidence,
    # not as the headline.  On a big host this is the process-per-core
    # posture (63 replica processes + driver on 64 cores).
    ab_n = max(1, min(cores - 1, n_servers))
    if ab_n not in counts:
        # The acceptance rung must be MEASURED: add it to the ladder
        # rather than silently degrading to the 1-process rung (which
        # would make the headline A/B measure no scale-out at all on
        # hosts whose cores-1 is not a power of two).
        counts = sorted(set(counts) | {ab_n})
    _host_warmup()

    # ---- acceptance A/B: interleaved one-sweep chunks, both postures up —
    # second-scale pairing (the separated-leg design measured this
    # container's minute-scale tenancy swings, not the architecture).
    ab = asyncio.run(
        _interleaved_ab(
            n_servers, rf, ab_n, n_clients, keys_per_client, 2 * pairs,
            ops_per_txn,
        )
    )
    ab["posture"] = (
        "single-process posture (VirtualCluster, replicas+clients one "
        f"loop) vs N={ab_n} server processes + client process (acceptance "
        f"rung: cores-1; host has {cores} cores); both postures booted "
        "once, timed one-sweep chunks alternating between them with order "
        "flipping per round, same workload seed"
    )

    # ---- ladder curve + CPU constants: separated full legs per rung.
    ladder: List[Dict] = []
    for i in range(max(2, pairs // 2)):
        if i:
            _host_warmup(4.0)  # re-anchor the host's ramp state per round
        ladder.append(
            asyncio.run(
                _single_process_leg(
                    n_servers, rf, n_clients, keys_per_client, sweeps, ops_per_txn
                )
            )
        )
        for n in counts:
            ladder.append(
                asyncio.run(
                    _put_leg(
                        n, n_servers, rf, n_clients, keys_per_client, sweeps,
                        ops_per_txn,
                    )
                )
            )
    curve = []
    for n in [0] + counts:  # 0 = the single-process (VirtualCluster) rung
        runs = [r["put_ops_s"] for r in ladder if r["processes"] == n]
        if not runs:
            continue
        entry = {
            "processes": n,
            "posture": "single-process" if n == 0 else "process",
            "runs": len(runs),
            "put_ops_s_median": round(statistics.median(runs), 1),
            "put_ops_s_all": runs,
        }
        cpus = [
            r["replica_cpu_us_per_txn_cluster"]
            for r in ladder
            if r["processes"] == n and "replica_cpu_us_per_txn_cluster" in r
        ]
        if cpus:
            entry["replica_cpu_us_per_txn_cluster_median"] = round(
                statistics.median(cpus), 1
            )
        curve.append(entry)
    crypto = _crypto_microbench()
    q = 2 * ((rf - 1) // 3) + 1
    # Refit legs: the model's constants are per 1-op transaction (the
    # north-star shape) under the SIDECAR posture, whose process split
    # measures replica base+sign and memoized verify work separately —
    # run at N=max, off the A/B clock.
    refit_legs = [
        asyncio.run(
            _put_leg(
                ab_n, n_servers, rf, n_clients, keys_per_client, 1, 1,
                verifier="service",
            )
        )
        for _ in range(max(1, pairs // 2))
    ]
    rec = {
        "metric": "aggregate_signed_put_scaleout",
        "value": next(
            c["put_ops_s_median"] for c in curve if c["processes"] == ab_n
        ),
        "unit": "signed PUT ops/s (aggregate, max local process count)",
        "topology": {
            "n_servers": n_servers,
            "rf": rf,
            "f": (rf - 1) // 3,
            "quorum": q,
            "process_counts": counts,
            "host_cores": os.cpu_count(),
            "transport": "unix-domain sockets",
            "ladder_verifier": "inline cpu (native host engine) per replica "
            "— best-aggregate posture on a small host; the refit legs run "
            "the sidecar service posture to split the model's constants",
            "clients": n_clients,
            "keys_per_client": keys_per_client,
            "sweeps": sweeps,
            "ops_per_txn": ops_per_txn,
            "workload": "batched shard-local signed PUTs (each client's "
            "keys pin to one token-ring replica set; clients dealt over "
            "the distinct shards)",
            "workload_seed": SEED,
        },
        "ladder": curve,
        "single_vs_max_ab": ab,
        "host_crypto_us": crypto,
        "refit_legs": refit_legs,
        "scaling_refit": _refit(refit_legs, ab, crypto, rf, q, ab_n),
        "note": (
            "client drives from the parent process and shares the host's "
            "cores with the replica processes: the ladder measures the "
            "whole-deployment aggregate on this host, and the refit's "
            "efficiency constant carries that interference honestly "
            "instead of assuming dedicated cores"
        ),
    }
    return rec


if __name__ == "__main__":
    import json

    print(json.dumps(run(), indent=2))
