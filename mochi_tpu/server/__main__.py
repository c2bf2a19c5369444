"""Replica server entrypoint.

Ops-layer equivalent of the reference's boot path (``start_mochi.sh:4-8`` →
``Application.main`` → ``MochiServerInitializator`` → ``MochiServer.start()``,
SURVEY.md §3.1), as a plain asyncio process instead of a Spring Boot shell.

Usage:
    python -m mochi_tpu.server --config cluster/cluster_config.json \
        --server-id server-0 --seed-file cluster/server-0.seed [--verifier cpu|tpu]

Repeating ``--server-id``/``--seed-file`` (pairwise, in order) hosts SEVERAL
replicas on this process's one event loop — the packing knob of the
shard-per-core deployment ladder (``testing/process_cluster.py``): one
replica per process is the
production scale-out posture; all replicas in one process is the
single-core baseline the ladder is measured against.

Lifecycle: each replica prints ``READY <server-id> <port>`` on stdout once
it serves (the machine-readable readiness probe), and SIGTERM/SIGINT runs a
bounded graceful drain — stop accepting, finish admitted work, flush
coalesced response writes (``MochiReplica.drain``) — before the close path
(final snapshot, pool/socket teardown), so a supervisor's TERM is
deterministic instead of a mid-batch abort.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
from pathlib import Path

from ..cluster.config import ClusterConfig
from ..crypto.keys import keypair_from_seed
from ..server.replica import MochiReplica


def load_config(path: str) -> ClusterConfig:
    text = Path(path).read_text()
    if text.lstrip().startswith("{"):
        return ClusterConfig.from_json(text)
    return ClusterConfig.from_properties(text)


def _build_verifier(args, config: ClusterConfig):
    """One verifier instance per hosted replica (simple ownership: each
    replica's close is followed by its own verifier's close)."""
    if args.verifier == "tpu":
        try:
            from ..verifier.tpu import TpuBatchVerifier
        except ImportError as exc:
            raise SystemExit(f"TPU verifier unavailable ({exc}); use --verifier cpu") from exc
        from ..utils.runtime import device_info, enable_compile_cache

        # This process becomes the chip's owner (one per chip: every other
        # replica process must use remote:<host>:<port>).  Refuses to boot
        # when JAX found no accelerator and JAX_PLATFORMS=cpu was not set.
        enable_compile_cache()
        device_info(require_accelerator=True)
        # Warm both programs at boot (doing it here keeps the compile out of
        # the first client's commit latency) — READY is only printed once
        # the verifier can serve.  The cluster's replica identities are
        # known signers: their cert signatures take the doubling-free comb
        # path (crypto/comb.py).
        return TpuBatchVerifier(
            warmup_buckets=(16,), signers=list(config.public_keys.values())
        )
    if args.verifier.startswith("remote:"):
        # Shared TPU sidecar: one mochi_tpu.verifier.service process owns the
        # chip; every replica ships its signature batches there (the north
        # star's sidecar boundary — a chip has one owner process).
        from ..verifier.service import RemoteVerifier

        target = args.verifier[len("remote:"):]
        host, _, port = target.rpartition(":")
        if not host or not port.isdigit():
            raise SystemExit(f"--verifier remote:<host>:<port> (got {args.verifier!r})")
        secret = None
        if args.verifier_secret_file:
            from ..verifier.service import load_secret

            secret = load_secret(args.verifier_secret_file)
        from ..verifier.spi import CoalescingVerifier

        # Coalescer: concurrent Write2 certificate checks share one RPC
        # round trip to the service instead of paying one each (two
        # loopback frames per call dominate the replica-side cost).
        return CoalescingVerifier(RemoteVerifier(host, int(port), secret=secret))
    if args.verifier != "cpu":
        # No silent fallback: a typo'd --verifier must not quietly run the
        # inline CPU path (the misconfiguration argparse choices= used to
        # reject before remote:<host>:<port> made the value open-ended).
        raise SystemExit(
            f"unknown --verifier {args.verifier!r}: use cpu | tpu | remote:<host>:<port>"
        )
    return None  # replica defaults to the inline CpuVerifier


async def amain(args) -> None:
    config = load_config(args.config)
    if args.require_client_auth and not config.admin_keys:
        # Unrecoverable lockout otherwise: every client is unknown, and
        # registering one requires an authenticated write, which requires
        # being registered — only an admin key breaks the cycle.
        raise SystemExit(
            "--require-client-auth needs config.admin_keys to bootstrap the "
            "client registry (generate with gen_cluster --with-admin)"
        )
    server_ids = args.server_id
    seed_files = args.seed_file
    if len(server_ids) != len(seed_files):
        raise SystemExit(
            f"{len(server_ids)} --server-id but {len(seed_files)} --seed-file "
            "(repeat them pairwise, in order)"
        )
    if len(set(server_ids)) != len(server_ids):
        raise SystemExit(f"duplicate --server-id in {server_ids}")
    byzantine = {}
    for spec in args.byzantine or ():
        sid, sep, strategy = spec.partition("=")
        if not sep or not strategy:
            raise SystemExit(f"--byzantine wants <server-id>=<strategy>, got {spec!r}")
        if sid not in server_ids:
            raise SystemExit(f"--byzantine {spec!r}: {sid} is not hosted here")
        byzantine[sid] = strategy
    replicas = []
    admins = []
    for i, (sid, seed_file) in enumerate(zip(server_ids, seed_files)):
        keypair = keypair_from_seed(bytes.fromhex(Path(seed_file).read_text().strip()))
        if keypair.public_key != config.public_keys.get(sid):
            raise SystemExit(
                f"seed file does not match configured public key for {sid}"
            )
        info = config.servers[sid]
        snapshot_path = None
        if args.data_dir:
            snapshot_path = str(Path(args.data_dir) / f"{sid}.snapshot")
        storage = None
        if args.storage_dir:
            # Log-structured durable engine (docs/OPERATIONS.md §4i): WAL +
            # snapshots under <storage-dir>/<sid>; boot recovery replays
            # through the verified path before READY is printed.
            from ..storage import build_storage

            storage = build_storage(
                args.storage_dir, sid, fsync=args.wal_fsync,
                engine=args.storage_engine,
            )
        replica_cls = MochiReplica
        replica_kwargs = {}
        if sid in byzantine:
            # Fault-injection posture (testing/process_cluster drives this
            # for cross-process adversarial scenarios); make_strategy
            # rejects unknown names before the replica binds a port.
            from ..testing.byzantine import ByzantineReplica, make_strategy

            make_strategy(byzantine[sid])  # validate early, fail the boot
            replica_cls = ByzantineReplica
            replica_kwargs = dict(
                strategy=byzantine[sid], strategy_seed=sum(sid.encode())
            )
        replica = replica_cls(
            server_id=sid,
            config=config,
            keypair=keypair,
            verifier=_build_verifier(args, config),
            require_client_auth=args.require_client_auth,
            host=args.host or info.host,
            port=info.port,
            snapshot_path=snapshot_path,
            snapshot_interval_s=args.snapshot_interval,
            storage=storage,
            # explicit --admission wins; the deprecated --shed-lag-ms alias
            # only applies when the new flag was not passed; default on
            admission=(
                args.admission == "on"
                if args.admission is not None
                else (args.shed_lag_ms is None or args.shed_lag_ms > 0)
            ),
            **replica_kwargs,
        )
        await replica.start()
        replicas.append(replica)
        resynced = ""
        if args.resync_on_boot:
            # After the verified replay of whatever --storage-dir / --data-dir
            # held (nothing, on a lost disk): pull committed state from peers
            # before serving (paper's UptoSpeed).  ONE pass, bounded by the
            # peers' stores as they stood when it began: under load there is
            # no pass that finds nothing new, so none is waited for.  What
            # commits during the pass reaches this replica as it reaches any
            # serving one (it listens since start(): Write2s, client nudges).
            advanced = await replica.resync()
            report = replica.resync_report()
            logging.info(
                "boot resync: %d objects recovered (%d entries pulled in %.0f ms; "
                "%d of %d shards and %d of %d keys matched, %d pulls abandoned): "
                "caught up with what a quorum of peers held at epoch_us %d",
                advanced, report["entries_pulled"], report["ms"],
                report["shards_matched"], report["shards_compared"],
                report["keys_matched"], report["keys_compared"],
                sum(p["abandoned"] for p in report["by_peer"].values()),
                report["began_epoch_us"],
            )
            # READY after --resync-on-boot means caught up to the pass's start
            # (``began_epoch_us`` in /status storage.resync and in the line
            # above); a boot whose pulls were abandoned past what f tolerates
            # says so here (and in /status) instead of passing for one
            resynced = " resync=complete" if report["complete"] else " resync=INCOMPLETE"
        if args.admin_port is not None:
            from ..admin import AdminServer

            # Deliberately NOT args.host: --host 0.0.0.0 opens the replica
            # protocol port, but the unauthenticated admin endpoints stay on
            # loopback unless --admin-host explicitly widens them.  Hosted
            # replica i serves its shell on --admin-port + i.
            admin = AdminServer(replica, host=args.admin_host, port=args.admin_port + i)
            await admin.start()
            admins.append(admin)
            logging.info("admin shell for %s on port %s", sid, admin.bound_port)
        logging.info("replica %s serving on %s:%s", sid, replica.rpc.host, replica.bound_port)
        # Machine-readable readiness probe (one line per hosted replica):
        # supervisors and testing/process_cluster.py block on these.
        print(f"READY {sid} {replica.bound_port}{resynced}", flush=True)
    # Graceful SIGTERM/SIGINT: drain first — stop accepting, finish admitted
    # work, flush coalesced writes (bounded by --drain-timeout) — then the
    # real close path: final snapshot (state is in-memory; the snapshot IS
    # the durability), peer/RPC teardown, and the UDS socket unlink.
    # Without this a supervisor's TERM aborts mid-batch, loses the last
    # snapshot interval, and leaves stale .sock files (reclaimed at next
    # bind, but ENOENT beats ECONNREFUSED for probes).
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    import signal as _signal

    for sig in (_signal.SIGTERM, _signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):
            pass  # non-unix / nested-loop environments
    try:
        await stop.wait()
        logging.info("shutdown signal received; draining %s", server_ids)
    finally:
        await asyncio.gather(
            *(r.drain(args.drain_timeout) for r in replicas),
            return_exceptions=True,
        )
        for admin in admins:
            await admin.close()
        for replica in replicas:
            await replica.close()
            if replica.verifier is not None:
                await replica.verifier.close()


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", required=True)
    parser.add_argument(
        "--server-id",
        action="append",
        required=True,
        help="replica identity to host; repeat (with a pairwise --seed-file) "
        "to host several replicas on this process's event loop",
    )
    parser.add_argument(
        "--seed-file",
        action="append",
        required=True,
        help="hex Ed25519 seed for the matching --server-id (same order)",
    )
    parser.add_argument("--host", default=None, help="bind host override (e.g. 0.0.0.0)")
    parser.add_argument(
        "--verifier",
        default="cpu",
        help="cpu | tpu | remote:<host>:<port> (shared verifier service)",
    )
    parser.add_argument(
        "--verifier-secret-file",
        default=None,
        help="hex shared secret MAC-authenticating the remote verifier RPC "
        "(must match the service's --secret-file)",
    )
    parser.add_argument(
        "--admin-port",
        type=int,
        default=None,
        help="serve the HTTP admin shell (/status, /metrics) on this port "
        "(hosted replica i gets port+i)",
    )
    parser.add_argument(
        "--admin-host",
        default="127.0.0.1",
        help="bind host for the admin shell (kept separate from --host so a "
        "wide replica bind does not expose the unauthenticated admin API)",
    )
    parser.add_argument(
        "--data-dir",
        default=None,
        help="persist state snapshots here (reference has no durability at all)",
    )
    parser.add_argument(
        "--snapshot-interval",
        type=float,
        default=30.0,
        help="seconds between periodic snapshots (with --data-dir or "
        "--storage-dir)",
    )
    parser.add_argument(
        "--storage-dir",
        default=None,
        help="durable log-structured storage root (WAL + snapshots + "
        "verified crash recovery under <dir>/<server-id>; "
        "docs/OPERATIONS.md §4i).  Orthogonal to --data-dir's legacy "
        "whole-store snapshots",
    )
    parser.add_argument(
        "--storage-engine",
        choices=("wal", "paged"),
        default=None,
        help="durable engine under --storage-dir (default: "
        "MOCHI_STORAGE_ENGINE or 'wal'): wal = whole-store snapshots, "
        "everything resident (§4i); paged = immutable self-certifying "
        "value pages + bounded resident cache, keyspace can exceed RAM "
        "(docs/OPERATIONS.md §4l)",
    )
    parser.add_argument(
        "--wal-fsync",
        choices=("always", "group", "off"),
        default=None,
        help="WAL durability policy (default: MOCHI_WAL_FSYNC or 'group'): "
        "always = fsync before every ack (group-committed); group = ack "
        "after the OS write (SIGKILL-safe), fsync on a background tick; "
        "off = no fsync outside snapshot/close",
    )
    parser.add_argument(
        "--resync-on-boot",
        action="store_true",
        help="after the replay of --storage-dir/--data-dir, pull what the peers "
        "hold and this replica lacks, in ONE bounded pass, before serving "
        "(UptoSpeed); the READY line then ends in resync=complete or "
        "resync=INCOMPLETE",
    )
    parser.add_argument(
        "--require-client-auth",
        action="store_true",
        help="reject envelopes from clients with no registered key "
        "(register via the _CONFIG_CLIENT_<id> keyspace, "
        "MochiDBClient.register_client_key; admin-gated when "
        "config.admin_keys is set)",
    )
    parser.add_argument(
        "--admission",
        choices=("on", "off"),
        default=None,  # unset: the deprecated --shed-lag-ms alias may apply
        help="overload admission control (deterministic load signal: "
        "dispatch pressure + verify occupancy + send-queue pressure — "
        "server/admission.py; docs/OPERATIONS.md §4g): shed new Write1s "
        "with typed OVERLOADED + retry-after once load exceeds the "
        "MOCHI_SHED_* high-water marks",
    )
    parser.add_argument(
        "--shed-lag-ms",
        type=float,
        default=None,
        help="DEPRECATED alias for --admission (the wall-clock lag signal "
        "is retired): 0 maps to off, any positive value to on",
    )
    parser.add_argument(
        "--byzantine",
        action="append",
        default=None,
        metavar="SID=STRATEGY",
        help="FAULT INJECTION (testing only): host the named replica as a "
        "ByzantineReplica running the given attack strategy (equivocate | "
        "forge-cert | stale-replay | silent | storm) — see "
        "mochi_tpu/testing/byzantine.py and docs/OPERATIONS.md §4f",
    )
    parser.add_argument(
        "--drain-timeout",
        type=float,
        default=5.0,
        help="max seconds the SIGTERM/SIGINT drain waits for in-flight "
        "work before the close path cancels the remainder",
    )
    parser.add_argument("--log-level", default="INFO")
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=args.log_level, format="%(asctime)s %(name)s %(levelname)s %(message)s"
    )
    from mochi_tpu.utils.runtime import tune_gc_for_server

    tune_gc_for_server()
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
