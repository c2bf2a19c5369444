"""Verifier RPC service: one process owns the TPU, the cluster shares it.

The north star (BASELINE.json) draws the replica ↔ accelerator boundary as a
sidecar RPC: replica processes buffer signature checks and ship them to the
single JAX process that owns the chip, which returns a validity bitmap.  An
in-process ``VirtualCluster`` doesn't need this — its replicas share the
interpreter with the device owner — but a real ``scripts/start_cluster.sh``
cluster is N separate OS processes, and a TPU has exactly one owner process:
without this service, N-1 replicas are stuck on the CPU path.  The service
is therefore the ONLY process of a deployment that imports JAX; it refuses
to boot a device backend on a host where JAX found no accelerator (unless
``JAX_PLATFORMS=cpu`` was exported on purpose) and reports the device it
holds in ``/status``.

Server: :class:`VerifierService` — an ``RpcServer`` (the same length-prefixed
mcode transport the replicas speak, ``net/transport.py``) in front of a
:class:`~mochi_tpu.verifier.spi.BatchingVerifier` over the JAX device.
Requests from many replicas coalesce in the batcher, so the *cluster-wide*
signature stream forms device-sized batches even when each replica's own
traffic is thin — exactly the aggregation the reference's per-JVM
BouncyCastle model can never do.

Client: :class:`RemoteVerifier` — a ``SignatureVerifier`` that ships batches
to the service and falls back to local CPU verification if the service is
unreachable (availability degrades to the reference-analog path; safety —
never skip a check — is preserved).

Trust model: the verify RPC carries VERDICTS — a forged response saying
"all valid" would admit forged grants — so the channel must be
authenticated.  Two supported postures: (1) loopback-only (the default
bind; the OS is the trust boundary), or (2) a shared secret
(``--secret-file`` / ``secret=``): both directions MAC every envelope with
HMAC-SHA256 over the canonical envelope bytes.  A service with a secret
rejects unMAC'd requests; a client with a secret rejects unMAC'd responses
(falling back to LOCAL CPU verification, never to trusting the network).

Run:  ``python -m mochi_tpu.verifier.service --port 18200 [--secret-file f]``
Wire: ``python -m mochi_tpu.server ... --verifier remote:127.0.0.1:18200``
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal
import time
from pathlib import Path
from typing import List, Optional, Sequence

from ..admin.http import HttpJsonServer
from ..cluster.config import ServerInfo
from ..crypto import session as session_crypto
from ..net.transport import RpcServer, _Connection, new_msg_id
from ..protocol import (
    Envelope,
    FailType,
    RequestFailedFromServer,
    VerifyBitmapFromServer,
    VerifyRequestToServer,
)
from .spi import (
    BatchingVerifier,
    CachingVerifier,
    CpuVerifier,
    SignatureVerifier,
    VerifyItem,
    verifier_stats,
)

LOG = logging.getLogger(__name__)

SERVICE_ID = "verifier-service"


def _seal(env: Envelope, secret: Optional[bytes]) -> Envelope:
    """Attach the shared-secret MAC (no-op without a secret) — the single
    place the sealing scheme lives for requests, responses and failures."""
    if secret is None:
        return env
    return session_crypto.seal(env, secret)


def load_secret(path: str) -> bytes:
    """Load a hex shared secret; refuse degenerate keys (an empty file would
    silently 'authenticate' with HMAC key b'' that anyone can compute)."""
    secret = bytes.fromhex(Path(path).read_text().strip())
    if len(secret) < 16:
        raise SystemExit(
            f"verifier secret in {path} is {len(secret)} bytes; need >= 16"
        )
    return secret


class VerifierService:
    """TPU-owning verification service shared by all replica processes."""

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 18200,
        verifier: Optional[SignatureVerifier] = None,
        max_items_per_request: int = 65536,
        cache: bool = True,
        secret: Optional[bytes] = None,
        device: Optional[dict] = None,
    ):
        self.secret = secret
        # what the boot path learned about the chip this process owns
        # (platform/device_kind/n_devices, warmup seconds, compile cache
        # dir); None for the CPU backend, which holds no device
        self.device = device
        if verifier is None:
            from .tpu import TpuBatchVerifier

            verifier = TpuBatchVerifier()
        if cache:
            # Every replica of a set re-checks the same certificate grants;
            # the service-level memo collapses those rf duplicates into one
            # device verification (CachingVerifier docstring).
            verifier = CachingVerifier(verifier)
        self.verifier = verifier
        self.max_items_per_request = max_items_per_request
        self.rpc = RpcServer(host, port, self._handle)
        self.requests = 0
        self.items = 0

    async def start(self) -> None:
        await self.rpc.start()

    async def close(self) -> None:
        await self.rpc.close()
        await self.verifier.close()

    @property
    def bound_port(self) -> int:
        return self.rpc.bound_port

    def status(self) -> dict:
        """Operational counters for the one process that owns the device
        (served over HTTP via ``--admin-port``; the replica-side analog is
        the admin shell's ``/metrics``)."""
        return {
            "service_id": SERVICE_ID,
            "requests": self.requests,
            "items": self.items,
            "authenticated": self.secret is not None,
            "device": self.device,
            "verifier": verifier_stats(self.verifier),
        }

    async def _handle(self, env: Envelope) -> Optional[Envelope]:
        def fail(ft: FailType, detail: str) -> Envelope:
            # Fail FAST with a typed error — a silent drop would park the
            # requesting replica for its full RPC timeout.  Sealed like the
            # success path so a secret-holding client sees the real reason
            # instead of misreporting it as a response-MAC failure.
            return _seal(
                Envelope(
                    RequestFailedFromServer(ft, detail),
                    msg_id=new_msg_id(),
                    sender_id=SERVICE_ID,
                    reply_to=env.msg_id,
                ),
                self.secret,
            )

        if not isinstance(env.payload, VerifyRequestToServer):
            return fail(FailType.BAD_REQUEST, "expected VerifyRequestToServer")
        if self.secret is not None and not (
            env.mac is not None
            and session_crypto.mac_ok(self.secret, env.signing_bytes(), env.mac)
        ):
            return fail(FailType.BAD_SIGNATURE, "verify request MAC missing/invalid")
        items = env.payload.items
        if len(items) > self.max_items_per_request:
            return fail(
                FailType.BAD_REQUEST,
                f"{len(items)} items > limit {self.max_items_per_request}",
            )
        bitmap = await self.verifier.verify_batch(
            [VerifyItem(pk, msg, sig) for pk, msg, sig in items]
        )
        self.requests += 1
        self.items += len(items)
        return _seal(
            Envelope(
                VerifyBitmapFromServer(tuple(bitmap)),
                msg_id=new_msg_id(),
                sender_id=SERVICE_ID,
                reply_to=env.msg_id,
            ),
            self.secret,
        )


class RemoteVerifier(SignatureVerifier):
    """Ship verification batches to a :class:`VerifierService`.

    The replica keeps its own micro-batching upstream (``BatchingVerifier``
    can wrap this), but even bare it benefits from the service-side batcher
    coalescing traffic across the whole cluster.  On transport failure the
    batch is re-verified locally (CPU) — never skipped.
    """

    # client-side request cap, kept under the service default so one request
    # can never trip the service's oversize rejection
    MAX_REQUEST_ITEMS = 16384

    def __init__(
        self,
        host: str,
        port: int,
        timeout_s: float = 30.0,
        fallback: Optional[SignatureVerifier] = None,
        secret: Optional[bytes] = None,
    ):
        self._conn = _Connection(ServerInfo("verifier", host, port))
        self.timeout_s = timeout_s
        self.fallback = fallback if fallback is not None else CpuVerifier()
        self.secret = secret
        self.remote_batches = 0
        self.fallback_batches = 0

    async def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        if not items:
            return []
        if len(items) > self.MAX_REQUEST_ITEMS:
            out: List[bool] = []
            for i in range(0, len(items), self.MAX_REQUEST_ITEMS):
                out.extend(await self.verify_batch(items[i : i + self.MAX_REQUEST_ITEMS]))
            return out
        req = Envelope(
            VerifyRequestToServer(
                tuple((it.public_key, it.message, it.signature) for it in items)
            ),
            msg_id=new_msg_id(),
            sender_id="verifier-client",
        )
        req = _seal(req, self.secret)
        try:
            resp = await self._conn.send_and_receive(req, self.timeout_s)
            if self.secret is not None and not (
                resp.mac is not None
                and session_crypto.mac_ok(self.secret, resp.signing_bytes(), resp.mac)
            ):
                # forged/unauthenticated verdicts NEVER pass through — the
                # fallback below re-verifies locally instead
                raise ValueError("verifier response MAC missing/invalid")
            payload = resp.payload
            if (
                not isinstance(payload, VerifyBitmapFromServer)
                or len(payload.bitmap) != len(items)
            ):
                raise ValueError("malformed verifier response")
            self.remote_batches += 1
            return [bool(b) for b in payload.bitmap]
        except asyncio.CancelledError:
            raise
        except Exception:
            LOG.exception("remote verify failed; falling back to CPU")
            self.fallback_batches += 1
            return await self.fallback.verify_batch(items)

    async def close(self) -> None:
        await self._conn.close()
        await self.fallback.close()


def load_signers(path: str) -> List[bytes]:
    """Parse a signers file: one hex Ed25519 pubkey per line (# comments)."""
    out: List[bytes] = []
    for line in Path(path).read_text().splitlines():
        line = line.split("#", 1)[0].strip()
        if line:
            out.append(bytes.fromhex(line))
    return out


async def amain(args) -> None:
    signers: List[bytes] = (
        load_signers(args.signers_file) if args.signers_file else []
    )
    if signers and args.backend == "cpu":
        # Failing silently would hide a missing ~3x from the operator
        # (code-review r4); the CPU backend has no device comb path.
        LOG.warning(
            "--signers-file has no effect with --backend cpu: "
            "verification runs OpenSSL per item",
        )
    verifier: Optional[SignatureVerifier] = None
    device: Optional[dict] = None
    if args.backend == "cpu":
        verifier = CpuVerifier()
    else:
        from ..utils.runtime import device_info, enable_compile_cache

        cache_dir = enable_compile_cache()
        # refuses (SystemExit) when JAX found no accelerator and the CPU
        # was not asked for: XLA:CPU never serves under the TPU's name
        device = device_info(require_accelerator=True)
        from . import tpu

        verifier_cls = (
            tpu.TpuBatchVerifier if args.backend == "tpu"
            else tpu.ShardedTpuBatchVerifier
        )
        t0 = time.time()
        verifier = verifier_cls(
            warmup_buckets=tuple(int(b) for b in args.warmup.split(",") if b),
            signers=signers,
        )
        device["warmup_seconds"] = round(time.time() - t0, 1)
        device["compile_cache_dir"] = cache_dir
        LOG.info(
            "%s backend on %s %r x%d: warmup %.1fs at buckets [%s], "
            "%d known signers, compile cache %s",
            args.backend, device["platform"], device["device_kind"],
            device["n_devices"], device["warmup_seconds"], args.warmup,
            len(signers), cache_dir,
        )
    secret = None
    if args.secret_file:
        secret = load_secret(args.secret_file)
    service = VerifierService(
        host=args.host, port=args.port, verifier=verifier, secret=secret,
        device=device,
    )
    await service.start()
    admin = None
    if args.admin_port is not None:
        admin = ServiceAdminServer(service, port=args.admin_port)
        await admin.start()
    print(f"READY {SERVICE_ID} {service.bound_port}", flush=True)
    # SIGTERM/SIGINT close the RPC server, drain the batcher and return, so
    # the interpreter exits normally and the runtime releases the chip: an
    # owner that is killed hard can leave the next owner failing or hanging
    # at backend init.
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):
            pass  # non-unix / nested-loop environments
    try:
        await stop.wait()
        LOG.info("shutdown signal received; closing")
    finally:
        if admin is not None:
            await admin.close()
        await service.close()


class ServiceAdminServer(HttpJsonServer):
    """Loopback HTTP status endpoint for the standalone service: /status
    (and /) serve :meth:`VerifierService.status` as JSON.  Reuses the
    admin shell's hardened transport loop (read timeouts, header drain)."""

    def __init__(self, service: VerifierService, host: str = "127.0.0.1", port: int = 0):
        super().__init__(host, port)
        self.service = service

    def _route(self, path: str):
        import json as _json

        if path in ("/", "/status", "/metrics"):
            return 200, "application/json", _json.dumps(self.service.status())
        if path == "/metrics.prom":
            # Flatten the status counters into Prometheus samples (numeric
            # leaves only), same exposition family as the replica shell.
            def walk(prefix, obj, out):
                for k, v in obj.items():
                    key = f"{prefix}_{k}" if prefix else str(k)
                    if isinstance(v, dict):
                        walk(key, v, out)
                    elif isinstance(v, bool):
                        out.append((key, int(v)))
                    elif isinstance(v, (int, float)):
                        out.append((key, v))

            samples: list = []
            walk("", self.service.status(), samples)
            body = "".join(
                f'mochi_verifier_service{{name="{k}"}} {v}\n' for k, v in samples
            )
            return 200, "text/plain; version=0.0.4", body
        return 404, "application/json", '{"error": "not found"}'


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=18200)
    parser.add_argument(
        "--backend",
        choices=("tpu", "tpu-sharded", "cpu"),
        default="tpu",
        help="tpu: single-device batch verifier; tpu-sharded: shard batches "
        "over ALL local devices (multi-chip hosts); cpu: OpenSSL",
    )
    parser.add_argument(
        "--warmup",
        default="512,8192",
        help="comma-separated bucket sizes to pre-compile at boot, both "
        "programs each (default: the first bucket the 384-item device "
        "crossover can dispatch, and the largest launch)",
    )
    parser.add_argument(
        "--secret-file",
        default=None,
        help="hex shared secret: MAC-authenticate the verify RPC in both "
        "directions (required when the service is not loopback-only)",
    )
    parser.add_argument(
        "--signers-file",
        default=None,
        help="file of hex Ed25519 pubkeys (one per line, # comments ok): "
        "known signers — usually the cluster's replica identities — whose "
        "signatures take the doubling-free comb path (crypto/comb.py, "
        "~3x fewer device FLOPs); unknown signers still verify via the "
        "general ladder",
    )
    parser.add_argument(
        "--admin-port",
        type=int,
        default=None,
        help="serve service counters as JSON over loopback HTTP (0 = ephemeral)",
    )
    parser.add_argument("--log-level", default="INFO")
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=args.log_level, format="%(asctime)s %(name)s %(levelname)s %(message)s"
    )
    from ..utils.runtime import tune_gc_for_server

    tune_gc_for_server()
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
