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
import gc
import json
import logging
import os
import signal
import time
import urllib.parse
from pathlib import Path
from typing import Callable, List, Optional, Sequence

from ..admin.http import HttpJsonServer
from ..cluster.config import ServerInfo
from ..crypto import session as session_crypto
from ..net.transport import RpcServer, _Connection, new_msg_id
from ..obs import hostspan
from ..utils.metrics import Metrics
from ..protocol import (
    Envelope,
    FailType,
    RequestFailedFromServer,
    VerifyBitmapFromServer,
    VerifyRequestToServer,
)
from . import stages
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

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
MAX_PROFILE_SECONDS = 30.0


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
        metrics: Optional[Metrics] = None,
        programs_built: Optional[Callable[[], int]] = None,
        profile_dir: Optional[str] = None,
    ):
        self.secret = secret
        # what the boot path learned about the chip this process owns
        # (platform/device_kind/n_devices, warmup seconds, compile cache
        # dir); None for the CPU backend, which holds no device
        self.device = device
        # ONE registry of stage timers for everything this service composes
        # (verifier/stages.py); a verifier built before the service brings
        # the registry its backend already ticks in
        if metrics is None:
            metrics = getattr(verifier, "metrics", None)
        self.metrics = metrics if metrics is not None else Metrics()
        if verifier is None:
            from .tpu import TpuBatchVerifier

            verifier = TpuBatchVerifier(metrics=self.metrics)
        if cache:
            # Every replica of a set re-checks the same certificate grants;
            # the service-level memo collapses those rf duplicates into one
            # device verification (CachingVerifier docstring).
            verifier = CachingVerifier(verifier, metrics=self.metrics)
        self.verifier = verifier
        self.max_items_per_request = max_items_per_request
        self.rpc = RpcServer(host, port, self._handle)
        self.requests = 0
        self.items = 0
        self._programs_built = programs_built
        # where /profile writes its captures; None: the route answers 404
        self.profile_dir = profile_dir
        self.profiling = False
        self._gc_started: Optional[float] = None
        self._gc_span = None
        self._tick_handle: Optional[asyncio.TimerHandle] = None

    async def start(self) -> None:
        await self.rpc.start()
        gc.callbacks.append(self._on_gc)
        if hostspan.installed():
            self._tick()

    async def close(self) -> None:
        if self._tick_handle is not None:
            self._tick_handle.cancel()
            self._tick_handle = None
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        await self.rpc.close()
        await self.verifier.close()

    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks``: seconds in the collector, as a timer and as a
        span on whichever thread it ran (one collection at a time, so one
        slot holds the pair)."""
        if phase == "start":
            self._gc_span = hostspan.span(stages.SPAN_GC, generation=info["generation"])
            self._gc_span.__enter__()
            self._gc_started = time.perf_counter()
        elif self._gc_started is not None:
            self.metrics.timers[stages.GC].record(time.perf_counter() - self._gc_started)
            self._gc_started = None
            self._gc_span.__exit__(None, None, None)

    def _tick(self) -> None:
        """Once a second on the loop thread while spans are installed: the
        thread's CPU seconds and the epoch clock, as one instant span, so a
        profiler capture holds the loop's busy share and the offset between
        its clock and the one obs/trace.py spans of other processes use."""
        with hostspan.span(
            stages.SPAN_TICK,
            loop_cpu_us=int(time.thread_time() * 1e6),
            epoch_us=time.time_ns() // 1000,
        ):
            pass
        self._tick_handle = asyncio.get_running_loop().call_later(1.0, self._tick)

    @property
    def bound_port(self) -> int:
        return self.rpc.bound_port

    def status(self) -> dict:
        """Operational counters for the one process that owns the device
        (served over HTTP via ``--admin-port``; the replica-side analog is
        the admin shell's ``/metrics``)."""
        st = {
            "service_id": SERVICE_ID,
            "requests": self.requests,
            "items": self.items,
            "authenticated": self.secret is not None,
            "device": self.device,
            "verifier": verifier_stats(self.verifier),
            # stage timers, counters and the flush histogram: exact lifetime
            # count and sum_ms each, so a window is the delta of two reads
            "stages": self.metrics.snapshot(),
            # device programs this process built since boot (JAX's own
            # backend_compile events: a cache hit still loads, and counts)
            "programs_built": self._programs_built() if self._programs_built else 0,
            "building": [],
            "builds_started": 0,
            "builds_finished": 0,
        }
        backend = _device_backend(self.verifier)
        if backend is not None:
            st.update(backend.build_state())
        return st

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

        # Spans cover the synchronous head and tail only: one must not cross
        # the await (obs/hostspan.py); the awaited stretch is in the timer.
        started = time.perf_counter()
        with hostspan.span(stages.SPAN_RPC_ADMIT):
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
            # the memo takes the request's triples as they are (a VerifyItem
            # is a named 3-tuple) and builds VerifyItems only for what it
            # sends on; a bare verifier is handed VerifyItems
            if isinstance(self.verifier, CachingVerifier):
                batch = items
            else:
                batch = [VerifyItem(pk, msg, sig) for pk, msg, sig in items]
        bitmap = await self.verifier.verify_batch(batch)
        with hostspan.span(
            stages.SPAN_RPC_REPLY, wait_us=int((time.perf_counter() - started) * 1e6)
        ):
            self.requests += 1
            self.items += len(items)
            reply = _seal(
                Envelope(
                    VerifyBitmapFromServer(tuple(bitmap)),
                    msg_id=new_msg_id(),
                    sender_id=SERVICE_ID,
                    reply_to=env.msg_id,
                ),
                self.secret,
            )
        self.metrics.timers[stages.SERVICE_RPC].record(time.perf_counter() - started)
        return reply

    async def capture_profile(self, seconds: float) -> str:
        """One ``jax.profiler`` trace of ``seconds`` of whatever the service
        is doing, written under ``profile_dir``; returns its directory.  The
        options are the benchmark launcher's: host spans and device events,
        no Python tracer, no HLO protos (tens of megabytes a capture, and
        nothing reads them)."""
        import jax

        assert self.profile_dir is not None and not self.profiling
        self.profiling = True
        try:
            out = os.path.join(
                self.profile_dir, time.strftime("profile-%Y%m%dT%H%M%S", time.gmtime())
            )
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            options.enable_hlo_proto = False
            # start and stop take tenths of seconds to seconds: off the loop
            await asyncio.to_thread(
                jax.profiler.start_trace, out, profiler_options=options
            )
            try:
                await asyncio.sleep(seconds)
            finally:
                await asyncio.to_thread(jax.profiler.stop_trace)
            return out
        finally:
            self.profiling = False


def _device_backend(verifier):
    """The device batch backend under a verifier composition (the object
    with ``build_state``), or None for a composition that holds no device."""
    while verifier is not None:
        backend = getattr(verifier, "backend", None)
        if hasattr(backend, "build_state"):
            return backend
        verifier = getattr(verifier, "inner", None)
    return None


def count_programs_built() -> Callable[[], int]:
    """Count JAX's backend-compile events in this process from now on (call
    before the first program is built); returns the reader."""
    import jax.monitoring

    built = [0]

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            built[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return lambda: built[0]


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
    metrics = Metrics()
    programs_built = None
    profile_dir = getattr(args, "profile_dir", None)  # callers build args by hand too
    if args.backend == "cpu":
        verifier = CpuVerifier()
        if profile_dir is not None:
            LOG.warning("--profile-dir has no effect with --backend cpu: no device to trace")
            profile_dir = None
    else:
        from ..utils.runtime import device_info, enable_compile_cache

        cache_dir = enable_compile_cache()
        # refuses (SystemExit) when JAX found no accelerator and the CPU
        # was not asked for: XLA:CPU never serves under the TPU's name
        device = device_info(require_accelerator=True)
        # this process holds the device: from here its host spans go on the
        # profiler's clock, and every program it builds is counted
        import jax.profiler

        hostspan.install(jax.profiler.TraceAnnotation)
        programs_built = count_programs_built()
        from . import tpu

        verifier_cls = (
            tpu.TpuBatchVerifier if args.backend == "tpu"
            else tpu.ShardedTpuBatchVerifier
        )
        t0 = time.time()
        verifier = verifier_cls(
            warmup_buckets=tuple(int(b) for b in args.warmup.split(",") if b),
            signers=signers,
            metrics=metrics,
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
        device=device, metrics=metrics, programs_built=programs_built,
        profile_dir=profile_dir,
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

    def _status(self) -> dict:
        st = self.service.status()
        # this handler runs on the service's event-loop thread, so this is
        # the loop's CPU time: its delta over a window is the loop's busy
        # share, at no cost on the request path
        st["loop_thread_cpu_s"] = time.thread_time()
        return st

    def _route(self, path: str):
        if path in ("/", "/status", "/metrics"):
            return 200, "application/json", json.dumps(self._status())
        if path == "/metrics.prom":
            # Flatten the status counters into Prometheus samples (numeric
            # leaves only), same exposition family as the replica shell; the
            # stage registry follows in the shared mochi_timer/_counter/
            # _histogram families.
            def walk(prefix, obj, out):
                for k, v in obj.items():
                    key = f"{prefix}_{k}" if prefix else str(k)
                    if isinstance(v, dict):
                        walk(key, v, out)
                    elif isinstance(v, bool):
                        out.append((key, int(v)))
                    elif isinstance(v, (int, float)):
                        out.append((key, v))

            status = self._status()
            del status["stages"]
            samples: list = []
            walk("", status, samples)
            body = "".join(
                f'mochi_verifier_service{{name="{k}"}} {v}\n' for k, v in samples
            )
            body += self.service.metrics.to_prometheus({"service": SERVICE_ID})
            return 200, "text/plain; version=0.0.4", body
        return 404, "application/json", '{"error": "not found"}'

    async def _route_target(self, target: str):
        url = urllib.parse.urlsplit(target)
        if url.path != "/profile":
            return self._route(url.path)
        svc = self.service
        if svc.profile_dir is None:
            return 404, "application/json", '{"error": "started without --profile-dir"}'
        if svc.profiling:
            return 409, "application/json", '{"error": "a capture is running"}'
        try:
            seconds = float(urllib.parse.parse_qs(url.query)["seconds"][0])
        except (KeyError, ValueError):
            seconds = -1.0
        if not 0.0 < seconds <= MAX_PROFILE_SECONDS:
            return 400, "application/json", json.dumps(
                {"error": f"seconds must be in (0, {MAX_PROFILE_SECONDS:g}]"}
            )
        path = await svc.capture_profile(seconds)
        return 200, "application/json", json.dumps({"path": path, "seconds": seconds})


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
    parser.add_argument(
        "--profile-dir",
        default=None,
        help="let GET /profile?seconds=N on the admin port write one "
        "jax.profiler capture (N <= 30) under this directory; without it "
        "the route answers 404",
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
