"""TPU-backed batch verifier: the BASELINE.json ``TpuBatchVerifier``.

Composition of the two halves built elsewhere:

* :class:`mochi_tpu.verifier.spi.BatchingVerifier` — async micro-batching
  with a CPU fallback (never skips verification on device failure);
* :class:`mochi_tpu.crypto.batch_verify.JaxBatchBackend` — one jitted XLA
  program per batch-size bucket running the limb-decomposed Ed25519
  pipeline (decompress + double-scalar-mul) on the default JAX device.

Unlike BASELINE.json's sketch (gRPC sidecar between a JVM replica and a JAX
process), this framework's replicas are *already* in the JAX process, so the
batcher feeds the device in-process — one IPC hop less on the hot path.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence

import jax

from ..crypto.batch_verify import JaxBatchBackend
from .spi import BatchingVerifier, SignatureVerifier


LOG = logging.getLogger(__name__)


class _SignerRegistrationMixin:
    """Shared registration hook for the device-backed verifiers (both store
    ``_warmup_buckets``; keeping ONE definition avoids silent divergence).
    Registers with the backend FIRST — passing the warmup buckets so comb
    programs re-warm for the grown registry (see
    :meth:`mochi_tpu.crypto.batch_verify.JaxBatchBackend.register_signers`
    for the no-stall growth semantics) — then runs the base SPI walk so the
    registration ALSO reaches the CPU ``fallback`` (host comb priming on
    wheel-less hosts): if the device path ever degrades to the fallback,
    cluster signers are already promoted there.  The walk's second visit to
    ``backend`` is an idempotent no-op (no growth → no recompiles)."""

    def register_signers(self, pubs: Sequence[bytes]) -> bool:
        self.backend.register_signers(pubs, extra_buckets=self._warmup_buckets)
        SignatureVerifier.register_signers(self, pubs)
        return True


class TpuBatchVerifier(_SignerRegistrationMixin, BatchingVerifier):
    """BatchingVerifier over the JAX device backend.

    ``max_batch``/``max_delay_s`` implement the batching discipline of
    SURVEY.md §7: ship partial batches on a timer so p50 commit latency stays
    bounded at low load while large batches amortize device launches at high
    load.
    """

    def __init__(
        self,
        device: Optional[jax.Device] = None,
        max_batch: int = 8192,
        max_delay_s: float = 0.002,
        fallback: Optional[SignatureVerifier] = None,
        warmup_buckets: Sequence[int] = (),
        min_device_items: Optional[int] = None,
        max_inflight: int = 4,
        signers: Sequence[bytes] = (),
        metrics=None,
    ):
        registry = None
        if signers:
            from ..crypto.comb import SignerRegistry

            registry = SignerRegistry(device=device)
            registry.register_all(signers)
        jax_backend = JaxBatchBackend(
            device=device, min_device_items=min_device_items, registry=registry,
            metrics=metrics,
        )
        super().__init__(
            backend=jax_backend,
            max_batch=max_batch,
            max_delay_s=max_delay_s,
            fallback=fallback,
            max_inflight=max_inflight,
            metrics=jax_backend.metrics,
        )
        self._device = device
        self._warmup_buckets = tuple(warmup_buckets)
        if warmup_buckets:
            jax_backend.warmup(warmup_buckets)

class ShardedJaxBatchBackend(JaxBatchBackend):
    """``JaxBatchBackend`` whose device path shards each batch over a MESH.

    The single-device backend is the right choice for one chip; on a
    multi-chip host (or a ``jax.distributed`` multi-host fleet — see
    ``parallel/multihost.py``) this splits the prepared batch over ``mesh``
    with ``shard_map`` so every chip verifies its slice concurrently.
    Verification is embarrassingly parallel (no collective; the cluster's
    quorum tally happens back at the replicas), so scaling is linear in
    devices up to the host-prepare bound.

    Inherits ALL of the base machinery — the low-batch CPU crossover,
    boot-time warmup, background compiles with chunk-at-ready-buckets (no
    live request ever parks behind a 20-60 s XLA compile) — by plugging a
    sharded verify into the base's ``verify_fn`` hook.  Scalars travel in
    the packed (B, 32)-byte form (``parallel.sharded
    .make_sharded_verify_packed``), same 32x-smaller H2D transfer as the
    single-device path.
    """

    def __init__(
        self, mesh=None, min_device_items: Optional[int] = None, registry=None,
        metrics=None,
    ):
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.sharded import (
            make_mesh,
            make_sharded_verify_comb,
            make_sharded_verify_packed,
        )

        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_devices = int(self.mesh.devices.size)
        self._sharded = make_sharded_verify_packed(self.mesh)
        self._sharded_comb = make_sharded_verify_comb(self.mesh)
        # comb tables replicate to every device (a few MB; each chip
        # gathers locally — no collective)
        self._rep_sharding = NamedSharding(self.mesh, PartitionSpec())
        super().__init__(
            device=None,
            min_device_items=min_device_items,
            verify_fn=self._sharded_verify,
            registry=registry,
            metrics=metrics,
        )

    def _comb_capable(self) -> bool:
        return True

    def _registry_device(self):
        return self._rep_sharding

    def _warm_comb(self, bucket: int) -> None:
        """Compile the sharded comb program for one bucket (the base warms
        the single-device program, which is not the one this backend
        dispatches)."""
        import numpy as np

        from ..crypto import batch_verify, field as F

        gen = self.registry.generation
        m = ((bucket + self.n_devices - 1) // self.n_devices) * self.n_devices
        table = self.registry.device_table(self._rep_sharding, gen)
        out = self._sharded_comb(
            table,
            np.zeros((m,), np.int32),
            np.zeros((m, F.NLIMBS), np.int32),
            np.zeros((m,), np.int32),
            np.zeros((m, 32), np.uint8),
            np.zeros((m, 32), np.uint8),
        )
        np.asarray(out)
        # which devices actually hold a slice of the result: the evidence
        # that a multi-chip host is used, not just its first chip
        LOG.info(
            "sharded comb at bucket %d: %d shards of %s on devices %s",
            bucket,
            len(out.addressable_shards),
            out.addressable_shards[0].data.shape,
            sorted(s.device.id for s in out.addressable_shards),
        )
        with self._lock:
            self._ready_comb[bucket] = max(gen, self._ready_comb.get(bucket, 0))

    def _sharded_verify(
        self, items, device=None, bucket=None, registry=None, comb_gen=None
    ):
        import numpy as np

        from ..crypto import batch_verify

        del device  # placement comes from the mesh sharding
        if not items:
            return []
        # Comb routing is all-or-nothing per launch: a mixed batch runs the
        # general program whole rather than paying two sharded launches —
        # cluster service traffic is ~100% registered, so the split case
        # is rare enough that simplicity wins.
        use_comb = (
            registry is not None
            and len(registry)
            and batch_verify.comb_enabled()
        )
        key_idx = None
        gen = None
        if use_comb:
            gen = comb_gen if comb_gen is not None else registry.generation
            idxs = [registry.index_of(it.public_key) for it in items]
            if any(k is None or k >= gen for k in idxs):
                use_comb = False
            else:
                key_idx = np.asarray(idxs, dtype=np.int32)
            # router occupancy: all-or-nothing per launch here, so a batch
            # with any unregistered key counts whole as ladder traffic
            batch_verify._note_routing(
                len(items) if use_comb else 0,
                0 if use_comb else len(items),
            )
        y_a, sign_a, y_r, sign_r, s_sc, h_sc, pre_ok = batch_verify.prepare_packed(items)
        if not pre_ok.any():
            # All-rejected chunk (garbage flood): no device work, and —
            # like the base _dispatch fast path — no dispatch-count bump,
            # so the bucket is not falsely marked compiled.
            return [False] * len(items)
        n = len(items)
        m = batch_verify._bucket_size(n) if bucket is None else bucket
        # static shapes for the compile cache, rounded up to a device
        # multiple (buckets are powers of two, so this is a no-op on
        # power-of-two meshes)
        m = ((m + self.n_devices - 1) // self.n_devices) * self.n_devices
        if m != n:
            pad2 = ((0, m - n), (0, 0))
            y_r = np.pad(y_r, pad2)
            s_sc = np.pad(s_sc, pad2)
            h_sc = np.pad(h_sc, pad2)
            sign_r = np.pad(sign_r, ((0, m - n),))
            if use_comb:
                key_idx = np.pad(key_idx, ((0, m - n),))
            else:
                # only the general program reads the pubkey tensors
                y_a = np.pad(y_a, pad2)
                sign_a = np.pad(sign_a, ((0, m - n),))
        if use_comb:
            batch_verify._note_dispatch(comb=True)
            table = registry.device_table(self._rep_sharding, gen)
            bitmap = np.asarray(
                self._sharded_comb(table, key_idx, y_r, sign_r, s_sc, h_sc)
            )[:n]
        else:
            batch_verify._note_dispatch()
            bitmap = np.asarray(
                self._sharded(y_a, sign_a, y_r, sign_r, s_sc, h_sc)
            )[:n]
        return [bool(b) for b in np.logical_and(bitmap, pre_ok)]


class ShardedTpuBatchVerifier(_SignerRegistrationMixin, BatchingVerifier):
    """BatchingVerifier over the mesh-sharded backend (all local devices)."""

    def __init__(
        self,
        mesh=None,
        max_batch: int = 8192,
        max_delay_s: float = 0.002,
        fallback: Optional[SignatureVerifier] = None,
        warmup_buckets: Sequence[int] = (),
        min_device_items: Optional[int] = None,
        max_inflight: int = 4,
        signers: Sequence[bytes] = (),
        metrics=None,
    ):
        backend = ShardedJaxBatchBackend(
            mesh=mesh, min_device_items=min_device_items, metrics=metrics
        )
        if signers:
            backend.register_signers(signers)
        super().__init__(
            backend=backend,
            max_batch=max_batch,
            max_delay_s=max_delay_s,
            fallback=fallback,
            max_inflight=max_inflight,
            metrics=backend.metrics,
        )
        self._warmup_buckets = tuple(warmup_buckets)
        if warmup_buckets:
            backend.warmup(warmup_buckets)

