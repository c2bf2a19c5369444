"""Batched Ed25519 verification: host preprocessing + jitted device kernel.

Division of labor (the TPU-first design, SURVEY.md §7):

* **Host** — everything variable-length or trivially cheap: SHA-512 of
  R||A||M (hashlib -> OpenSSL C, ~GB/s), the mod-L scalar reduction
  (python bignum), RFC 8032 canonical-encoding prechecks (y < p, S < L),
  and packing into fixed-shape tensors (scalars as bytes — 32x less H2D
  transfer than bit tensors; the device unpacks).
* **Device** — all the modular heavy lifting (~4400 field muls per
  signature): point decompression (two fixed exponentiation chains) and the
  256-step double-scalar-mul, batched over the leading axis.  Oversized
  requests chunk at MAX_BUCKET with every chunk launched before any is
  read back, so chunk k+1's host prepare and transfer overlap chunk k's
  device execution.

Batches are padded to power-of-two buckets so XLA compiles a handful of
program shapes, then caches (SURVEY.md §7: static shapes; first compile
20-40s, later calls cached).

The verdict matches the CPU path (OpenSSL cofactorless verify) bit-for-bit;
``tests/test_crypto_jax.py`` checks this differentially including forged and
malformed inputs.

Considered and rejected: random-linear-combination batch verification
(one multi-scalar-mul checking sum_i z_i*(S_i*B - R_i - h_i*A_i) = 0, as
surveyed for committee consensus in arXiv:2302.00418).  It cuts device
FLOPs ~2x, but (a) its cofactored acceptance can DISAGREE with
cofactorless per-signature verification on adversarial mixed-order /
non-canonical inputs — breaking this module's bit-for-bit differential
contract with OpenSSL, which the cluster's Byzantine tests rely on; and
(b) a failed batch yields no per-item verdicts, forcing bisection retries
exactly when an attacker salts batches with one bad signature.  Batching
here means SIMD over independent per-signature checks: same verdicts as
serial verification, per-item bitmaps, no degradation under attack.
"""

from __future__ import annotations

import contextlib
import hashlib
import logging
import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import curve
from . import field as F
from ..obs import hostspan
from ..utils.metrics import Metrics
from ..verifier import stages
from ..verifier.spi import VerifyItem

LOG = logging.getLogger(__name__)

MIN_BUCKET = 16
# Largest single device launch: the ladder's per-item small-multiples
# tables are what a launch holds in VMEM, and past this many lanes they
# spill.  Bigger requests are chunked at this size behind a bounded launch
# window, so rate stays flat instead of regressing.  Tune via
# MOCHI_MAX_BUCKET without a code change.
def _max_bucket() -> int:
    """MOCHI_MAX_BUCKET, sanitized: >= MIN_BUCKET and a power of two (a
    non-power would chunk at sizes _bucket_size pads PAST the VMEM cap the
    knob exists to enforce; 0/negative would break the chunk loop)."""
    try:
        v = int(os.environ.get("MOCHI_MAX_BUCKET", "8192"))
    except ValueError:
        return 8192
    v = max(v, MIN_BUCKET)
    return 1 << (v.bit_length() - 1)  # round DOWN to a power of two


MAX_BUCKET = _max_bucket()

# Bounded launch-ahead for verify_batch's chunked path: live memory stays
# O(_PIPELINE_DEPTH * MAX_BUCKET) while chunk k+1's host prepare/transfer
# overlaps chunk k's device execution.
_PIPELINE_DEPTH = 4


def _bucket_size(n: int) -> int:
    m = MIN_BUCKET
    while m < n:
        m *= 2
    return m


def _hbatch():
    """The native batched-h module (lazy; None -> pure-python fallback)."""
    from ..native import get_hbatch

    return get_hbatch()


# 15-bit limb weights and the uint64-word forms of p and L, for the
# vectorized prechecks below.
_W15 = (1 << np.arange(15, dtype=np.int32)).astype(np.int32)
_P_WORDS = [(F.P_INT >> (64 * k)) & ((1 << 64) - 1) for k in range(4)]
_L_WORDS = [(F.L_INT >> (64 * k)) & ((1 << 64) - 1) for k in range(4)]


def _bits_le(rows: np.ndarray) -> np.ndarray:
    """(n, 32) uint8 -> (n, 256) little-endian bits (uint8)."""
    return np.unpackbits(rows, axis=1, bitorder="little")


def _bits_to_limbs(bits: np.ndarray) -> np.ndarray:
    """(n, 256) LE bits -> (n, 17) int32 limbs (bit 255 never read: only
    bits 0..254 enter the limbs, which is exactly the & (2^255-1) mask)."""
    return (
        bits[:, :255].reshape(-1, F.NLIMBS, F.RADIX).astype(np.int32) @ _W15
    )


def _words_le(rows: np.ndarray) -> np.ndarray:
    """(n, 32) uint8 -> (n, 4) uint64 little-endian words."""
    return rows.view("<u8")


def _lt_p(words: np.ndarray) -> np.ndarray:
    """value < p = 2^255-19, for values already masked below 2^255.
    p's words are (0xff..ed, ff.., ff.., 0x7fff..): >= p requires the top
    three words saturated and word0 >= 0xff..ed."""
    w0, w1, w2, w3 = (words[:, k] for k in range(4))
    ge = (
        (w3 == _P_WORDS[3]) & (w2 == _P_WORDS[2]) & (w1 == _P_WORDS[1])
        & (w0 >= _P_WORDS[0])
    )
    return ~ge


def _lt_l(words: np.ndarray) -> np.ndarray:
    """value < L (group order), lexicographic compare from the top word."""
    w0, w1, w2, w3 = (words[:, k] for k in range(4))
    ge = (w3 > _L_WORDS[3]) | (
        (w3 == _L_WORDS[3])
        & ((w2 > _L_WORDS[2]) | ((w2 == _L_WORDS[2]) & (
            (w1 > _L_WORDS[1]) | ((w1 == _L_WORDS[1]) & (w0 >= _L_WORDS[0]))
        )))
    )
    return ~ge


def prepare(items: Sequence[VerifyItem]):
    """Host-side packing: items -> fixed-shape numpy tensors + precheck
    bitmap, scalars as (n, 256) int32 bit tensors (the
    :func:`~mochi_tpu.crypto.curve.verify_prepared` input format)."""
    y_a, sign_a, y_r, sign_r, s_bytes, h_bytes, pre_ok = prepare_packed(items)
    s_bits = _bits_le(s_bytes).astype(np.int32)
    h_bits = _bits_le(h_bytes).astype(np.int32)
    return y_a, sign_a, y_r, sign_r, s_bits, h_bits, pre_ok


def prepare_packed(items: Sequence[VerifyItem]):
    """Host-side packing with scalars as (n, 32) uint8 LE bytes.

    Vectorized over the batch (numpy byte/bit ops; the only per-item Python
    is SHA-512 — hashlib's C — and the mod-L bignum): ~6 us/item vs the
    round-2a per-item loop's ~114 us/item, which capped the end-to-end
    service at ~9k items/s in front of a >100k items/s device pipeline.
    The byte (not bit) scalar form keeps the host->device transfer 32x
    smaller (device unpacks — curve.verify_prepared_packed).
    Semantics: malformed lengths, non-canonical y (>= p) and S >= L are
    rejected on host exactly as RFC 8032 decode / OpenSSL do.
    """
    n = len(items)
    y_a = np.zeros((n, F.NLIMBS), dtype=np.int32)
    y_r = np.zeros((n, F.NLIMBS), dtype=np.int32)
    sign_a = np.zeros(n, dtype=np.int32)
    sign_r = np.zeros(n, dtype=np.int32)
    s_bytes = np.zeros((n, 32), dtype=np.uint8)
    h_bytes = np.zeros((n, 32), dtype=np.uint8)
    pre_ok = np.zeros(n, dtype=bool)

    idx = [
        i
        for i, it in enumerate(items)
        if len(it.public_key) == 32 and len(it.signature) == 64
    ]
    if not idx:
        return y_a, sign_a, y_r, sign_r, s_bytes, h_bytes, pre_ok
    m = len(idx)

    a_rows = np.frombuffer(
        b"".join(bytes(items[i].public_key) for i in idx), dtype=np.uint8
    ).reshape(m, 32)
    sig_rows = np.frombuffer(
        b"".join(bytes(items[i].signature) for i in idx), dtype=np.uint8
    ).reshape(m, 64)
    r_rows = np.ascontiguousarray(sig_rows[:, :32])
    s_rows = np.ascontiguousarray(sig_rows[:, 32:])

    a_bits = _bits_le(a_rows)
    r_bits = _bits_le(r_rows)
    sa = a_bits[:, 255].astype(np.int32)
    sr = r_bits[:, 255].astype(np.int32)

    # canonicity: y < p on the masked value, S < L on the raw scalar
    a_masked = a_rows.copy()
    a_masked[:, 31] &= 0x7F
    r_masked = r_rows.copy()
    r_masked[:, 31] &= 0x7F
    ok = _lt_p(_words_le(a_masked)) & _lt_p(_words_le(r_masked))
    ok &= _lt_l(_words_le(s_rows))

    # h = SHA-512(R || A || M) mod L — ONLY for items that passed the
    # prechecks (a flood of non-canonical signatures over big messages
    # must not buy host hashing work; rejected lanes are masked by pre_ok
    # regardless).  The native batch path (native/hbatch.c: one C call,
    # embedded SHA-512 + Barrett mod-L) cuts the per-item python loop
    # (~2.1 of ~4.5 us/item at bucket 8192) that capped host prepare at
    # ~224k items/s; hashlib + python bignum is the automatic fallback.
    idx_arr = np.asarray(idx)
    ok_idx = idx_arr[ok]
    if len(ok_idx):
        hb = _hbatch()
        if hb is not None:
            msgs = b"".join(bytes(items[i].message) for i in ok_idx)
            lens = np.fromiter(
                (len(items[i].message) for i in ok_idx),
                dtype=np.uint64,
                count=len(ok_idx),
            )
            h_cat = hb.h_batch(
                np.ascontiguousarray(r_rows[ok]).tobytes(),
                np.ascontiguousarray(a_rows[ok]).tobytes(),
                msgs,
                lens.tobytes(),
            )
            h_bytes[ok_idx] = np.frombuffer(h_cat, dtype=np.uint8).reshape(-1, 32)
        else:
            h_parts = []
            for i in ok_idx:
                it = items[i]
                h_int = (
                    int.from_bytes(
                        hashlib.sha512(
                            bytes(it.signature[:32])
                            + bytes(it.public_key)
                            + bytes(it.message)
                        ).digest(),
                        "little",
                    )
                    % F.L_INT
                )
                h_parts.append(h_int.to_bytes(32, "little"))
            h_rows = np.frombuffer(b"".join(h_parts), dtype=np.uint8).reshape(-1, 32)
            h_bytes[ok_idx] = h_rows

    y_a[idx_arr] = _bits_to_limbs(a_bits)
    y_r[idx_arr] = _bits_to_limbs(r_bits)
    sign_a[idx_arr] = sa
    sign_r[idx_arr] = sr
    s_bytes[idx_arr] = s_rows
    pre_ok[idx_arr] = ok
    return y_a, sign_a, y_r, sign_r, s_bytes, h_bytes, pre_ok


# The general ladder's device program, under the name a profiler trace and
# the compile cache know it by.  The name is pinned here, not left to follow
# whatever the traced function happens to be called.
LADDER_PROGRAM = "jit_verify_prepared_packed"

_verify_packed_jit = jax.jit(
    curve.named_program(curve.verify_prepared_packed, LADDER_PROGRAM)
)


def verify_batch(
    items: Sequence[VerifyItem],
    device: Optional[jax.Device] = None,
    bucket: Optional[int] = None,
    registry=None,
    comb_gen: Optional[int] = None,
) -> List[bool]:
    """Verify a batch of Ed25519 signatures on the default JAX device.

    Returns a python bool list (the SPI bitmap).  Invalid encodings are
    rejected on host; padding lanes carry pre_ok=False and are sliced away.
    ``bucket`` forces a specific padded size (callers that know which program
    shapes are already compiled use it to avoid a fresh compile).

    ``registry`` (a :class:`mochi_tpu.crypto.comb.SignerRegistry`) enables
    the known-signer comb path: items whose public key is registered run
    the doubling-free comb kernel (~3x fewer field muls — comb.py
    docstring); the rest take the general ladder below.  Verdicts are
    identical either way (``tests/test_comb.py``); disable with
    ``MOCHI_COMB=0``.  ``comb_gen`` pins the registry generation the
    CALLER checked comb-readiness against: keys registered after that
    generation route to the general ladder (their table rows may not be
    in the pinned device table), and the device table keeps the pinned
    generation's shape so no retrace can hit this call.
    """
    if not items:
        return []
    if registry is not None and len(registry) and comb_enabled():
        from . import comb

        comb_pos: List[int] = []
        kidx: List[int] = []
        gen_pos: List[int] = []
        for i, it in enumerate(items):
            k = registry.index_of(it.public_key)
            if k is None or (comb_gen is not None and k >= comb_gen):
                gen_pos.append(i)
            else:
                comb_pos.append(i)
                kidx.append(k)
        _note_routing(len(comb_pos), len(gen_pos))
        if comb_pos:
            comb_items = [items[i] for i in comb_pos]
            key_arr = np.asarray(kidx, dtype=np.int32)
            if not gen_pos:
                return comb.verify_stream(
                    comb_items, key_arr, registry, device, bucket, comb_gen
                )
            gen_items = [items[i] for i in gen_pos]
            if len(comb_items) <= MAX_BUCKET and len(gen_items) <= MAX_BUCKET:
                # Mixed batch, both subsets single-chunk: DISPATCH both
                # programs before reading either back, so the two device
                # launches overlap instead of serializing on the first
                # readback (JAX dispatch is async).
                comb_launched = comb._dispatch_comb(
                    comb._prepare_comb(comb_items, key_arr, bucket),
                    registry,
                    device,
                    registry.device_table(device, comb_gen),
                )
                gen_launched = _launch(gen_items, device, bucket)
                comb_out = _readback(comb_launched, len(comb_items))
                gen_out = _readback(gen_launched, len(gen_items))
            else:
                comb_out = comb.verify_stream(
                    comb_items, key_arr, registry, device, bucket, comb_gen
                )
                gen_out = verify_batch(gen_items, device, bucket)
            out: List[bool] = [False] * len(items)
            for i, v in zip(comb_pos, comb_out):
                out[i] = v
            for i, v in zip(gen_pos, gen_out):
                out[i] = v
            return out
    if len(items) > MAX_BUCKET and bucket is None:
        # Two-level pipeline behind a bounded window, live memory
        # O(depth * MAX_BUCKET) instead of O(request):
        #   * a single worker thread runs chunk k+1's host PREPARE while the
        #     main thread blocks on chunk k-depth's readback (the device-
        #     wait releases the GIL, and prepare is numpy/hashlib C that
        #     mostly does too) — prepare cost ~6 us/item no longer
        #     serializes against the device;
        #   * launches stay ahead of readbacks by _PIPELINE_DEPTH, so
        #     transfer + device execution overlap across chunks (JAX
        #     dispatch is async).
        # Sequential chunking measured 19.1k sigs/s end-to-end on 64k
        # items; pipelined+packed ~70k; adding the prepare thread closes
        # most of the remaining gap to the same-buffer pipelined steady
        # state (config-2 artifact).
        window: deque = deque()
        out: List[bool] = []
        chunks = [items[i : i + MAX_BUCKET] for i in range(0, len(items), MAX_BUCKET)]
        metrics = _stage_metrics()  # the prepare worker serves this thread's call
        prep_fut = _prep_pool().submit(_prepare_padded, chunks[0], None, metrics)
        for k, chunk in enumerate(chunks):
            prepared = prep_fut.result()
            if k + 1 < len(chunks):
                prep_fut = _prep_pool().submit(
                    _prepare_padded, chunks[k + 1], None, metrics
                )
            window.append((_dispatch(prepared, device), len(chunk)))
            if len(window) >= _PIPELINE_DEPTH:
                out.extend(_readback(*window.popleft()))
        while window:
            out.extend(_readback(*window.popleft()))
        return out
    return _readback((_launch(items, device, bucket)), len(items))


# One persistent prepare worker: verify_batch is called from the verifier's
# flush executor, so a single overlap thread is enough and avoids per-call
# thread churn.
_PREP_POOL: Optional[ThreadPoolExecutor] = None
_PREP_POOL_LOCK = threading.Lock()


def _prep_pool() -> ThreadPoolExecutor:
    global _PREP_POOL
    if _PREP_POOL is None:
        with _PREP_POOL_LOCK:
            if _PREP_POOL is None:
                _PREP_POOL = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="mochi-prep"
                )
    return _PREP_POOL


# Stage timers of a launch (verifier/stages.py) tick in the registry of the
# backend call the thread is serving: JaxBatchBackend names its registry here
# before it calls down, and a bare verify_batch() ticks in the module's own.
_MODULE_METRICS = Metrics()
_STAGE_LOCK = threading.Lock()  # several flush threads share one registry


def _stage_metrics() -> Metrics:
    return getattr(_tls, "metrics", None) or _MODULE_METRICS


def _stage(timer: str, span: str, metrics: Optional[Metrics] = None) -> stages.stage:
    return stages.stage(metrics or _stage_metrics(), timer, span, lock=_STAGE_LOCK)


def _readback(launched, n: int) -> List[bool]:
    """Block on one launched chunk and combine with its host prechecks."""
    bitmap_dev, pre_ok = launched
    if bitmap_dev is None:  # all-rejected chunk: no device work was done
        return [False] * n
    with _stage(stages.READBACK, stages.SPAN_READBACK):
        bitmap = np.asarray(bitmap_dev)[:n]
    return [bool(b) for b in np.logical_and(bitmap, pre_ok)]


def _prepare_padded(
    items: Sequence[VerifyItem], bucket: Optional[int], metrics: Optional[Metrics] = None
):
    """Host half of a launch: pack + pad one chunk (pure numpy/hashlib —
    safe on the prepare worker thread, no JAX calls; ``metrics`` is the
    submitting thread's registry when this runs on the worker)."""
    with _stage(stages.PREPARE, stages.SPAN_PREPARE, metrics):
        return _pack_padded(items, bucket)


def _pack_padded(items: Sequence[VerifyItem], bucket: Optional[int]):
    y_a, sign_a, y_r, sign_r, s_sc, h_sc, pre_ok = prepare_packed(items)
    n = len(items)
    m = _bucket_size(n) if bucket is None else bucket
    assert m >= n
    if m != n:
        pad = ((0, m - n), (0, 0))
        y_a = np.pad(y_a, pad)
        y_r = np.pad(y_r, pad)
        s_sc = np.pad(s_sc, pad)
        h_sc = np.pad(h_sc, pad)
        sign_a = np.pad(sign_a, ((0, m - n),))
        sign_r = np.pad(sign_r, ((0, m - n),))
    return (y_a, sign_a, y_r, sign_r, s_sc, h_sc), pre_ok


def _dispatch(prepared, device: Optional[jax.Device] = None):
    """Device half of a launch: transfer + async dispatch (main thread).

    A chunk whose prechecks rejected EVERY item (e.g. a flood of
    non-canonical garbage) skips the device entirely — an attacker must
    spend real signing-grade work (canonical encodings) to buy device
    time; byte noise is absorbed at host precheck rates."""
    args, pre_ok = prepared
    if not pre_ok.any():
        return None, pre_ok
    _note_dispatch()
    with _stage(stages.DISPATCH, stages.SPAN_DISPATCH):
        if device is not None:
            args = tuple(jax.device_put(a, device) for a in args)
        return _verify_packed_jit(*args), pre_ok


# Monotone count of real device dispatches.  JaxBatchBackend uses it to
# tell "this call compiled/ran the bucket's program" from "the all-rejected
# fast path skipped the device" — marking a bucket ready without a compile
# would park the NEXT legitimate batch behind a synchronous 20-60 s compile
# (the stall the ready/chunking machinery exists to prevent).
_device_dispatches = 0

# Per-thread dispatch counters: readiness attribution must not observe
# OTHER threads' dispatches (BatchingVerifier runs up to max_inflight
# backend calls concurrently; a global-delta read would let thread B's
# dispatch mark thread A's bucket ready without a compile — code-review
# r4).  All dispatching happens on the calling thread (the prepare worker
# only packs), so thread-local deltas attribute exactly.
_tls = threading.local()


_comb_device_dispatches = 0

# Guards the module-global totals only: BatchingVerifier runs up to
# max_inflight backend calls concurrently and a bare += can drop counts
# (ADVICE r4).  The thread-local counters need no lock.
_dispatch_count_lock = threading.Lock()


def _note_dispatch(comb: bool = False) -> None:
    global _device_dispatches, _comb_device_dispatches
    with _dispatch_count_lock:
        _device_dispatches += 1
        if comb:
            _comb_device_dispatches += 1
    if comb:
        _tls.comb = getattr(_tls, "comb", 0) + 1
    else:
        _tls.general = getattr(_tls, "general", 0) + 1


def thread_dispatch_counts() -> tuple:
    """(general, comb) dispatches made by THIS thread (monotone)."""
    return (getattr(_tls, "general", 0), getattr(_tls, "comb", 0))


# Router occupancy (process-global, monotone): how many items the known-
# signer router sent down each leg, and how often one verify_batch call
# carried BOTH programs in a single merged-bitmap round trip.  Only counted
# at the routing decision (registry present), so the general-only fast path
# costs nothing; surfaced by verifier_stats → admin /status and
# /metrics.prom (docs/OPERATIONS.md §"Comb-first verification").
_comb_items_routed = 0
_ladder_items_routed = 0
_mixed_batches = 0


def _note_routing(n_comb: int, n_ladder: int) -> None:
    global _comb_items_routed, _ladder_items_routed, _mixed_batches
    with _dispatch_count_lock:
        _comb_items_routed += n_comb
        _ladder_items_routed += n_ladder
        if n_comb and n_ladder:
            _mixed_batches += 1


def comb_routing_counts() -> dict:
    """Snapshot of the router's occupancy counters (monotone totals)."""
    with _dispatch_count_lock:
        return {
            "comb_items": _comb_items_routed,
            "ladder_items": _ladder_items_routed,
            "mixed_batches": _mixed_batches,
        }


def device_dispatch_count() -> int:
    return _device_dispatches


def comb_enabled() -> bool:
    """Operator kill switch for the known-signer comb path.  Checked by
    routing AND by every comb compile site — MOCHI_COMB=0 must not keep
    paying 20-60 s comb compiles for programs that will never run."""
    return os.environ.get("MOCHI_COMB", "1") != "0"


def _launch(
    items: Sequence[VerifyItem],
    device: Optional[jax.Device] = None,
    bucket: Optional[int] = None,
):
    """Prepare, pad and DISPATCH one chunk; no result readback.

    Returns ``(device_bitmap, pre_ok)`` — the caller reads the bitmap back
    with ``np.asarray`` when it needs the verdicts, which is what lets
    multiple chunks pipeline on the device.  Scalars travel as packed
    bytes (32x smaller H2D transfer; the device unpacks).
    """
    return _dispatch(_prepare_padded(items, bucket), device)


class JaxBatchBackend:
    """``BatchBackend`` for :class:`mochi_tpu.verifier.spi.BatchingVerifier`.

    The replica's async batcher calls this from a thread executor; each call
    is one device program launch (cached compile per bucket shape).

    Compile-stall avoidance: XLA compiles one program per batch-size bucket
    (20-60s each).  A batch whose bucket isn't compiled yet is served in
    chunks of the largest *already-compiled* bucket, while the bigger
    bucket's compile is kicked off on a background thread — so ramping load
    never parks live traffic behind a compile (it would blow client
    timeouts; see the batching discipline in SURVEY.md §7).
    """

    def __init__(
        self,
        device: Optional[jax.Device] = None,
        min_device_items: Optional[int] = None,
        verify_fn=None,
        registry=None,
        metrics: Optional[Metrics] = None,
    ):
        self.device = device
        # stage timers (verifier/stages.py); the service hands in its own
        self.metrics = metrics if metrics is not None else Metrics()
        # Known-signer comb registry (crypto/comb.py); None = ladder only.
        self.registry = registry
        # Hook for alternative device paths (the mesh-sharded backend in
        # verifier/tpu.py) so they inherit the crossover + warmup +
        # compile-stall machinery below instead of re-implementing it.
        # Same contract as verify_batch(items, device=..., bucket=...).
        # None means "the module's verify_batch", resolved at CALL time so
        # tests that monkeypatch the module function still intercept.
        self._verify_fn = verify_fn
        # CPU/device crossover: a device launch costs a fixed dispatch +
        # readback, so batches below the crossover verify on the host.  The
        # default has not been measured on an attached chip (ROADMAP A1);
        # both sides are counted (``stats``) so the policy is visible.
        # Tunable: MOCHI_DEVICE_MIN_BATCH.
        if min_device_items is None:
            try:
                min_device_items = int(
                    os.environ.get("MOCHI_DEVICE_MIN_BATCH", "384")
                )
            except ValueError:
                min_device_items = 384
        self.min_device_items = max(0, min_device_items)
        self._ready: set[int] = set()
        self._compiling: set[int] = set()
        self._failed: set[int] = set()
        # Comb readiness is per (bucket, registry generation): capacity
        # growth changes the device-table SHAPE, invalidating every comb
        # compile, and live traffic must never park behind the recompile —
        # a stale bucket routes through the (compiled) general ladder
        # while the comb program re-warms in the background.
        self._ready_comb: dict = {}  # bucket -> generation compiled at
        self._comb_compiling: set = set()  # (bucket, generation)
        # Buckets whose comb compile FAILED — latched like _failed so a
        # persistently failing shape doesn't re-attempt a 20-60 s compile
        # on every batch; such buckets serve on the general path.
        self._comb_failed: set = set()
        # crossover accounting: items each side of min_device_items served
        self.host_routed_items = 0
        self.device_items = 0
        # program builds under way, (bucket, program) -> monotonic start, and
        # how many began and how many ended well: a build inside served
        # traffic holds the interpreter, and only these fields say so
        self._building: dict = {}
        self.builds_started = 0
        self.builds_finished = 0
        self._lock = threading.Lock()
        self._registry_mutex = threading.Lock()

    def _comb_capable(self) -> bool:
        """Whether this backend's device path can route through the comb
        kernel (the sharded subclass overrides with its own comb program)."""
        return self._verify_fn is None

    def _registry_device(self):
        """Placement for the registry's device tables (the sharded subclass
        returns a replicated NamedSharding instead of a single device)."""
        return self.device

    def register_signers(self, pubs: Sequence[bytes], extra_buckets=()) -> None:
        """Register known signers (cluster replica identities), creating
        the registry on first use.  Thread-safe; growth never stalls live
        traffic (already-registered signers keep comb at their pinned
        generation, new keys ride the general path until the background
        re-warms here finish)."""
        with self._registry_mutex:
            if self.registry is None:
                from .comb import SignerRegistry

                self.registry = SignerRegistry(device=self._registry_device())
            before = self.registry.generation
            self.registry.register_all(pubs)
            grew = self.registry.generation != before
        if grew and self._comb_capable():
            with self._lock:
                buckets = set(self._ready) | set(self._ready_comb)
            buckets |= {_bucket_size(int(b)) for b in extra_buckets}
            for bucket in sorted(buckets):
                self._comb_compile_in_background(bucket)

    def _comb_pinned_gen(self, bucket: int) -> Optional[int]:
        """Generation a comb program is provably compiled for at this
        bucket, or None.  An OLD generation stays valid forever: keys
        registered after it simply route to the general ladder (the
        ``comb_gen`` clamp in :func:`verify_batch`), so registry growth
        never interrupts comb service — it only leaves the new keys on
        the ladder until the background re-warm lands."""
        if (
            self.registry is None
            or not len(self.registry)
            or not comb_enabled()
            or not self._comb_capable()
        ):
            return None
        return self._ready_comb.get(bucket)

    def comb_ready_buckets(self) -> list:
        """Sorted buckets with a compiled comb program — snapshot taken
        under the backend lock so stats readers never race the background
        comb-warm threads' dict inserts (ADVICE r4)."""
        with self._lock:
            return sorted(self._ready_comb)

    def stats(self) -> dict:
        """Where this backend's work ran and on what: the device as JAX
        reports it, the crossover and the items each side of it served, and
        the compile state per bucket — including the buckets whose compile
        failed and whose traffic is therefore served some other way."""
        from ..utils.runtime import device_info

        with self._lock:
            st = {
                "min_device_items": self.min_device_items,
                "device_items": self.device_items,
                "host_routed_items": self.host_routed_items,
                "ready_buckets": sorted(self._ready),
                "failed_buckets": sorted(self._failed),
                "comb_failed_buckets": sorted(self._comb_failed),
            }
        st.update(device_info())
        return st

    def build_state(self) -> dict:
        """Builds under way and the counts of those begun and ended well
        (begun - ended well - under way = failed; ``stats`` names those)."""
        now = time.monotonic()
        with self._lock:
            return {
                "building": [
                    {"bucket": b, "program": prog, "since_s": round(now - t0, 3)}
                    for (b, prog), t0 in sorted(self._building.items())
                ],
                "builds_started": self.builds_started,
                "builds_finished": self.builds_finished,
            }

    @contextlib.contextmanager
    def _build(self, bucket: int, program: str):
        """One program build (compile or cache load, plus one run), on the
        calling thread: listed in ``build_state`` while it runs, timed, and
        a span on the profiler's clock."""
        _tls.metrics = self.metrics
        with self._lock:
            self._building[(bucket, program)] = time.monotonic()
            self.builds_started += 1
        try:
            with stages.stage(
                self.metrics, stages.BUILD, stages.SPAN_BUILD, lock=_STAGE_LOCK,
                bucket=bucket, program=program,
            ):
                yield
            with self._lock:
                self.builds_finished += 1
        finally:
            with self._lock:
                self._building.pop((bucket, program), None)

    def _build_comb(self, bucket: int) -> None:
        from .comb import COMB_PROGRAM

        with self._build(bucket, COMB_PROGRAM):
            self._warm_comb(bucket)

    def _call_verify(
        self,
        items,
        bucket: Optional[int] = None,
        use_comb=False,
        comb_gen: Optional[int] = None,
    ):
        fn = self._verify_fn if self._verify_fn is not None else verify_batch
        if use_comb and self._comb_capable():
            return fn(
                items,
                device=self.device,
                bucket=bucket,
                registry=self.registry,
                comb_gen=comb_gen,
            )
        return fn(items, device=self.device, bucket=bucket)

    def _warm_comb(self, bucket: int) -> None:
        """Compile the comb program for one bucket (synchronous; callers
        choose the thread) and record the generation it covers.  The
        compile runs against THAT generation's table shape — later
        dispatches pin the same generation, so the compiled program is
        exactly the one they hit."""
        from . import comb

        gen = self.registry.generation
        comb.warmup(self.registry, [bucket], self.device, gen=gen)
        with self._lock:
            # monotone: never regress a bucket below a generation another
            # warm already covered
            self._ready_comb[bucket] = max(gen, self._ready_comb.get(bucket, 0))

    def warmup(self, batch_sizes: Sequence[int]) -> None:
        """Synchronously pre-compile the given bucket sizes (boot path).

        With a registry attached this warms BOTH programs per bucket: the
        dummy items exercise the general ladder (their throwaway key is
        never registered), and ``comb.warmup`` compiles the comb program —
        otherwise the first live batch of registered-signer traffic would
        park behind a synchronous compile, exactly the stall the
        ready-bucket machinery exists to prevent."""
        for n in batch_sizes:
            bucket = _bucket_size(n)
            t0 = time.monotonic()
            # one forged lane among the valid ones: a program that compiles
            # but computes wrongly (or answers "valid" to everything) fails
            # the boot here instead of serving
            items = _dummy_items(bucket)
            items[-1] = VerifyItem(
                items[-1].public_key, b"mochi-tpu warmup forged", items[-1].signature
            )
            with self._build(bucket, LADDER_PROGRAM):
                verdicts = list(self._call_verify(items))
            if verdicts != [True] * (bucket - 1) + [False]:
                raise RuntimeError(
                    f"verify program at bucket {bucket} computed wrong "
                    f"verdicts at warmup ({sum(map(bool, verdicts))}/{bucket} "
                    "valid, expected all but the last)"
                )
            with self._lock:
                self._ready.add(bucket)
            t1 = time.monotonic()
            if (
                self.registry is not None
                and len(self.registry)
                and self._comb_capable()
                and comb_enabled()
            ):
                self._build_comb(bucket)
            LOG.info(
                "bucket %d warm: ladder %.1fs, comb %.1fs (compile or cache "
                "load, plus one run)",
                bucket, t1 - t0, time.monotonic() - t1,
            )

    def _compile_in_background(self, bucket: int) -> None:
        def run():
            try:
                items = _dummy_items(bucket)
                with self._build(bucket, LADDER_PROGRAM):
                    self._call_verify(items)
                with self._lock:
                    self._ready.add(bucket)
                if (
                    self.registry is not None
                    and len(self.registry)
                    and self._comb_capable()
                    and comb_enabled()
                ):
                    self._build_comb(bucket)
            except Exception:
                LOG.exception(
                    "background compile of verify bucket %d failed; "
                    "bucket disabled (batches keep chunking at smaller sizes)",
                    bucket,
                )
                with self._lock:
                    self._failed.add(bucket)
            finally:
                with self._lock:
                    self._compiling.discard(bucket)

        threading.Thread(target=run, name=f"verify-warm-{bucket}", daemon=True).start()

    def _comb_compile_in_background(self, bucket: int) -> None:
        """Re-warm a stale comb program (new bucket or registry growth)
        without blocking the caller's traffic (which keeps serving: comb
        at its pinned older generation, new keys on the ladder)."""
        if not comb_enabled() or self.registry is None or not len(self.registry):
            return
        gen = self.registry.generation
        with self._lock:
            if (bucket, gen) in self._comb_compiling or bucket in self._comb_failed:
                return
            self._comb_compiling.add((bucket, gen))

        def run():
            try:
                self._build_comb(bucket)
            except Exception:
                LOG.exception(
                    "comb compile (bucket %d) failed; bucket latched — its "
                    "traffic stays on the general path",
                    bucket,
                )
                with self._lock:
                    self._comb_failed.add(bucket)
            finally:
                with self._lock:
                    self._comb_compiling.discard((bucket, gen))

        threading.Thread(
            target=run, name=f"comb-warm-{bucket}", daemon=True
        ).start()

    def __call__(self, items: Sequence[VerifyItem]) -> Sequence[bool]:
        to_host = len(items) < self.min_device_items
        with self._lock:
            if to_host:
                self.host_routed_items += len(items)
            else:
                self.device_items += len(items)
        _tls.metrics = self.metrics  # where this thread's launch stages tick
        bucket = 0 if to_host else _bucket_size(len(items))
        # one tick and one span per backend call, by route, beside the item
        # counters above: the two sides of the crossover, timed where it is
        # decided.  epoch_us ties the profiler's clock to the epoch clock of
        # the clients' and replicas' obs/trace.py spans.
        with stages.stage(
            self.metrics,
            stages.FLUSH_HOST if to_host else stages.FLUSH_DEVICE,
            stages.SPAN_FLUSH,
            lock=_STAGE_LOCK,
            items=len(items),
            route="host" if to_host else "device",
            bucket=bucket,
            epoch_us=time.time_ns() // 1000,
        ):
            if not to_host:
                return self._serve_on_device(items, bucket)
            from . import keys as _keys

            with hostspan.span(stages.SPAN_HOST_VERIFY):
                return [
                    _keys.verify(it.public_key, it.message, it.signature)
                    for it in items
                ]

    def _serve_on_device(self, items: Sequence[VerifyItem], bucket: int) -> Sequence[bool]:
        registry_active = self.registry is not None and len(self.registry)
        pinned = self._comb_pinned_gen(bucket)
        with self._lock:
            general_ready = bucket in self._ready
            ready = sorted(self._ready)
            comb_ready_buckets = sorted(self._ready_comb)
            anything = bool(ready) or bool(comb_ready_buckets)
            schedule = (
                not general_ready
                and anything
                and bucket not in self._compiling
                and bucket not in self._failed
            )
            if schedule:
                self._compiling.add(bucket)
        if schedule:
            # Kick the background GENERAL compile here — before any serve
            # path returns.  The comb direct-serve branch below returns
            # without reaching the chunked fallback, and scheduling-
            # without-starting would leak the bucket in _compiling forever,
            # permanently disabling background compiles for it
            # (code-review r4).
            self._compile_in_background(bucket)
        use_comb = pinned is not None
        if (
            registry_active
            and comb_enabled()
            and anything
            and (pinned is None or pinned < self.registry.generation)
        ):
            # Comb program missing for this bucket, or compiled before the
            # latest registrations: serve THIS batch as-is (ladder, or
            # comb at the pinned older generation) and re-warm off the
            # hot path so the new keys join the comb path shortly.  Not
            # gated on general readiness: comb-only traffic never
            # populates _ready at all (code-review r4).
            self._comb_compile_in_background(bucket)
        # Direct serve when this bucket has a compiled program for its
        # traffic: the general program, or — for registered-signer traffic
        # — the comb program alone (an unregistered leftover in that
        # posture is rare enough to accept its one-off compile), or when
        # NOTHING is compiled yet (first ever call eats the compile;
        # servers avoid it via boot warmup).
        ready_now = general_ready or (use_comb and registry_active)
        if ready_now or not anything:
            # Bucket compiled, or nothing compiled yet (first ever call):
            # run directly (the latter eats one synchronous compile — servers
            # avoid it via boot-time warmup).  Only a call that actually
            # dispatched the device program proves the bucket is compiled;
            # the all-rejected fast path skips the device and must not mark
            # readiness.  With a registry, comb and general dispatches are
            # counted separately — each program proves only ITS OWN
            # readiness (a comb-only dispatch must not green-light the
            # general program, or a later mixed batch stalls on a
            # "ready" bucket).
            if registry_active and not anything and comb_enabled():
                # first-ever call, nothing compiled: pin the current
                # generation and eat both compiles synchronously
                use_comb = True
                pinned = self.registry.generation
            gen = pinned if use_comb else None
            # bucket is passed explicitly (when it is a single launch) so
            # a MIXED batch's subsets pad to this compiled shape instead
            # of their own smaller, never-compiled natural buckets; an
            # oversize batch keeps bucket=None so verify_batch's bounded
            # MAX_BUCKET launch-window pipeline still applies
            # (code-review r4, both directions).
            explicit = bucket if bucket <= MAX_BUCKET else None
            general_before, comb_before = thread_dispatch_counts()
            out = self._call_verify(
                items, bucket=explicit, use_comb=use_comb, comb_gen=gen
            )
            general_after, comb_after = thread_dispatch_counts()
            comb_n = comb_after - comb_before
            general_n = general_after - general_before
            if explicit is not None:
                with self._lock:
                    if general_n > 0:
                        self._ready.add(bucket)
                    if comb_n > 0 and gen is not None:
                        self._ready_comb[bucket] = max(
                            gen, self._ready_comb.get(bucket, 0)
                        )
            return out
        # Serve via already-compiled shapes only: chunk at the largest
        # compiled bucket and pad each chunk up to the smallest compiled
        # bucket that fits, so no chunk can trigger a synchronous compile.
        # General-program buckets serve any traffic; with comb-only
        # history (registered-signer service without boot warmup) the
        # comb buckets serve instead — an unregistered leftover there is
        # the rare accept-one-compile case documented above.
        targets = ready if ready else comb_ready_buckets
        largest_ready = targets[-1]
        out: List[bool] = []
        for i in range(0, len(items), largest_ready):
            chunk = items[i : i + largest_ready]
            target = next(b for b in targets if b >= len(chunk))
            tgt_gen = self._comb_pinned_gen(target)
            out.extend(
                self._call_verify(
                    chunk,
                    bucket=target,
                    use_comb=tgt_gen is not None,
                    comb_gen=tgt_gen,
                )
            )
        return out


def _dummy_items(n: int) -> List[VerifyItem]:
    from .keys import generate_keypair

    kp = generate_keypair()
    msg = b"mochi-tpu warmup"
    sig = kp.sign(msg)
    return [VerifyItem(kp.public_key, msg, sig)] * n
