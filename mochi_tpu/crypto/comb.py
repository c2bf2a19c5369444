"""Known-signer comb verification: doubling-free Ed25519 for registered keys.

The cluster's verification traffic is not random-key: every grant
certificate, session handshake, and view-change vote is signed by one of
the n replica identities in the cluster configuration (SURVEY.md §2.4's
verify seam; BASELINE.json's n=64 f=21 north star).  The general ladder
(:func:`mochi_tpu.crypto.curve.double_scalar_mul_windowed`) cannot exploit
that: with an arbitrary public key A it must interleave 256 sequential
doublings with its window additions, and those doublings are both the FLOP
majority (~2048 of ~3600 field muls/signature) and the dependency chain
that keeps the VPU pipeline shallow.

For a REGISTERED key the doublings can be precomputed away entirely —
the classic fixed-base windowing idea (Lim-Lee comb / Pippenger
precomputation, as in ed25519 ref10's basepoint tables), applied here to
the SIGNER set rather than the curve basepoint.  On registration the
host computes, once per signer, the Niels-form table

    T[w][d] = [d * 16^w](-A)      w in 0..63, d in 0..8

(and the module keeps the analogous constant table for the basepoint B),
so the device-side check [S]B + [h](-A) == R becomes a pure sum of 128
table points — 64 constant-table selects for the B comb plus 64 per-lane
gathers from the signer table — with ZERO doublings:

    Q = sum_w  B_tab[w][s_w]  +  T[key][w][h_w]      (signed 4-bit digits)

Per item that is ~260 field muls for the R decompression plus 128 Niels
mixed additions (~900 muls): **~3x fewer field muls than the ladder and a
~3x shallower sequential chain** (128 dependent madds vs 256 doublings
interleaved with 128 additions).  The per-window signer lookup is a row
gather from a (K*576, 51) int32 table resident on device (~117 KB per
signer: 64 windows x 9 entries x 3 coords x 17 limbs), so even n=64
clusters stay ~7.5 MB.

Verdict semantics are IDENTICAL to the general path: the same cofactorless
equation with exact limb arithmetic, the same host prechecks
(:func:`mochi_tpu.crypto.batch_verify.prepare_packed`), the same RFC 8032
decompression checks — registration itself performs the host-side decode
and refuses non-canonical or non-point keys, which then simply fall
through to the general path.  ``tests/test_comb.py`` checks the bitmap
differentially against OpenSSL and the ladder, including forgeries,
wrong-key and malformed items, and mixed registered/unregistered batches.

This module is pure compute + registry; routing lives in
:func:`mochi_tpu.crypto.batch_verify.verify_batch` (``registry=`` arg) so
callers keep one entry point.  The reference has no counterpart for any of
this (it never signs — ``MochiProtocol.proto:123``).

Considered and not built: 5-bit windows (51 windows x 17 entries).  They
cut the madd count ~20% (128 -> 102) but the basepoint masked-select grows
from 9x64 to 17x51 terms (+50% select work), tables grow 1.5x, and the
signed recode needs base-32 carries — net model estimate <10% either way.
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import curve
from . import field as F
from ..verifier import stages

LOG = logging.getLogger(__name__)

N_WINDOWS = 64
N_ENTRIES = 9  # signed 4-bit digits: magnitudes 0..8
ROW_WIDTH = 3 * F.NLIMBS  # ypx | ymx | xy2d


# --------------------------------------------------------------------------
# Host point arithmetic (python ints, extended coordinates).  Table builds
# run one batched inversion at the end (Montgomery trick) instead of two
# modular inversions per affine addition — ~10 ms per signer instead of
# ~100 ms, which matters when a service registers a 64-replica identity
# set at boot.


def _ext_add(p, q):
    """Complete unified addition (same add-2008-hwcd-3 law as the device)
    on python-int extended coordinates (X, Y, Z, T)."""
    P = F.P_INT
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * F.D_INT * t1 % P * t2 % P
    d = 2 * z1 * z2 % P
    e = b - a
    f = d - c
    g = d + c
    h = b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


_EXT_IDENTITY = (0, 1, 1, 0)


def _batch_affine(points) -> List[Tuple[int, int]]:
    """Extended -> affine for a list of points with ONE modular inversion."""
    P = F.P_INT
    zs = [pt[2] for pt in points]
    prefix = [1]
    for z in zs:
        prefix.append(prefix[-1] * z % P)
    inv_all = pow(prefix[-1], P - 2, P)
    out: List[Optional[Tuple[int, int]]] = [None] * len(points)
    for i in range(len(points) - 1, -1, -1):
        z_inv = prefix[i] * inv_all % P
        inv_all = inv_all * zs[i] % P
        x, y = points[i][0] * z_inv % P, points[i][1] * z_inv % P
        out[i] = (x, y)
    return out  # type: ignore[return-value]


def decompress_host(pub: bytes) -> Optional[Tuple[int, int]]:
    """RFC 8032 §5.1.3 point decoding on host ints.

    Mirrors :func:`mochi_tpu.crypto.curve.decompress` exactly (same
    candidate-root construction, same rejects: y >= p, no root, x = 0 with
    sign bit set).  Returns affine (x, y) or None.
    """
    if len(pub) != 32:
        return None
    P = F.P_INT
    enc = int.from_bytes(pub, "little")
    sign = enc >> 255
    y = enc & ((1 << 255) - 1)
    if y >= P:
        return None
    u = (y * y - 1) % P
    v = (F.D_INT * y * y + 1) % P
    x = u * pow(v, 3, P) % P * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P) % P
    vxx = v * x * x % P
    if vxx == u:
        pass
    elif vxx == (-u) % P:
        x = x * F.SQRT_M1_INT % P
    else:
        return None
    if x == 0 and sign == 1:
        return None
    if (x & 1) != sign:
        x = P - x
    return (x, y)


def _comb_table_rows(x: int, y: int) -> np.ndarray:
    """(64, 9, 51) int32 Niels comb table for the affine point (x, y):
    row [w][d] = [d * 16^w](x, y) as (y+x | y-x | 2dxy) limbs."""
    P = F.P_INT
    base = (x, y, 1, x * y % P)
    pts = []
    for _ in range(N_WINDOWS):
        acc = _EXT_IDENTITY
        for _d in range(N_ENTRIES - 1):
            acc = _ext_add(acc, base)
            pts.append(acc)
        # next window's base = [16] * base: 4 doublings of the current base
        for _ in range(4):
            base = _ext_add(base, base)
    affine = _batch_affine(pts)
    rows = np.zeros((N_WINDOWS, N_ENTRIES, ROW_WIDTH), dtype=np.int32)
    # d = 0 is the identity: Niels (1, 1, 0)
    one = F.int_to_limbs(1)
    rows[:, 0, : F.NLIMBS] = one
    rows[:, 0, F.NLIMBS : 2 * F.NLIMBS] = one
    k = 0
    for w in range(N_WINDOWS):
        for d in range(1, N_ENTRIES):
            ax, ay = affine[k]
            k += 1
            rows[w, d, : F.NLIMBS] = F.int_to_limbs((ay + ax) % P)
            rows[w, d, F.NLIMBS : 2 * F.NLIMBS] = F.int_to_limbs((ay - ax) % P)
            rows[w, d, 2 * F.NLIMBS :] = F.int_to_limbs(2 * F.D_INT * ax % P * ay % P)
    return rows


def signer_table(pub: bytes) -> Optional[np.ndarray]:
    """(64, 9, 51) comb table for -A (the verify equation uses [h](-A)),
    or None if ``pub`` is not a canonical curve-point encoding."""
    aff = decompress_host(pub)
    if aff is None:
        return None
    x, y = aff
    return _comb_table_rows((F.P_INT - x) % F.P_INT if x else 0, y)


_B_COMB: Optional[np.ndarray] = None
_B_COMB_LOCK = threading.Lock()


def _b_comb() -> np.ndarray:
    """(64, 9, 51) comb table for +B, built lazily once (import-time build
    would add ~10 ms to every process that merely imports the package)."""
    global _B_COMB
    if _B_COMB is None:
        with _B_COMB_LOCK:
            if _B_COMB is None:
                _B_COMB = _comb_table_rows(F.BX_INT, F.BY_INT)
    return _B_COMB


# --------------------------------------------------------------------------
# Registry


class SignerRegistry:
    """Pubkey -> device comb table registry.

    Capacity is padded to powers of two (min 8) so XLA compiles at most a
    handful of table shapes as the signer set grows; unused slots hold
    identity rows.  Thread-safe: the cluster registers replica identities
    at boot and (rarely) on live reconfiguration while the verifier's
    flush executor reads concurrently.
    """

    def __init__(self, device: Optional[jax.Device] = None):
        self._device = device
        self._idx: Dict[bytes, int] = {}
        self._tables: List[np.ndarray] = []
        self._rejected: set[bytes] = set()
        self._lock = threading.Lock()
        # (device, capacity) -> (device array, rows filled at build time)
        self._dev_tables: Dict[tuple, tuple] = {}

    def __len__(self) -> int:
        return len(self._idx)

    @property
    def generation(self) -> int:
        """Monotone registration count.  A comb program compiled against an
        older generation may be stale (capacity growth changes the table
        SHAPE and forces a recompile); the backend gates comb routing on
        generation match so live traffic never parks behind that compile."""
        return len(self._tables)

    def register(self, pub: bytes) -> Optional[int]:
        """Add a signer; returns its index, or None for invalid encodings
        (which the caller simply leaves to the general verify path)."""
        pub = bytes(pub)
        with self._lock:
            if pub in self._idx:
                return self._idx[pub]
            if pub in self._rejected:
                return None
        tab = signer_table(pub)  # outside the lock: ~10 ms of host math
        with self._lock:
            if pub in self._idx:  # raced with another registrar
                return self._idx[pub]
            if tab is None:
                self._rejected.add(pub)
                return None
            idx = len(self._tables)
            self._tables.append(tab)
            self._idx[pub] = idx
            # device tables invalidate via the generation check in
            # device_table() (gen = table count at build time)
            return idx

    def register_all(self, pubs: Sequence[bytes]) -> None:
        for p in pubs:
            self.register(p)

    def index_of(self, pub: bytes) -> Optional[int]:
        return self._idx.get(bytes(pub))

    @staticmethod
    def _capacity_for(gen: int) -> int:
        cap = 8
        while cap < gen:
            cap *= 2
        return cap

    def device_table(
        self, device: Optional[jax.Device] = None, gen: Optional[int] = None
    ) -> jax.Array:
        """(capacity * 576, 51) int32 flat table on the target device.

        ``gen`` pins the GENERATION the caller's compiled program (and its
        key-index routing) was checked against: the returned table has the
        capacity of that generation — so a registration that crossed a
        capacity boundary concurrently cannot change the table SHAPE under
        a ready-checked dispatch and force a synchronous retrace
        (code-review r4).  Content newer than ``gen`` is harmless (rows
        beyond the caller's generation are never indexed); content is
        always at least ``gen`` rows (tables only append).  Cached per
        (device, capacity), rebuilt when registrations outgrow the cache.
        """
        device = device if device is not None else self._device
        with self._lock:
            cur = len(self._tables)
            g = cur if gen is None else gen
            cap = self._capacity_for(max(1, g))
            key = (device, cap)
            cached = self._dev_tables.get(key)
            if cached is not None and cached[1] >= min(cur, cap):
                return cached[0]
            n_rows = min(cur, cap)
            flat = np.zeros((cap, N_WINDOWS, N_ENTRIES, ROW_WIDTH), np.int32)
            if n_rows:
                flat[:n_rows] = np.stack(self._tables[:n_rows])
            else:
                # even an empty registry ships well-formed identity rows
                one = F.int_to_limbs(1)
                flat[:, :, :, : F.NLIMBS] = one
                flat[:, :, :, F.NLIMBS : 2 * F.NLIMBS] = one
            flat = flat.reshape(cap * N_WINDOWS * N_ENTRIES, ROW_WIDTH)
            arr = (
                jax.device_put(flat, device)
                if device is not None
                else jnp.asarray(flat)
            )
            self._dev_tables[key] = (arr, n_rows)
            return arr


# --------------------------------------------------------------------------
# Device kernel

SCOPE_COMB = "mochi_comb"  # the comb loop, beside curve.py's phase scopes

def verify_comb_prepared(
    table_flat: jnp.ndarray,
    key_idx: jnp.ndarray,
    y_r: jnp.ndarray,
    sign_r: jnp.ndarray,
    s_bytes: jnp.ndarray,
    h_bytes: jnp.ndarray,
) -> jnp.ndarray:
    """Batched comb verify -> (B,) validity bitmap.

    ``table_flat``: (K*576, 51) int32 signer tables (row [k*576 + w*9 + d]
    = Niels [d*16^w](-A_k)); ``key_idx``: (B,) int32 registry indices;
    ``y_r``/``sign_r``: R encodings as in
    :func:`mochi_tpu.crypto.curve.verify_prepared`; scalars as (B, 32)
    packed LE bytes.  Public-key validity is the REGISTRY's invariant
    (registration performs the host-side RFC 8032 decode), so the kernel
    checks only R's decode and the group equation.
    """
    with jax.named_scope(curve.SCOPE_UNPACK):
        s_dig = curve.digits4_from_bits(curve.unpack_bits(s_bytes).T)
        h_dig = curve.digits4_from_bits(curve.unpack_bits(h_bytes).T)
        s_mag, s_neg = curve.recode_signed4(s_dig)
        h_mag, h_neg = curve.recode_signed4(h_dig)

    with jax.named_scope(curve.SCOPE_DECOMPRESS):
        r_point, ok_r = curve.decompress(y_r.T, sign_r)
    lanes = y_r.shape[:1]
    with jax.named_scope(SCOPE_COMB):
        q = _comb_accumulate(table_flat, key_idx, s_mag, s_neg, h_mag, h_neg, lanes)
    with jax.named_scope(curve.SCOPE_COMPARE):
        eq_x = F.eq(q.x, F.mul(r_point.x, q.z))
        eq_y = F.eq(q.y, F.mul(r_point.y, q.z))
        return ok_r & eq_x & eq_y


def _comb_accumulate(table_flat, key_idx, s_mag, s_neg, h_mag, h_neg, lanes):
    """Q = [S]B + [h](-A) from the signer and basepoint comb tables: the
    comb loop of :func:`verify_comb_prepared`, 128 Niels mixed additions in
    one ``fori_loop`` (64 iterations x 2 madds x 7 muls ~ 900 field muls)."""

    # One upfront row gather for all 64 windows — (64, B, 51) — instead of
    # 64 small in-loop gathers: XLA schedules a single fused gather and the
    # loop body stays pure VPU work on contiguous slices.
    win = jnp.arange(N_WINDOWS, dtype=jnp.int32)[:, None]
    flat_idx = key_idx[None, :] * (N_WINDOWS * N_ENTRIES) + win * N_ENTRIES + h_mag
    a_rows = jnp.take(table_flat, flat_idx, axis=0, mode="clip")

    b_tab = jnp.asarray(_b_comb())  # (64, 9, 51) trace-time constant

    h_neg_i = h_neg.astype(jnp.int32)
    s_neg_i = s_neg.astype(jnp.int32)

    def body(w, q):
        q = curve.Point(*q)
        # --- signer-table point (gathered rows, limbs last -> limbs first)
        row = lax.dynamic_index_in_dim(a_rows, w, axis=0, keepdims=False).T
        aypx = row[: F.NLIMBS]
        aymx = row[F.NLIMBS : 2 * F.NLIMBS]
        axy2d = row[2 * F.NLIMBS :]
        hn = lax.dynamic_index_in_dim(h_neg_i, w, axis=0, keepdims=False).astype(bool)
        aypx, aymx = F.select(hn, aymx, aypx), F.select(hn, aypx, aymx)
        axy2d = F.select(hn, F.neg(axy2d), axy2d)
        q = curve.madd_niels(q, aypx, aymx, axy2d)
        # --- basepoint comb entry (constant table, masked 9-entry select)
        bw = lax.dynamic_index_in_dim(b_tab, w, axis=0, keepdims=False)  # (9, 51)
        sd = lax.dynamic_index_in_dim(s_mag, w, axis=0, keepdims=False)  # (B,)
        acc = jnp.zeros((ROW_WIDTH, *sd.shape), jnp.int32)
        for e in range(N_ENTRIES):
            acc = acc + jnp.where((sd == e)[None], bw[e][:, None], 0)
        bypx = acc[: F.NLIMBS]
        bymx = acc[F.NLIMBS : 2 * F.NLIMBS]
        bxy2d = acc[2 * F.NLIMBS :]
        sn = lax.dynamic_index_in_dim(s_neg_i, w, axis=0, keepdims=False).astype(bool)
        bypx, bymx = F.select(sn, bymx, bypx), F.select(sn, bypx, bymx)
        bxy2d = F.select(sn, F.neg(bxy2d), bxy2d)
        q = curve.madd_niels(q, bypx, bymx, bxy2d)
        return tuple(q)

    q = lax.fori_loop(
        0, N_WINDOWS, body, tuple(curve.identity(lanes)), unroll=curve.LADDER_UNROLL
    )
    return curve.Point(*q)


# The comb's device program, under the name a profiler trace and the compile
# cache know it by (pinned here; see batch_verify.LADDER_PROGRAM).
COMB_PROGRAM = "jit_verify_comb_prepared"

_verify_comb_jit = jax.jit(curve.named_program(verify_comb_prepared, COMB_PROGRAM))


# --------------------------------------------------------------------------
# Launch machinery — mirrors batch_verify's prepare/dispatch/readback split
# (same pipelining discipline, same padding/precheck semantics), with the
# signer index as an extra lane tensor and y_a dropped (the registry IS the
# pubkey check).


def _prepare_comb(items, key_idx: np.ndarray, bucket: Optional[int], metrics=None):
    """Host half: pack + pad one chunk (numpy/hashlib only — safe on the
    prepare worker thread; ``metrics`` as in ``batch_verify._prepare_padded``)."""
    from . import batch_verify as BV

    with BV._stage(stages.PREPARE, stages.SPAN_PREPARE, metrics):
        return _pack_comb(items, key_idx, bucket)


def _pack_comb(items, key_idx: np.ndarray, bucket: Optional[int]):
    from . import batch_verify as BV

    _, _, y_r, sign_r, s_sc, h_sc, pre_ok = BV.prepare_packed(items)
    n = len(items)
    m = BV._bucket_size(n) if bucket is None else bucket
    assert m >= n
    if m != n:
        pad2 = ((0, m - n), (0, 0))
        y_r = np.pad(y_r, pad2)
        s_sc = np.pad(s_sc, pad2)
        h_sc = np.pad(h_sc, pad2)
        sign_r = np.pad(sign_r, ((0, m - n),))
        key_idx = np.pad(key_idx, ((0, m - n),))
    return (key_idx.astype(np.int32), y_r, sign_r, s_sc, h_sc), pre_ok


def comb_dispatch_count() -> int:
    """Monotone process-global count of real comb-program device dispatches
    (tests and stats; the backend's readiness marking uses the
    THREAD-LOCAL counters in ``batch_verify.thread_dispatch_counts``)."""
    from . import batch_verify as BV

    return BV._comb_device_dispatches


def _dispatch_comb(prepared, registry: SignerRegistry, device, table=None):
    """Device half: transfer + async dispatch (main thread — device_table
    may device_put on first use).  Shares batch_verify's all-rejected fast
    path and its dispatch counter (the backend's compile-readiness
    tracking counts REAL device dispatches, comb or general).

    ``table``: the device table PINNED at routing time.  Callers that
    checked comb-readiness must pass the table they checked against — a
    concurrent registration can grow the live registry's table SHAPE
    between the check and this dispatch, and fetching it here would
    retrace + compile synchronously on the hot path (code-review r4).
    Verdicts with a pinned older table stay exact: the items were
    index-mapped against that table's generation."""
    from . import batch_verify as BV

    args, pre_ok = prepared
    if not pre_ok.any():
        return None, pre_ok
    BV._note_dispatch(comb=True)
    with BV._stage(stages.DISPATCH, stages.SPAN_DISPATCH):
        if table is None:
            table = registry.device_table(device)
        if device is not None:
            args = tuple(jax.device_put(a, device) for a in args)
        key_idx, y_r, sign_r, s_sc, h_sc = args
        return _verify_comb_jit(table, key_idx, y_r, sign_r, s_sc, h_sc), pre_ok


def verify_stream(
    items,
    key_idx: np.ndarray,
    registry: SignerRegistry,
    device: Optional[jax.Device] = None,
    bucket: Optional[int] = None,
    gen: Optional[int] = None,
) -> List[bool]:
    """Comb-verify ``items`` (all with registered signers; ``key_idx``
    aligned, every index < ``gen`` when pinned) -> bool list.  Oversized
    requests chunk at ``batch_verify.MAX_BUCKET`` behind the same bounded
    launch window and prepare-thread overlap as the general path."""
    from . import batch_verify as BV

    if not items:
        return []
    # Pin the device table ONCE for the whole stream (at the caller's
    # checked generation when given): concurrent registration must not
    # swap in a new-shaped table mid-stream — every later chunk would
    # retrace (see _dispatch_comb docstring).
    table = registry.device_table(device, gen)
    if len(items) > BV.MAX_BUCKET and bucket is None:
        from collections import deque

        window: deque = deque()
        out: List[bool] = []
        chunks = [
            (items[i : i + BV.MAX_BUCKET], key_idx[i : i + BV.MAX_BUCKET])
            for i in range(0, len(items), BV.MAX_BUCKET)
        ]
        pool = BV._prep_pool()
        metrics = BV._stage_metrics()
        prep_fut = pool.submit(_prepare_comb, chunks[0][0], chunks[0][1], None, metrics)
        for k, (chunk, _) in enumerate(chunks):
            prepared = prep_fut.result()
            if k + 1 < len(chunks):
                nxt = chunks[k + 1]
                prep_fut = pool.submit(_prepare_comb, nxt[0], nxt[1], None, metrics)
            window.append(
                (_dispatch_comb(prepared, registry, device, table), len(chunk))
            )
            if len(window) >= BV._PIPELINE_DEPTH:
                out.extend(BV._readback(*window.popleft()))
        while window:
            out.extend(BV._readback(*window.popleft()))
        return out
    launched = _dispatch_comb(
        _prepare_comb(items, key_idx, bucket), registry, device, table
    )
    return BV._readback(launched, len(items))


def warmup(
    registry: SignerRegistry,
    batch_sizes,
    device: Optional[jax.Device] = None,
    gen: Optional[int] = None,
) -> None:
    """Pre-compile the comb program for the given bucket sizes against the
    table shape of generation ``gen`` (default: current).  Compiles are
    keyed on shapes only, so zero-filled operands suffice — no signing
    needed.  Callers that record readiness for a generation must pass that
    generation so the compiled shape is the one later dispatches pin."""
    from . import batch_verify as BV

    table = registry.device_table(device, gen)
    for n in batch_sizes:
        m = BV._bucket_size(int(n))
        bm = _verify_comb_jit(
            table,
            jnp.zeros((m,), jnp.int32),
            jnp.zeros((m, F.NLIMBS), jnp.int32),
            jnp.zeros((m,), jnp.int32),
            jnp.zeros((m, 32), jnp.uint8),
            jnp.zeros((m, 32), jnp.uint8),
        )
        np.asarray(bm)  # block until the program compiled and ran
