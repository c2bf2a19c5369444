"""Ed25519 group operations in extended twisted-Edwards coordinates — JAX.

Everything is branchless and fixed-shape so the whole verify lowers to one
XLA program: the addition law used here is the *complete* law for twisted
Edwards curves with a = -1 (a is a square mod p since p === 1 mod 4, d is a
non-square), so identity/doubling/degenerate cases need no case analysis —
exactly the property that makes Ed25519 verification map cleanly onto a
vector machine (SURVEY.md §7: "no data-dependent Python control flow").

Layout (round-2 rework): **limbs-leading**.  A point is a 4-tuple
(X, Y, Z, T) of field elements shaped ``(17, ...lanes)``
(:mod:`mochi_tpu.crypto.field`), with x = X/Z, y = Y/Z, T = XY/Z — batch on
the trailing lane axis, which is the TPU's 128-wide vector axis, so every
field op runs on dense lane vectors.

Scalars arrive as little-endian bit arrays precomputed on the host (the host
also does SHA-512 and the mod-L reduction: variable-length hashing is host
work; the device sees only fixed-shape integer tensors).

Table lookups in the windowed ladder are branchless masked-select sums
(:func:`select_entry`): data-dependent per-lane gathers don't vectorize on
the TPU VPU; 9 masked adds (signed windows) do.

The reference never implements any of this (it never signs — SURVEY.md
preamble); this is the north-star TPU verifier path of BASELINE.json.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import field as F

# Phases of the device programs, as ``jax.named_scope`` names: they go into
# each operation's metadata (a profiler trace shows the path under an op's
# ``tf_op``/``long_name``), so the loops a trace prints as ``while.33`` say
# which one they are.  Metadata only: the compiled program, its verdicts and
# its compile-cache key are what they were.
SCOPE_UNPACK = "mochi_scalar_unpack"
SCOPE_DECOMPRESS = "mochi_decompress"
SCOPE_LADDER = "mochi_ladder"
SCOPE_COMPARE = "mochi_compare"


def named_program(fn, program: str):
    """``fn`` under the name ``jax.jit`` will give its program: ``program``
    is ``jit_<name>``, pinned by the caller as a constant instead of
    following whatever the traced function is called today."""
    assert program.startswith("jit_"), program

    @functools.wraps(fn)
    def entry(*args, **kwargs):
        return fn(*args, **kwargs)

    entry.__name__ = entry.__qualname__ = program[len("jit_"):]
    return entry

# Ladder fori_loop unroll factor (1 = loop 64 window bodies; higher trades
# compile time for a larger per-iteration fusion scope on the VPU).
LADDER_UNROLL = 1


class Point(NamedTuple):
    """Extended coordinates (X : Y : Z : T), x=X/Z, y=Y/Z, T=XY/Z.
    Each coordinate is a (17, ...lanes) limb array."""

    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray
    t: jnp.ndarray


def identity(lanes) -> Point:
    zero = F.zeros(lanes)
    one = F.one(lanes)
    return Point(zero, one, one, zero)


def basepoint(lanes) -> Point:
    """The Ed25519 basepoint B, broadcast over the lane shape."""
    bx = F.const(F.BX_INT, lanes)
    by = F.const(F.BY_INT, lanes)
    return Point(bx, by, F.one(lanes), F.mul(bx, by))


# 2*d mod p, a trace-time constant
_D2_INT = (2 * F.D_INT) % F.P_INT


def add(p: Point, q: Point) -> Point:
    """Complete unified addition (add-2008-hwcd-3, a=-1). ~9 field muls."""
    a = F.mul(F.sub(p.y, p.x), F.sub(q.y, q.x))
    b = F.mul(F.add(p.y, p.x), F.add(q.y, q.x))
    c = F.mul(F.mul(p.t, F.const(_D2_INT, p.t.shape[1:])), q.t)
    d = F.mul(F.add(p.z, p.z), q.z)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return Point(F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def double(p: Point) -> Point:
    """Doubling (dbl-2008-hwcd, a=-1). ~4 muls + 4 squares."""
    a = F.square(p.x)
    b = F.square(p.y)
    c = F.mul_small(F.square(p.z), 2)
    h = F.add(a, b)
    e = F.sub(h, F.square(F.add(p.x, p.y)))
    g = F.sub(a, b)
    f = F.add(c, g)
    return Point(F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def negate(p: Point) -> Point:
    return Point(F.neg(p.x), p.y, p.z, F.neg(p.t))


def select_point(cond: jnp.ndarray, p: Point, q: Point) -> Point:
    return Point(*(F.select(cond, a, b) for a, b in zip(p, q)))


def decompress(y_limbs: jnp.ndarray, sign: jnp.ndarray) -> Tuple[Point, jnp.ndarray]:
    """RFC 8032 §5.1.3 point decoding, batched and branchless.

    ``y_limbs``: (17, lanes) with y < p (host prechecks canonicity);
    ``sign``: (lanes,) int32 in {0,1} — the x-parity bit from byte 31.
    Returns (point with Z=1, ok) where ok=False marks non-points
    (x^2 = u/v has no root, or x=0 with sign=1).
    """
    lanes = y_limbs.shape[1:]
    yy = F.square(y_limbs)
    one = F.one(lanes)
    u = F.sub(yy, one)  # y^2 - 1
    v = F.add(F.mul(yy, F.const(F.D_INT, lanes)), one)  # d*y^2 + 1

    # candidate root x = u * v^3 * (u*v^7)^((p-5)/8)
    v3 = F.mul(F.square(v), v)
    v7 = F.mul(F.square(v3), v)
    x = F.mul(F.mul(u, v3), F.pow_p58(F.mul(u, v7)))

    vxx = F.mul(v, F.square(x))
    root_ok = F.eq(vxx, u)
    root_neg = F.eq(vxx, F.neg(u))
    x = F.select(root_neg, F.mul(x, F.const(F.SQRT_M1_INT, lanes)), x)
    ok = root_ok | root_neg

    x_can = F.canonical(x)
    x_is_zero = jnp.all(x_can == 0, axis=0)
    ok = ok & ~(x_is_zero & (sign == 1))
    # flip sign to match the encoded parity bit
    flip = (x_can[0] & 1) != sign
    x = F.select(flip, F.neg(x), x)

    return Point(x, y_limbs, one, F.mul(x, y_limbs)), ok


# --------------------------------------------------------------------------
# Windowed double-scalar-mul: 4-bit digits, msb-first over 64 windows.
# Per window: 4 doublings, one complete addition from the per-item [0..8]P
# table (signed digits), one Niels mixed addition from the constant [0..8]B
# table (saves
# 2 muls per addition).  ~3200 field muls/signature.


def _py_edwards_add(p, q):
    """Affine Edwards addition on python ints (host, table precompute only)."""
    P, D = F.P_INT, F.D_INT
    x1, y1 = p
    x2, y2 = q
    k = D * x1 * x2 * y1 * y2 % P
    x3 = (x1 * y2 + x2 * y1) * pow(1 + k, P - 2, P) % P
    y3 = (y1 * y2 + x1 * x2) * pow(1 - k, P - 2, P) % P
    return (x3, y3)


def _basepoint_niels_table() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """[d]B for d in 0..8 in Niels form, as three (9, NLIMBS) int32 arrays.

    9 entries, not 16: the ladder uses SIGNED 4-bit windows (digits in
    [-8, 8]) — negative digits reuse entry |d| with the cheap Niels
    negation (swap y+x / y-x, negate xy2d).
    """
    b = (F.BX_INT, F.BY_INT)
    pts = [(0, 1)]  # identity
    for _ in range(8):
        pts.append(_py_edwards_add(pts[-1], b))
    ypx = np.stack([F.int_to_limbs((y + x) % F.P_INT) for x, y in pts])
    ymx = np.stack([F.int_to_limbs((y - x) % F.P_INT) for x, y in pts])
    xy2d = np.stack(
        [F.int_to_limbs(2 * F.D_INT * x * y % F.P_INT) for x, y in pts]
    )
    return ypx, ymx, xy2d


_B_TAB_YPX, _B_TAB_YMX, _B_TAB_XY2D = _basepoint_niels_table()


def madd_niels(
    p: Point, ypx: jnp.ndarray, ymx: jnp.ndarray, xy2d: jnp.ndarray
) -> Point:
    """Mixed addition with a precomputed Niels-form point (madd-2008-hwcd-3).

    7 field muls; complete for the same reason as :func:`add` (a = -1,
    d non-square), and the identity entry (1, 1, 0) is handled uniformly.
    """
    a = F.mul(F.sub(p.y, p.x), ymx)
    b = F.mul(F.add(p.y, p.x), ypx)
    c = F.mul(xy2d, p.t)
    d = F.add(p.z, p.z)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return Point(F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def digits4_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(256, lanes) little-endian bits -> (64, lanes) base-16 digits."""
    lanes = bits.shape[1:]
    w = jnp.asarray([1, 2, 4, 8], dtype=jnp.int32).reshape(1, 4, *([1] * len(lanes)))
    return (bits.reshape(64, 4, *lanes) * w).sum(axis=1).astype(jnp.int32)


def recode_signed4(dig: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Base-16 digits [0,15] -> signed digits: (magnitude [0,8], neg flag).

    Exact carry recode: d + c_in = d' + 16*c_out with d' in [-7, 8]
    (t = d + c_in in [0, 16]; t > 8 maps to t - 16 in [-7, 0] with carry).
    The final window of an Ed25519 scalar < 2^253 is <= 1, so the last
    carry never overflows.  Signed windows HALVE the per-item table — the VMEM
    limiter that capped throughput at batch 4096 — and cut the masked
    lookup from 16 to 9 terms.
    """
    mags, negs = [], []
    c = jnp.zeros(dig.shape[1:], dtype=jnp.int32)
    for k in range(dig.shape[0]):
        t = dig[k] + c
        carry = (t > 8).astype(jnp.int32)
        d = t - 16 * carry  # in [-8, 8]
        c = carry
        neg = d < 0
        mags.append(jnp.where(neg, -d, d))
        negs.append(neg)
    return jnp.stack(mags, axis=0), jnp.stack(negs, axis=0)


def select_entry(table, idx: jnp.ndarray, n_entries: int):
    """Branchless per-lane table lookup: sum of masked entries.

    ``table``: sequence of arrays with entry axis 0, each
    ``(n_entries, 17, lanes)`` or the lane-constant ``(n_entries, 17, 1)``;
    ``idx``: (lanes,) int32.  Data-dependent per-lane gathers don't
    vectorize on the VPU; n_entries masked adds do.
    """
    out = []
    for coord in table:
        acc = jnp.zeros_like(coord[0] + jnp.zeros_like(idx))
        for e in range(n_entries):
            acc = acc + jnp.where((idx == e)[None], coord[e], 0)
        out.append(acc)
    return tuple(out)


N_TABLE = 9  # [0..8]P — signed 4-bit windows need magnitudes 0..8 only


def _small_multiples_table(p: Point):
    """[0..8]P stacked on axis 0 — built by 8 chained additions inside ONE
    fori_loop body (vs unrolled point ops: much smaller traced graph).
    """
    lanes = p.x.shape[1:]
    ident = identity(lanes)
    table = tuple(
        jnp.zeros((N_TABLE, F.NLIMBS, *lanes), jnp.int32).at[0].set(c) for c in ident
    )

    def chain(k, carry):
        table, prev = carry
        cur = add(Point(*prev), p)
        table = tuple(
            lax.dynamic_update_index_in_dim(t, c, k, axis=0)
            for t, c in zip(table, cur)
        )
        return (table, tuple(cur))

    table, _ = lax.fori_loop(1, N_TABLE, chain, (table, tuple(ident)))
    return table


def double_scalar_mul_windowed(
    s_dig: jnp.ndarray, p_dig: jnp.ndarray, p_point: Point
) -> Point:
    """[s]B + [p]P with 4-bit windows, msb-first over 64 windows.

    ``s_dig``/``p_dig``: (64, lanes) base-16 digits (little-endian windows)
    — recoded internally to signed digits (:func:`recode_signed4`).
    """
    lanes = s_dig.shape[1:]
    s_mag, s_neg = recode_signed4(s_dig)
    p_mag, p_neg = recode_signed4(p_dig)
    a_tab = _small_multiples_table(p_point)
    b_tab = tuple(
        jnp.asarray(t)[..., None] if lanes else jnp.asarray(t)
        for t in (_B_TAB_YPX, _B_TAB_YMX, _B_TAB_XY2D)
    )

    def digit_at(dig, w):
        return lax.dynamic_index_in_dim(dig, w, axis=0, keepdims=False)

    def body(i, q):
        w = 63 - i
        q = double(double(double(double(Point(*q)))))
        ex, ey, ez, et = select_entry(a_tab, digit_at(p_mag, w), N_TABLE)
        pn = digit_at(p_neg.astype(jnp.int32), w).astype(bool)
        # negative digit: -(x, y, z, t) = (-x, y, z, -t), branchless
        entry = Point(
            F.select(pn, F.neg(ex), ex), ey, ez, F.select(pn, F.neg(et), et)
        )
        q = add(q, entry)
        nypx, nymx, nxy2d = select_entry(b_tab, digit_at(s_mag, w), N_TABLE)
        sn = digit_at(s_neg.astype(jnp.int32), w).astype(bool)
        # Niels negation: swap (y+x)/(y-x), negate xy2d
        nypx, nymx = (
            F.select(sn, nymx, nypx),
            F.select(sn, nypx, nymx),
        )
        nxy2d = F.select(sn, F.neg(nxy2d), nxy2d)
        return tuple(madd_niels(q, nypx, nymx, nxy2d))

    q = lax.fori_loop(0, 64, body, tuple(identity(lanes)), unroll=LADDER_UNROLL)
    return Point(*q)


def verify_core(
    y_a: jnp.ndarray,
    sign_a: jnp.ndarray,
    y_r: jnp.ndarray,
    sign_r: jnp.ndarray,
    s_dig: jnp.ndarray,
    h_dig: jnp.ndarray,
) -> jnp.ndarray:
    """Limbs-leading batched verify -> validity bitmap (lanes,) bool.

    Inputs: ``y_a``/``y_r`` (17, lanes) limb tensors; ``sign_*`` (lanes,);
    ``s_dig``/``h_dig`` (64, lanes) base-16 scalar digits.

    Checks the cofactorless equation [S]B == R + [h]A (as OpenSSL/the CPU
    path does), rearranged to Q := [S]B + [h](-A), Q == R, compared
    projectively (X_Q == x_R * Z_Q, Y_Q == y_R * Z_Q) to avoid an inversion.
    """
    # (Tried and rejected: fusing the A/R decompressions into one (17, 2B)
    # call to halve the pow_p58 sequential depth; the doubled lane width
    # during decompress cancels the depth win at the production bucket size.)
    with jax.named_scope(SCOPE_DECOMPRESS):
        a_point, ok_a = decompress(y_a, sign_a)
        r_point, ok_r = decompress(y_r, sign_r)
    with jax.named_scope(SCOPE_LADDER):
        q = double_scalar_mul_windowed(s_dig, h_dig, negate(a_point))
    with jax.named_scope(SCOPE_COMPARE):
        eq_x = F.eq(q.x, F.mul(r_point.x, q.z))
        eq_y = F.eq(q.y, F.mul(r_point.y, q.z))
        return ok_a & ok_r & eq_x & eq_y


def verify_prepared_packed(
    y_a: jnp.ndarray,
    sign_a: jnp.ndarray,
    y_r: jnp.ndarray,
    sign_r: jnp.ndarray,
    s_bytes: jnp.ndarray,
    h_bytes: jnp.ndarray,
) -> jnp.ndarray:
    """Like :func:`verify_prepared` but scalars arrive as (B, 32) uint8
    little-endian BYTES and are bit-unpacked on device — 32x less
    host->device transfer per scalar (the (B, 256) int32 bit tensors are
    ~8 MB per 8192-chunk each; the byte forms are 256 KB)."""
    with jax.named_scope(SCOPE_UNPACK):
        s_bits, h_bits = unpack_bits(s_bytes), unpack_bits(h_bytes)
    return verify_prepared(y_a, sign_a, y_r, sign_r, s_bits, h_bits)


def unpack_bits(b: jnp.ndarray) -> jnp.ndarray:
    """(B, 32) uint8 packed scalar bytes -> (B, 256) int32 LE bits — the
    on-device half of the packed-transfer format (shared with the comb
    path, :mod:`mochi_tpu.crypto.comb`)."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (b[:, :, None] >> shifts[None, None, :]) & 1
    return bits.reshape(b.shape[0], 256).astype(jnp.int32)


def verify_prepared(
    y_a: jnp.ndarray,
    sign_a: jnp.ndarray,
    y_r: jnp.ndarray,
    sign_r: jnp.ndarray,
    s_bits: jnp.ndarray,
    h_bits: jnp.ndarray,
) -> jnp.ndarray:
    """Batched verify on host-prepared batch-leading tensors -> (B,) bitmap.

    External API (unchanged from round 1 modulo limb count): ``y_a``/``y_r``
    (B, 17), ``sign_*`` (B,), ``s_bits``/``h_bits`` (B, 256) little-endian
    bits.  SHA-512, mod-L reduction, and canonical-encoding prechecks
    (y < p, S < L) happen on the host
    (:mod:`mochi_tpu.crypto.batch_verify`).  Internally transposes to the
    limbs-leading layout (one fused transpose each way in XLA).
    """
    with jax.named_scope(SCOPE_UNPACK):
        s_dig = digits4_from_bits(s_bits.T)
        h_dig = digits4_from_bits(h_bits.T)
    return verify_core(y_a.T, sign_a, y_r.T, sign_r, s_dig, h_dig)
