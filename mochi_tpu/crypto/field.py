"""GF(2^255-19) arithmetic in 17x15-bit limbs, pure int32 — TPU-native.

Design notes (why this representation — round-2 rework):

* **Radix 2^15, 17 limbs, limbs-leading layout.**  A field element is an
  int32 array ``(17, ...lanes)`` — limbs on the *leading* axis, batch on the
  trailing axes.  On TPU the last dim maps to the 128-wide lane axis, so a
  batch of field elements ``(17, B)`` runs every elementwise op on full
  128-lane vectors (a batch-leading ``(B, 16)`` layout wastes 7/8 of each
  lane group).
* **Why radix 15, not 16:** 17*15 = 255 exactly, so the fold constant is 19
  (2^255 === 19 mod p) and — the big one — products of *loosely reduced*
  limbs stay inside int32: with limbs <= 2^15+96, a product is < 2^31, so
  multiplication needs no uint32 casts and, crucially, limbs never need to
  be carried all the way down to < 2^15 between operations.  Radix 16 sits
  exactly at the uint32 boundary and forces a full sequential carry chain
  (16 data-dependent steps, x3 per multiply) after every op.
* **Loose-carry discipline.**  Invariant: every field element has limbs in
  ``[0, LOOSE]`` with ``LOOSE = 2^15 + 96``.  After an op, one or two
  *vectorized* carry passes (shift-add over all limbs at once, no sequential
  chain) restore the invariant.  Bounds, proven per-op in the docstrings:
  products <= LOOSE^2 < 2^31; schoolbook columns < 2^21; folded columns
  < 2^26; ``_carry2`` output <= 32786 <= LOOSE.  Exact canonical reduction
  (sequential chain + conditional subtract) happens only in :func:`canonical`
  — i.e. a handful of times per verify, not thousands.
* **Column accumulation is 17 shifted pad+adds.**  The 17x17 partial-
  product anti-diagonal sums ("columns") are built by padding each row to
  its shifted position and summing (:func:`_skew_cols`) — ~35 fusable
  elementwise ops, no relayout.  A flatten/reshape skew is fewer XLA ops,
  but the reshape is a relayout and a fusion barrier on TPU: its
  (17, 34, B) intermediates stream through HBM where the pad form stays
  fused in VMEM.
* No data-dependent control flow — everything is branchless select/arith
  so the whole verifier jits into one XLA program (SURVEY.md §7).

The reference implements no field arithmetic anywhere (it never signs:
``MochiProtocol.proto:123`` TODO, SURVEY.md preamble); this module is part of
the north-star TPU verifier that completes the reference's declared design.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

NLIMBS = 17
RADIX = 15
MASK = (1 << RADIX) - 1
LOOSE = (1 << RADIX) + 96  # loose-limb bound, see module docstring

# p = 2^255 - 19
P_INT = (1 << 255) - 19
# curve constant d = -121665/121666 mod p
D_INT = 37095705934669439343138083508754565189542113879843219016388785533085940283555
# sqrt(-1) mod p (2^((p-1)/4))
SQRT_M1_INT = 19681161376707505956807079304988542015446066515923890162744021073123829784752
# group order L = 2^252 + 27742317777372353535851937790883648493
L_INT = (1 << 252) + 27742317777372353535851937790883648493

# Ed25519 basepoint (affine)
BX_INT = 15112221349535400772501151409588531511454012693041857206046113283949847762202
BY_INT = 46316835694926478169428394003475163141307993866256225615783033603165251855960


def int_to_limbs(x: int) -> np.ndarray:
    """Host-side: python int -> 17 int32 limbs (little-endian, radix 2^15).

    The representation covers [0, 2^255) — every protocol input (y
    coordinates with bit 255 masked off, scalars < L, field constants) fits;
    larger values would silently truncate, so they are rejected.
    """
    assert 0 <= x < (1 << 255), "value out of 255-bit limb range"
    return np.array([(x >> (RADIX * i)) & MASK for i in range(NLIMBS)], dtype=np.int32)


def limbs_to_int(limbs) -> int:
    """Host-side: limb array (17,) or (17, 1) -> python int (no reduction)."""
    arr = np.asarray(limbs).reshape(NLIMBS)
    return sum(int(arr[i]) << (RADIX * i) for i in range(NLIMBS))


def limbs_to_int_batch(limbs) -> list:
    """Host-side: (17, B) limb array -> list of B python ints."""
    arr = np.asarray(limbs).reshape(NLIMBS, -1)
    out = []
    for j in range(arr.shape[1]):
        out.append(sum(int(arr[i, j]) << (RADIX * i) for i in range(NLIMBS)))
    return out


def bytes32_to_limbs(b: bytes) -> np.ndarray:
    """32 little-endian bytes -> limbs (full 256 bits would not fit 255;
    callers mask bit 255 first — this helper asserts the value fits)."""
    assert len(b) == 32
    x = int.from_bytes(b, "little")
    assert x < (1 << 255)
    return int_to_limbs(x)


def const(x: int, lanes=()) -> jnp.ndarray:
    """Device constant: (17, *lanes) int32, broadcast over trailing lanes."""
    c = jnp.asarray(int_to_limbs(x))
    if lanes:
        c = jnp.broadcast_to(c.reshape(NLIMBS, *([1] * len(lanes))), (NLIMBS, *lanes))
    return c


def _limb_vec(np_limbs: np.ndarray, lanes=()) -> jnp.ndarray:
    """A fixed limb vector (e.g. p or 2p) as (17, *lanes or broadcastable)."""
    return jnp.asarray(np_limbs).reshape(NLIMBS, *([1] * len(lanes)))


def zeros(lanes) -> jnp.ndarray:
    return jnp.zeros((NLIMBS, *lanes), dtype=jnp.int32)


def one(lanes) -> jnp.ndarray:
    return jnp.concatenate(
        [jnp.ones((1, *lanes), jnp.int32), jnp.zeros((NLIMBS - 1, *lanes), jnp.int32)],
        axis=0,
    )


# ------------------------------------------------------------------- carries


def _shift_in(c: jnp.ndarray, fold: int) -> jnp.ndarray:
    """Carries (17, ...) -> what each limb receives: limb k+1 gets c[k],
    limb 0 gets fold*c[16] (2^255 === fold mod p, fold = 19)."""
    return jnp.concatenate([fold * c[NLIMBS - 1 :], c[: NLIMBS - 1]], axis=0)


def _carry1(cols: jnp.ndarray) -> jnp.ndarray:
    """One vectorized carry pass.  Valid for 0 <= cols <= 2^17.

    r = cols & MASK < 2^15; c = cols >> 15 <= 4; out[k] = r[k] + c[k-1],
    out[0] = r[0] + 19*c[16] <= 32767 + 76 = 32843 <= LOOSE.
    """
    r = cols & MASK
    c = cols >> RADIX
    return r + _shift_in(c, 19)


def _carry2(cols: jnp.ndarray) -> jnp.ndarray:
    """Two vectorized carry passes.  Valid for 0 <= cols < 2^26.

    Pass 1: c <= 2^11, t[0] <= 32767 + 19*2^11 = 71679, t[k] <= 34815.
    Pass 2: c2[0] <= 2, c2[k] <= 1 -> out[k] <= 32769, out[0] <= 32786.
    Output <= 32786 <= LOOSE.
    """
    t = (cols & MASK) + _shift_in(cols >> RADIX, 19)
    return (t & MASK) + _shift_in(t >> RADIX, 19)


def _carry_chain(cols: jnp.ndarray):
    """Exact sequential carry (17 steps) -> (limbs < 2^15, signed carry-out).

    Only used inside :func:`canonical`; value = limbs + cout * 2^255.
    Arithmetic shift keeps negative columns correct (borrow propagation).
    """
    c = jnp.zeros(cols.shape[1:], dtype=jnp.int32)
    out = []
    for k in range(NLIMBS):
        t = cols[k] + c
        out.append(t & MASK)
        c = t >> RADIX
    return jnp.stack(out, axis=0), c


# ------------------------------------------------------------------- add/sub


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a + b mod p.  Columns <= 2*LOOSE = 65728 <= 2^17 -> one carry pass."""
    return _carry1(a + b)


# 2p as a NON-normalized limb vector: each canonical p-limb doubled, so every
# limb (65498, 65534 x16) dominates any loose limb (<= LOOSE) — the standard
# "add 2p before subtracting" trick without leaving the limb domain.
_P_LIMBS_NP = np.array(
    [(P_INT >> (RADIX * i)) & MASK for i in range(NLIMBS)], dtype=np.int32
)
_TWO_P_LIMBS = (2 * _P_LIMBS_NP).astype(np.int32)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b mod p.  t = a + 2p - b: limbwise 32634 <= t <= 98398 <= 2^17,
    nonnegative because every 2p limb (>= 65498) exceeds any loose limb."""
    two_p = _limb_vec(_TWO_P_LIMBS, a.shape[1:])
    return _carry1(a + (two_p - b))


def neg(a: jnp.ndarray) -> jnp.ndarray:
    return sub(jnp.zeros_like(a), a)


# ------------------------------------------------------------------- multiply


def _skew_cols(x: jnp.ndarray) -> jnp.ndarray:
    """Anti-diagonal sums of x (17, 17, *lanes), axis 0 = a-limb i, axis 1 =
    b-limb j: returns cols (33, *lanes) with cols[k] = sum_{i+j=k} x[i, j],
    via 17 shifted pad+adds: cols += pad(x[i], (i, 16-i)).

    Each term is an elementwise add of a sublane-shifted (17->33, lanes)
    slice — no flatten/reshape relayout, so XLA can fuse the whole column
    accumulation into the partial-product computation instead of
    materializing the (17, 34, lanes) skew intermediates in HBM.
    """
    n = NLIMBS
    lanes = x.shape[2:]
    lane_pad = [(0, 0)] * len(lanes)
    cols = jnp.pad(x[0], [(0, n - 1), *lane_pad])
    for i in range(1, n):
        cols = cols + jnp.pad(x[i], [(i, n - 1 - i), *lane_pad])
    return cols


def _fold_carry(cols_lo: jnp.ndarray, cols_hi: jnp.ndarray) -> jnp.ndarray:
    """Combine lo/hi column sums (hi shifted one limb up), fold the high 17
    columns at 2^255 === 19, and restore the loose-limb invariant.
    Precondition (both callers prove it): columns < 2^21, so the folded
    columns are < 20 * 2^21 < 2^26 -> :func:`_carry2`."""
    pad_lane = [(0, 0)] * (cols_lo.ndim - 1)
    cols = jnp.pad(cols_lo, [(0, 1), *pad_lane]) + jnp.pad(
        cols_hi, [(1, 0), *pad_lane]
    )  # (34, lanes)
    folded = cols[:NLIMBS] + 19 * cols[NLIMBS:]
    return _carry2(folded)


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook 17x17-limb multiply, radix 2^15, fold at 2^255 === 19.

    Bounds: loose limbs <= LOOSE -> products <= LOOSE^2 = 1.080e9 < 2^31
    (int32-safe, no uint32 casts).  lo < 2^15, hi = prod >> 15 <= 32965.
    Columns: <= 17 terms each for lo and hi -> < 2^21 -> :func:`_fold_carry`.
    """
    prod = a[:, None] * b[None, :]  # (17, 17, lanes) int32
    lo = prod & MASK
    hi = prod >> RADIX
    cols_lo = _skew_cols(lo)  # (33, lanes), cols of sum lo[i,j] at i+j
    cols_hi = _skew_cols(hi)  # hi contributes at i+j+1
    return _fold_carry(cols_lo, cols_hi)


def square(a: jnp.ndarray) -> jnp.ndarray:
    """a^2 via the symmetric schoolbook: only the upper triangle of the
    partial products is computed (153 int32 multiplies vs mul's 289),
    off-diagonal terms doubled.  Doubling happens AFTER the lo/hi split —
    doubling a product first would overflow int32 (2*LOOSE^2 > 2^31).

    Column values are exactly those of ``mul(a, a)`` (the doubled upper
    triangle equals the full ordered sum), so the mul bounds apply
    unchanged: columns < 2^21, folded < 2^26 -> :func:`_carry2`.
    Roughly 36% of the verifier's field muls are squarings (the ladder's
    doublings and the decompression power chains), so the ~47% product
    saving here is a slice of the whole pipeline.
    """
    n = NLIMBS
    lanes = a.shape[1:]
    lane_pad = [(0, 0)] * len(lanes)
    cols_lo = None
    cols_hi = None
    for i in range(n):
        prod = a[i] * a[i:]  # (n-i, lanes), <= LOOSE^2 < 2^31
        lo = prod & MASK
        hi = prod >> RADIX
        # double off-diagonal (j > i) terms; diagonal stays single
        lo = jnp.concatenate([lo[:1], lo[1:] * 2], axis=0)
        hi = jnp.concatenate([hi[:1], hi[1:] * 2], axis=0)
        # row i covers columns k = i+j for j in [i, n): left pad 2i,
        # right pad (2n-2) - (i+n-1) = n-1-i
        lo = jnp.pad(lo, [(2 * i, n - 1 - i), *lane_pad])
        hi = jnp.pad(hi, [(2 * i, n - 1 - i), *lane_pad])
        cols_lo = lo if cols_lo is None else cols_lo + lo
        cols_hi = hi if cols_hi is None else cols_hi + hi
    return _fold_carry(cols_lo, cols_hi)


def mul_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small python constant.

    k <= 3: columns <= 3*LOOSE < 2^17 -> one pass.  k < 2^10: columns
    < 2^26 -> two passes.  Larger constants route through the full multiply.
    """
    if 0 <= k <= 3:
        return _carry1(a * k)
    if 0 <= k < (1 << 10):
        return _carry2(a * k)
    return mul(a, const(k % P_INT, a.shape[1:]))


# ------------------------------------------------------------------- exact


def canonical(a: jnp.ndarray) -> jnp.ndarray:
    """Reduce a loose element to the unique representative < p.

    Loose value <= LOOSE * (2^255-1)/(2^15-1) < 1.003 * 2^255 < 2p.
    Exact chain -> limbs < 2^15 + cout in {0,1}; fold 19*cout -> value
    < 2^255 < p + 20; one conditional subtract of p settles it.
    """
    limbs, cout = _carry_chain(a)
    # limb-0 += 19*cout via concat (scatter-free: see one())
    limbs = limbs + jnp.concatenate(
        [(19 * cout)[None], jnp.zeros((NLIMBS - 1, *cout.shape), jnp.int32)], axis=0
    )
    limbs, _ = _carry_chain(limbs)

    p_vec = _limb_vec(_P_LIMBS_NP, a.shape[1:])
    diff, borrow = _carry_chain(limbs - p_vec)
    return jnp.where((borrow >= 0), diff, limbs)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field equality (canonicalizes both sides). Returns bool (lanes,)."""
    return jnp.all(canonical(a) == canonical(b), axis=0)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(canonical(a) == 0, axis=0)


def select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Branchless limb select: cond (lanes,) bool -> a or b (17, lanes)."""
    return jnp.where(cond[None], a, b)


# --------------------------------------------------------------- powering


def _square_n(a: jnp.ndarray, n: int) -> jnp.ndarray:
    """a^(2^n): n squarings as one fori_loop (graph stays one mul body)."""
    return lax.fori_loop(0, n, lambda i, x: square(x), a, unroll=False)


def pow_p58(a: jnp.ndarray) -> jnp.ndarray:
    """a^((p-5)/8) = a^(2^252 - 3), ref10's pow22523 addition chain:
    254 squarings (fori_loops) + 12 multiplies — vs 255 squarings *and*
    255 muls for naive bit-scan square-and-multiply."""
    z2 = square(a)  # 2
    z8 = _square_n(z2, 2)  # 8
    z9 = mul(a, z8)  # 9
    z11 = mul(z2, z9)  # 11
    z22 = square(z11)  # 22
    z_5_0 = mul(z9, z22)  # 2^5 - 1
    z_10_0 = mul(_square_n(z_5_0, 5), z_5_0)  # 2^10 - 1
    z_20_0 = mul(_square_n(z_10_0, 10), z_10_0)  # 2^20 - 1
    z_40_0 = mul(_square_n(z_20_0, 20), z_20_0)  # 2^40 - 1
    z_50_0 = mul(_square_n(z_40_0, 10), z_10_0)  # 2^50 - 1
    z_100_0 = mul(_square_n(z_50_0, 50), z_50_0)  # 2^100 - 1
    z_200_0 = mul(_square_n(z_100_0, 100), z_100_0)  # 2^200 - 1
    z_250_0 = mul(_square_n(z_200_0, 50), z_50_0)  # 2^250 - 1
    return mul(_square_n(z_250_0, 2), a)  # 2^252 - 3


def invert(a: jnp.ndarray) -> jnp.ndarray:
    """a^(p-2) (Fermat), via the pow22523 chain: p-2 = 2^255 - 21 and
    2^255 - 21 = 8*(2^252 - 3) + 3, so a^(p-2) = (a^(2^252-3))^8 * a^3."""
    t = pow_p58(a)  # a^(2^252 - 3)
    t = _square_n(t, 3)  # a^(2^255 - 24)
    return mul(t, mul(square(a), a))  # * a^3
