"""Differential tests: JAX Ed25519 verifier vs the OpenSSL CPU path.

The TPU verifier must agree bit-for-bit with the CPU fallback on valid,
forged, and malformed inputs (SURVEY.md §7: "correctness-tested against the
CPU path"; §4 "validity bitmap on mixed valid/forged batches").  Field
arithmetic is additionally checked against python bignums.

Layout note (round 2): field elements are limbs-leading ``(17, B)`` —
batch on the trailing lane axis (see ``field.py`` module docstring).
"""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from mochi_tpu.crypto import batch_verify as BV
from mochi_tpu.crypto import field as F
from mochi_tpu.crypto.keys import generate_keypair, verify as cpu_verify
from mochi_tpu.verifier.spi import VerifyItem

RANGE = 1 << 255  # the limb representation covers [0, 2^255)


def _pack(ints):
    """Python ints -> limbs-leading (17, B) device array."""
    return jnp.asarray(np.stack([F.int_to_limbs(x) for x in ints], axis=-1))


class TestField:
    def _rand_pairs(self, n=8, seed=1):
        rng = random.Random(seed)
        xs = [rng.randrange(0, RANGE) for _ in range(n)]
        ys = [rng.randrange(0, RANGE) for _ in range(n)]
        return xs, ys, _pack(xs), _pack(ys)

    def _assert_mod_eq(self, got, expect_ints):
        got_ints = F.limbs_to_int_batch(np.asarray(got))
        arr = np.asarray(got)
        assert arr.min() >= 0 and arr.max() <= F.LOOSE  # loose-carry invariant
        assert [g % F.P_INT for g in got_ints] == [e % F.P_INT for e in expect_ints]

    def test_add_sub_mul(self):
        xs, ys, A, B = self._rand_pairs()
        self._assert_mod_eq(F.add(A, B), [x + y for x, y in zip(xs, ys)])
        self._assert_mod_eq(F.sub(A, B), [x - y for x, y in zip(xs, ys)])
        self._assert_mod_eq(F.mul(A, B), [x * y for x, y in zip(xs, ys)])
        self._assert_mod_eq(F.square(A), [x * x for x in xs])
        self._assert_mod_eq(F.neg(A), [-x for x in xs])
        self._assert_mod_eq(F.mul_small(A, 2), [2 * x for x in xs])
        self._assert_mod_eq(F.mul_small(A, 977), [977 * x for x in xs])

    @pytest.mark.slow
    def test_pow_invert_canonical(self):
        xs, _, A, _ = self._rand_pairs(n=4, seed=2)
        p = F.P_INT
        self._assert_mod_eq(F.invert(A), [pow(x % p, p - 2, p) for x in xs])
        self._assert_mod_eq(F.pow_p58(A), [pow(x % p, (p - 5) // 8, p) for x in xs])
        can = F.limbs_to_int_batch(np.asarray(F.canonical(A)))
        assert can == [x % p for x in xs]

    def test_loose_chains_stay_bounded(self):
        """Long op chains must preserve the loose-limb invariant."""
        xs, ys, A, B = self._rand_pairs(seed=5)
        acc, acc_int = A, list(xs)
        for i in range(20):
            acc = F.mul(F.add(acc, B), F.sub(acc, A))
            acc_int = [
                ((a + y) * (a - x)) % F.P_INT
                for a, x, y in zip(acc_int, xs, ys)
            ]
            arr = np.asarray(acc)
            assert arr.min() >= 0 and arr.max() <= F.LOOSE
        self._assert_mod_eq(acc, acc_int)

    def test_edge_values(self):
        # 0, 1, p-1, p, p+17 (alias of 17), 2^255-1 (max representable)
        vals = [0, 1, F.P_INT - 1, F.P_INT, F.P_INT + 17, RANGE - 1]
        A = _pack(vals)
        can = F.limbs_to_int_batch(np.asarray(F.canonical(A)))
        assert can == [v % F.P_INT for v in vals]
        self._assert_mod_eq(F.mul(A, A), [v * v for v in vals])

    def test_int_to_limbs_rejects_oversize(self):
        with pytest.raises(AssertionError):
            F.int_to_limbs(1 << 255)


class TestBatchVerify:
    """One compiled bucket (16) exercising the full valid/forged matrix."""

    def _mixed_batch(self):
        kps = [generate_keypair() for _ in range(6)]
        items, expect = [], []
        for i, kp in enumerate(kps):
            m = f"txn-{i}".encode() * (i + 1)  # varying message lengths
            items.append(VerifyItem(kp.public_key, m, kp.sign(m)))
            expect.append(True)
        # forged: signature over a different message
        items.append(VerifyItem(kps[0].public_key, b"evil", kps[0].sign(b"good")))
        expect.append(False)
        # bit-flipped R
        s = bytearray(kps[1].sign(b"x"))
        s[3] ^= 1
        items.append(VerifyItem(kps[1].public_key, b"x", bytes(s)))
        expect.append(False)
        # bit-flipped S
        s = bytearray(kps[2].sign(b"x2"))
        s[40] ^= 1
        items.append(VerifyItem(kps[2].public_key, b"x2", bytes(s)))
        expect.append(False)
        # signed by a different key
        items.append(VerifyItem(kps[3].public_key, b"y", kps[4].sign(b"y")))
        expect.append(False)
        # non-canonical pubkey encoding (y >= p)
        items.append(VerifyItem(b"\xff" * 32, b"z", kps[0].sign(b"z")))
        expect.append(False)
        # scalar out of range (S >= L)
        sig = bytearray(kps[5].sign(b"w"))
        sig[32:] = b"\xff" * 31 + b"\x0f"
        items.append(VerifyItem(kps[5].public_key, b"w", bytes(sig)))
        expect.append(False)
        # truncated key / signature
        items.append(VerifyItem(b"\x01" * 31, b"t", kps[0].sign(b"t")))
        expect.append(False)
        items.append(VerifyItem(kps[0].public_key, b"t", b"\x02" * 63))
        expect.append(False)
        # empty message
        items.append(VerifyItem(kps[0].public_key, b"", kps[0].sign(b"")))
        expect.append(True)
        return items, expect

    def test_matches_cpu_path(self):
        items, expect = self._mixed_batch()
        got = BV.verify_batch(items)
        cpu = [
            cpu_verify(it.public_key, bytes(it.message), bytes(it.signature))
            for it in items
        ]
        assert got == expect
        assert got == cpu

    def test_empty_batch(self):
        assert BV.verify_batch([]) == []

    def test_chunked_stream_with_all_garbage_chunk(self, monkeypatch):
        """Chunked verify_batch: an all-rejected chunk inside the bounded
        launch window must skip its device launch (None in the pipeline)
        while neighboring chunks keep their verdicts — the fast path and
        the prepare-thread pipeline compose."""
        monkeypatch.setattr(BV, "MAX_BUCKET", 16)
        kp = generate_keypair()
        good = [
            VerifyItem(kp.public_key, b"c%d" % i, kp.sign(b"c%d" % i))
            for i in range(16)
        ]
        garbage = [
            VerifyItem(it.public_key, it.message, it.signature[:32] + b"\xff" * 32)
            for it in good
        ]
        stream = good + garbage + good  # 3 chunks at MAX_BUCKET=16
        before = BV.device_dispatch_count()
        out = BV.verify_batch(stream)
        assert out == [True] * 16 + [False] * 16 + [True] * 16
        assert BV.device_dispatch_count() == before + 2  # garbage chunk skipped

    def test_all_rejected_batch_skips_device(self, monkeypatch):
        """A chunk whose prechecks reject every item (garbage flood) must
        return all-False WITHOUT launching the device program: a flood of
        byte noise buys no device time."""
        kp = generate_keypair()
        # S >= L: canonical-length but fails the host range precheck
        garbage = [
            VerifyItem(kp.public_key, b"g%d" % i, kp.sign(b"g%d" % i)[:32] + b"\xff" * 32)
            for i in range(8)
        ]
        calls = []
        orig = BV._verify_packed_jit
        monkeypatch.setattr(
            BV, "_verify_packed_jit",
            lambda *a, **k: calls.append(1) or orig(*a, **k),
        )
        assert BV.verify_batch(garbage) == [False] * 8
        assert not calls, "device program ran on an all-rejected batch"
        # Mixed batch still goes to the device and keeps per-item verdicts
        ok_msg = b"ok"
        mixed = garbage + [VerifyItem(kp.public_key, ok_msg, kp.sign(ok_msg))]
        assert BV.verify_batch(mixed) == [False] * 8 + [True]
        assert calls
        # The skip must NOT mark the bucket compiled in the backend: the
        # next legitimate batch would then park behind a synchronous
        # 20-60 s compile (review finding, round 4).
        backend = BV.JaxBatchBackend(min_device_items=0)
        assert backend(garbage) == [False] * 8
        assert BV._bucket_size(8) not in backend._ready
        assert list(backend(mixed)) == [False] * 8 + [True]
        assert BV._bucket_size(9) in backend._ready

    def test_backend_plugs_into_spi(self):
        backend = BV.JaxBatchBackend(min_device_items=0)  # pin the device path: this test checks bucket behavior
        kp = generate_keypair()
        items = [VerifyItem(kp.public_key, b"m", kp.sign(b"m"))]
        assert list(backend(items)) == [True]

    def test_background_compile_failure_lands_in_failed(self):
        """ADVICE r1: a crash inside the background bucket compile must mark
        the bucket failed (not die with NameError and respawn threads)."""
        import threading

        backend = BV.JaxBatchBackend(min_device_items=0)  # pin the device path: this test checks bucket behavior
        backend._ready.add(16)  # pretend a small bucket is compiled
        done = threading.Event()
        orig = BV.verify_batch

        def boom(items, device=None, bucket=None):
            if bucket is None and len(items) > 16:
                raise RuntimeError("simulated compile failure")
            return orig(items, device=device, bucket=bucket)

        BV.verify_batch = boom
        try:
            kp = generate_keypair()
            items = [VerifyItem(kp.public_key, b"m", kp.sign(b"m"))] * 24
            out = backend(items)  # served chunked via bucket 16
            assert list(out) == [True] * 24
            for _ in range(100):
                with backend._lock:
                    if 32 in backend._failed and 32 not in backend._compiling:
                        done.set()
                        break
                import time

                time.sleep(0.05)
            assert done.is_set(), "failed bucket never recorded"
        finally:
            BV.verify_batch = orig
