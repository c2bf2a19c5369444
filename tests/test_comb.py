"""Known-signer comb verification (crypto/comb.py): differential contract.

The comb path must produce bit-for-bit the same verdicts as OpenSSL and as
the general ladder path, for valid signatures, forgeries, wrong-key and
malformed inputs, and mixed registered/unregistered batches — the same
contract ``tests/test_crypto_jax.py`` enforces for the general path.
"""

from __future__ import annotations

import numpy as np
import pytest

from mochi_tpu.crypto import batch_verify, comb, keys
from mochi_tpu.verifier.spi import VerifyItem


@pytest.fixture(scope="module")
def signers():
    return [keys.generate_keypair() for _ in range(5)]


@pytest.fixture(scope="module")
def registry(signers):
    reg = comb.SignerRegistry()
    for kp in signers:
        assert reg.register(kp.public_key) is not None
    return reg


def _expected(items):
    return [keys.verify(it.public_key, it.message, it.signature) for it in items]


# ---------------------------------------------------------------- registry


def test_register_rejects_invalid_encodings(registry):
    # non-canonical y (>= p): the encoding of p itself
    p_enc = ((1 << 255) - 19).to_bytes(32, "little")
    assert comb.SignerRegistry().register(p_enc) is None
    # not a curve point: some small y has no valid x; the registry must
    # reject exactly those the RFC 8032 decode rejects
    non_point = next(
        y
        for y in range(2, 64)
        if comb.decompress_host(y.to_bytes(32, "little")) is None
    )
    assert comb.SignerRegistry().register(non_point.to_bytes(32, "little")) is None
    # wrong length
    assert comb.SignerRegistry().register(b"\x00" * 31) is None
    # x = 0 with sign bit set: y = 1 encoding with bit 255
    bad = bytearray((1).to_bytes(32, "little"))
    bad[31] |= 0x80
    assert comb.SignerRegistry().register(bytes(bad)) is None


def test_register_is_idempotent_and_indexes_stable(signers, registry):
    for i, kp in enumerate(signers):
        assert registry.index_of(kp.public_key) == i
        assert registry.register(kp.public_key) == i
    assert len(registry) == len(signers)


def test_decompress_host_matches_device_decode(signers):
    # registration's host decode accepts exactly the keys the device path
    # accepts (spot check: all generated pubkeys round-trip)
    for kp in signers:
        aff = comb.decompress_host(kp.public_key)
        assert aff is not None
        x, y = aff
        # parity bit must match bit 255 of the encoding
        assert (x & 1) == (kp.public_key[31] >> 7)


# ---------------------------------------------------------------- verdicts


def _mixed_items(signers, n=64):
    """Valid + forged + wrong-key + malformed items from registered keys."""
    items, kinds = [], []
    for i in range(n):
        kp = signers[i % len(signers)]
        msg = b"comb-msg-%d" % i
        sig = kp.sign(msg)
        kind = i % 8
        if kind == 3:  # flip a signature bit (R half)
            sig = sig[:5] + bytes([sig[5] ^ 0x40]) + sig[6:]
        elif kind == 5:  # flip an S bit
            sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        elif kind == 6:  # sign with a different registered key
            sig = signers[(i + 1) % len(signers)].sign(msg)
            msg = b"comb-msg-%d" % i  # verify against kp's pubkey
        elif kind == 7:  # non-canonical S (S + L)
            s_int = int.from_bytes(sig[32:], "little")
            from mochi_tpu.crypto import field as F

            s2 = s_int + F.L_INT
            if s2 < (1 << 256):
                sig = sig[:32] + s2.to_bytes(32, "little")
        items.append(VerifyItem(kp.public_key, msg, sig))
        kinds.append(kind)
    return items


@pytest.mark.slow
def test_comb_matches_openssl_and_ladder(signers, registry):
    items = _mixed_items(signers)
    expect = _expected(items)
    got_comb = batch_verify.verify_batch(items, registry=registry)
    got_ladder = batch_verify.verify_batch(items)
    assert got_comb == expect
    assert got_ladder == expect
    assert any(expect) and not all(expect)  # the mix is non-trivial


@pytest.mark.slow
def test_mixed_registered_and_unregistered(signers, registry):
    stranger = keys.generate_keypair()  # never registered
    items = []
    for i in range(24):
        kp = signers[i % 3] if i % 2 == 0 else stranger
        msg = b"mix-%d" % i
        sig = kp.sign(msg) if i % 5 else kp.sign(b"other")
        items.append(VerifyItem(kp.public_key, msg, sig))
    expect = _expected(items)
    got = batch_verify.verify_batch(items, registry=registry)
    assert got == expect


def test_comb_disabled_by_env(monkeypatch, signers, registry):
    monkeypatch.setenv("MOCHI_COMB", "0")
    kp = signers[0]
    items = [VerifyItem(kp.public_key, b"x", kp.sign(b"x"))]
    assert batch_verify.verify_batch(items, registry=registry) == [True]


def test_empty_registry_routes_general(signers):
    reg = comb.SignerRegistry()
    kp = signers[0]
    items = [VerifyItem(kp.public_key, b"y", kp.sign(b"y"))]
    assert batch_verify.verify_batch(items, registry=reg) == [True]


def test_malformed_lengths_rejected(signers, registry):
    kp = signers[0]
    items = [
        VerifyItem(kp.public_key, b"m", kp.sign(b"m")[:63]),  # short sig
        VerifyItem(kp.public_key[:31], b"m", kp.sign(b"m")),  # short key
        VerifyItem(kp.public_key, b"m", kp.sign(b"m")),
    ]
    got = batch_verify.verify_batch(items, registry=registry)
    assert got == [False, False, True]


def test_noncanonical_r_rejected(signers, registry):
    # R encoding >= p: host precheck rejects on both paths identically
    kp = signers[0]
    sig = bytearray(kp.sign(b"m"))
    sig[:32] = ((1 << 255) - 19).to_bytes(32, "little")
    items = [VerifyItem(kp.public_key, b"m", bytes(sig))]
    assert batch_verify.verify_batch(items, registry=registry) == [False]
    assert batch_verify.verify_batch(items) == [False]


@pytest.mark.slow
def test_registry_growth_across_capacity_boundary():
    # capacity pads to powers of two (min 8): crossing 8 -> 16 must
    # invalidate the cached device table and keep verdicts correct
    kps = [keys.generate_keypair() for _ in range(10)]
    reg = comb.SignerRegistry()
    for kp in kps[:8]:
        reg.register(kp.public_key)
    items = [VerifyItem(kps[0].public_key, b"a", kps[0].sign(b"a"))]
    assert batch_verify.verify_batch(items, registry=reg) == [True]
    for kp in kps[8:]:
        reg.register(kp.public_key)
    items = [
        VerifyItem(kp.public_key, b"b%d" % i, kp.sign(b"b%d" % i))
        for i, kp in enumerate(kps)
    ]
    assert batch_verify.verify_batch(items, registry=reg) == [True] * len(kps)


def test_backend_with_registry_warmup_and_call(signers, registry):
    backend = batch_verify.JaxBatchBackend(
        min_device_items=0, registry=registry
    )
    backend.warmup([16])
    items = _mixed_items(signers, n=20)
    assert list(backend(items)) == _expected(items)


def test_backend_gating_never_stalls_on_registry_growth(signers):
    """Registration growth must not park live traffic behind a comb
    recompile: already-registered signers KEEP comb service at the pinned
    older generation (their table rows are stable), the NEW signer rides
    the general ladder until the background re-warm lands, and verdicts
    stay correct throughout."""
    import time

    reg = comb.SignerRegistry()
    reg.register_all([kp.public_key for kp in signers[:2]])
    backend = batch_verify.JaxBatchBackend(min_device_items=0, registry=reg)
    backend.warmup([16])
    kp = signers[0]
    items = [VerifyItem(kp.public_key, b"g1", kp.sign(b"g1"))] * 4

    before = comb.comb_dispatch_count()
    assert list(backend(items)) == [True] * 4
    assert comb.comb_dispatch_count() > before  # comb path live
    pinned = backend._comb_pinned_gen(16)
    assert pinned == 2

    # grow the registry: old signers keep comb at the pinned generation
    grower = keys.generate_keypair()
    assert reg.register(grower.public_key) is not None
    before = comb.comb_dispatch_count()
    assert list(backend(items)) == [True] * 4
    assert comb.comb_dispatch_count() > before  # still comb, no stall

    # the NEW signer verifies correctly right away (general ladder)
    new_items = [VerifyItem(grower.public_key, b"g2", grower.sign(b"g2"))] * 4
    assert list(backend(new_items)) == [True] * 4

    # the growth kicked a background re-warm; the new signer joins comb
    deadline = time.time() + 120
    while time.time() < deadline:
        if backend._comb_pinned_gen(16) == 3:
            break
        time.sleep(0.5)
    assert backend._comb_pinned_gen(16) == 3, "comb never re-warmed"
    before = comb.comb_dispatch_count()
    assert list(backend(new_items)) == [True] * 4
    assert comb.comb_dispatch_count() > before


def test_comb_only_service_chunks_at_comb_buckets(signers):
    """A registered-signer-only service with no boot warmup never
    populates the general ready set; a new batch size must still be
    served via the already-compiled comb buckets (chunked), not a
    synchronous compile of the new shape."""
    reg = comb.SignerRegistry()
    reg.register_all([kp.public_key for kp in signers])
    backend = batch_verify.JaxBatchBackend(min_device_items=0, registry=reg)
    kp = signers[0]
    small = [VerifyItem(kp.public_key, b"c%d" % i, kp.sign(b"c%d" % i)) for i in range(8)]
    assert list(backend(small)) == [True] * 8  # first call: comb compiles (bucket 16)
    assert backend._comb_pinned_gen(16) is not None
    assert 16 not in backend._ready  # no general dispatch ever happened

    # larger batch, new natural bucket (32): served by chunking at the
    # compiled comb bucket 16
    big = [VerifyItem(kp.public_key, b"d%d" % i, kp.sign(b"d%d" % i)) for i in range(20)]
    before = comb.comb_dispatch_count()
    assert list(backend(big)) == [True] * 20
    assert comb.comb_dispatch_count() - before == 2  # two 16-sized chunks
    assert backend._comb_pinned_gen(32) is None  # not synchronously compiled


@pytest.mark.slow
def test_sharded_comb_matches_openssl_on_cpu_mesh(signers):
    """Sharded comb (shard_map over the 8-device CPU mesh, table
    replicated) produces the same bitmap as OpenSSL — the config-5 /
    multi-chip production posture."""
    from mochi_tpu.verifier.tpu import ShardedJaxBatchBackend

    backend = ShardedJaxBatchBackend(min_device_items=0)
    backend.register_signers([kp.public_key for kp in signers])
    assert backend.n_devices > 1  # conftest forces the 8-device CPU mesh
    items = _mixed_items(signers, n=40)
    expect = _expected(items)
    assert list(backend(items)) == expect
    # comb program actually dispatched (all signers registered)
    before = comb.comb_dispatch_count()
    assert list(backend(items)) == expect
    assert comb.comb_dispatch_count() > before

    # a mixed batch with an unregistered signer runs the general sharded
    # program whole (all-or-nothing routing) — verdicts still exact
    stranger = keys.generate_keypair()
    mixed = items[:6] + [VerifyItem(stranger.public_key, b"s", stranger.sign(b"s"))]
    before = comb.comb_dispatch_count()
    assert list(backend(mixed)) == _expected(mixed)
    assert comb.comb_dispatch_count() == before


def test_cluster_protocol_over_comb_verifier():
    """Full BFT protocol with every replica's verification routed through
    the comb-backed device backend (registry = the cluster's own replica
    identities + its clients): honest transactions commit, a forged
    MultiGrant from an attacker key is dropped at the verify seam, and the
    honest quorum still commits — the cluster-level contract of
    test_byzantine.py, now on the comb fast path."""
    import asyncio
    from dataclasses import replace

    from mochi_tpu.client import TransactionBuilder
    from mochi_tpu.protocol import (
        Write2AnsFromServer,
        Write2ToServer,
        WriteCertificate,
    )
    from mochi_tpu.testing import VirtualCluster
    from mochi_tpu.verifier.spi import BatchingVerifier

    registry = comb.SignerRegistry()
    backends = []

    def factory():
        b = batch_verify.JaxBatchBackend(min_device_items=0, registry=registry)
        backends.append(b)
        return BatchingVerifier(backend=b, max_delay_s=0.001)

    async def main():
        async with VirtualCluster(4, rf=4, verifier_factory=factory) as vc:
            registry.register_all(vc.config.public_keys.values())
            client = vc.client()
            registry.register(client.keypair.public_key)

            # honest write commits through the comb-routed verify seam
            await client.execute_write_transaction(
                TransactionBuilder().write("ck", "cv").build()
            )
            r = await client.execute_read_transaction(
                TransactionBuilder().read("ck").build()
            )
            assert r.operations[0].value == b"cv"

            # forged MultiGrant (attacker key, NOT registered): dropped at
            # the verify seam, honest quorum still commits
            from tests.test_byzantine import write1_via_wire

            txn = TransactionBuilder().write("ck2", b"honest").build()
            grants = await write1_via_wire(vc, client, txn)
            attacker = keys.generate_keypair()
            victim = sorted(grants)[0]
            forged = replace(grants[victim], signature=None)
            forged = forged.with_signature(attacker.sign(forged.signing_bytes()))
            wc = WriteCertificate({**grants, victim: forged})
            env = client._envelope(Write2ToServer(wc, txn), "w2-comb-forged")
            tid = sorted(vc.config.servers)[1]
            resp = await client.pool.send_and_receive(vc.config.servers[tid], env)
            # 3 honest grants remain = quorum for rf=4 -> commit succeeds on
            # the target replica, with the forged grant detected + dropped
            assert isinstance(resp.payload, Write2AnsFromServer)
            assert resp.payload.result.operations[0].value == b"honest"
            assert (
                vc.replica(tid).metrics.counters.get("replica.dropped-grants", 0)
                == 1
            )

    dispatches_before = comb.comb_dispatch_count()
    asyncio.run(asyncio.wait_for(main(), timeout=300))
    # the comb program really carried traffic in this cluster
    assert comb.comb_dispatch_count() > dispatches_before
    assert any(b._ready_comb for b in backends)


def test_comb_chunked_pipeline_path(monkeypatch, signers, registry):
    """Oversized comb batches chunk at MAX_BUCKET behind the bounded
    launch window (verify_stream's pipelined path) — shrunk via
    monkeypatch so the CPU test exercises the real chunk/prepare-thread
    machinery without 8192-lane compiles."""
    monkeypatch.setattr(batch_verify, "MAX_BUCKET", 32)
    kp = signers[0]
    items = []
    for i in range(5 * 32 + 7):  # 5 full chunks + a ragged tail
        msg = b"chunk-%d" % i
        sig = kp.sign(msg)
        if i % 11 == 3:
            sig = sig[:8] + bytes([sig[8] ^ 2]) + sig[9:]
        items.append(VerifyItem(kp.public_key, msg, sig))
    expect = _expected(items)
    assert batch_verify.verify_batch(items, registry=registry) == expect


@pytest.mark.slow
def test_comb_randomized_mutation_fuzz(signers, registry):
    """Batched randomized differential fuzz: random byte flips at random
    positions in signature/pubkey/message, random message lengths, random
    registered/unregistered signers — one device launch, every verdict
    bit-compared against OpenSSL on BOTH the comb-routed and general
    paths.  Seed printed for reproduction."""
    import os as _os

    seed = int.from_bytes(_os.urandom(4), "little")
    print(f"fuzz seed: {seed}")
    rng = np.random.default_rng(seed)
    stranger = keys.generate_keypair()
    pool = signers + [stranger]
    items = []
    for i in range(96):
        kp = pool[int(rng.integers(0, len(pool)))]
        msg = bytes(rng.integers(0, 256, size=int(rng.integers(0, 200)), dtype=np.uint8))
        sig = bytearray(kp.sign(msg))
        pub = bytearray(kp.public_key)
        mutation = int(rng.integers(0, 4))
        if mutation == 1:  # flip a random signature bit
            pos = int(rng.integers(0, 64))
            sig[pos] ^= 1 << int(rng.integers(0, 8))
        elif mutation == 2:  # flip a random pubkey bit (may un-register it)
            pos = int(rng.integers(0, 32))
            pub[pos] ^= 1 << int(rng.integers(0, 8))
        elif mutation == 3:  # tamper the message after signing
            if msg:
                mpos = int(rng.integers(0, len(msg)))
                msg = msg[:mpos] + bytes([msg[mpos] ^ 0x10]) + msg[mpos + 1:]
        items.append(VerifyItem(bytes(pub), msg, bytes(sig)))
    expect = _expected(items)
    assert batch_verify.verify_batch(items, registry=registry) == expect, seed
    assert batch_verify.verify_batch(items) == expect, seed


def test_comb_table_math_against_host_ints(signers):
    """The device comb table rows really are [d*16^w](-A) in Niels form:
    rebuild one entry from host ints and compare limbs."""
    from mochi_tpu.crypto import field as F

    kp = signers[0]
    x, y = comb.decompress_host(kp.public_key)
    tab = comb.signer_table(kp.public_key)
    P = F.P_INT
    neg = ((P - x) % P, y)
    # [3 * 16^2](-A) by schoolbook host math
    pt = comb._EXT_IDENTITY
    base = (neg[0], neg[1], 1, neg[0] * neg[1] % P)
    for _ in range(2 * 4):  # 16^2 = 2 windows of 4 doublings
        base = comb._ext_add(base, base)
    for _ in range(3):
        pt = comb._ext_add(pt, base)
    (ax, ay), = comb._batch_affine([pt])
    row = tab[2, 3]
    np.testing.assert_array_equal(row[: F.NLIMBS], F.int_to_limbs((ay + ax) % P))
    np.testing.assert_array_equal(
        row[F.NLIMBS : 2 * F.NLIMBS], F.int_to_limbs((ay - ax) % P)
    )
    np.testing.assert_array_equal(
        row[2 * F.NLIMBS :], F.int_to_limbs(2 * F.D_INT * ax % P * ay % P)
    )


@pytest.mark.slow
def test_comb_program_has_no_matmul():
    """The comb program is integer VPU work: no ``dot_general``.  TPU's
    DEFAULT f32 matmul decomposes through bf16 passes whose 8-bit mantissa
    truncates the 15-bit table limbs (wrong basepoint rows, valid signatures
    rejected), and the CPU backend computes full f32 either way, so a numeric
    test here cannot catch one: a formulation that brings a matmul in has to
    pin ``Precision.HIGHEST`` and change this structural check with it."""
    import jax

    from mochi_tpu.crypto.batch_verify import prepare_packed

    reg = comb.SignerRegistry()
    kps = [keys.keypair_from_seed(bytes([i + 1] * 32)) for i in range(2)]
    for kp in kps:
        assert reg.register(kp.public_key) is not None
    items = [
        VerifyItem(kp.public_key, b"p%d" % i, kp.sign(b"p%d" % i))
        for i, kp in enumerate(kps)
    ]
    _, _, y_r, sign_r, s_sc, h_sc, ok = prepare_packed(items)
    assert ok.all()
    key_idx = np.asarray(
        [reg.index_of(it.public_key) for it in items], dtype=np.int32
    )
    table = reg.device_table()

    def dot_precisions(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                out.append(eqn.params.get("precision"))
            for v in eqn.params.values():
                for x in v if isinstance(v, (list, tuple)) else (v,):
                    if hasattr(x, "jaxpr"):
                        dot_precisions(x.jaxpr, out)
        return out

    jx = jax.make_jaxpr(comb.verify_comb_prepared)(
        table, key_idx, y_r, sign_r, s_sc, h_sc
    )
    assert dot_precisions(jx.jaxpr, []) == []
