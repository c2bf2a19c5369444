"""Pallas verify kernel: differential parity with the XLA and CPU paths.

Runs in interpret mode on CPU (exact, slow) — small blocks/batches only.
The same kernel compiles for real on TPU (tiling: limbs on sublanes, batch
on 128-wide lanes).  Round 2: the kernel shares ``curve.verify_core`` with
the XLA path, so the only kernel-specific behavior left to test is the
``pallas_call`` plumbing (block specs, padding, transposes) and the
Mosaic-safe "shift" column accumulation.
"""

import numpy as np
import pytest

from mochi_tpu.crypto import batch_verify, keys
from mochi_tpu.crypto import pallas_verify as PV
from mochi_tpu.verifier.spi import VerifyItem


def _prep(items):
    return batch_verify.prepare(items)[:6]


@pytest.mark.slow
def test_pallas_kernel_matches_xla_path():
    """Full kernel through pl.pallas_call in interpret mode; on a TPU
    backend the same call compiles the real kernel via Mosaic."""
    kp = keys.generate_keypair()
    items = []
    for i in range(6):
        msg = b"pallas %d" % i
        sig = bytearray(kp.sign(msg))
        if i in (2, 4):
            sig[1] ^= 0x40  # forge
        items.append(VerifyItem(kp.public_key, msg, bytes(sig)))
    tensors = _prep(items)
    got = np.asarray(PV.verify_prepared_pallas(*tensors, block=8, interpret=True))
    expect = np.array([True, True, False, True, False, True])
    assert (got == expect).all()
