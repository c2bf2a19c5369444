"""Config 5: multi-shard batch verify over a device mesh (shard_map/ICI).

BASELINE.json sketched this as "pmap across 4 TPU chips"; the modern
equivalent is ``shard_map`` over a ``jax.sharding.Mesh``
(``mochi_tpu.parallel``).  On single-chip hardware this still runs
(1-device mesh); to exercise a real 8-way mesh on CPU set
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Round-4 rework: the headline now measures what
production runs — :class:`mochi_tpu.verifier.tpu.ShardedJaxBatchBackend`
(packed (B, 32)-byte scalar transfers) — instead of the bit-tensor
``make_quorum_step`` the round-2 capture used (32x larger H2D transfers,
the prime suspect for its 12x sharded-vs-unsharded gap).  The published
record decomposes the gap into its three candidate factors:

* ``transfer_form``: packed-sharded vs bits-sharded at the same batch —
  isolates the H2D transfer form;
* ``shard_tax``: packed-sharded vs packed-unsharded at the same batch —
  isolates the shard_map/psum machinery;
* ``batch_size``: per-device batch 8192 vs the round-2 2048 — isolates
  underfilled devices.
"""

from __future__ import annotations

import time
from typing import Dict


def _timed_rate(fn, batch: int, iters: int) -> float:
    """Best-of-iters sigs/s; np.asarray readback inside the timed region."""
    import numpy as np

    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        if isinstance(out, tuple):
            out = tuple(np.asarray(x) for x in out)
        else:
            np.asarray(out)
        best = min(best, time.perf_counter() - t0)
    return batch / best


def run(batch_per_device: int = 0, n_groups: int = 64, iters: int = 3) -> Dict:
    import numpy as np

    import jax

    from mochi_tpu.crypto import batch_verify, keys
    from mochi_tpu.parallel.sharded import (
        make_mesh,
        make_quorum_step,
        make_sharded_verify_packed,
        pad_to_multiple,
    )
    from mochi_tpu.verifier.spi import VerifyItem
    from mochi_tpu.verifier.tpu import ShardedJaxBatchBackend

    mesh = make_mesh()
    n_dev = mesh.devices.size
    platform = jax.devices()[0].platform
    if batch_per_device <= 0:
        # 8192/device is the measured single-chip peak; CPU test meshes use
        # a small batch (the CPU backend compiles/runs ~75x slower).
        batch_per_device = 8192 if platform == "tpu" else 256
    b = batch_per_device * n_dev

    kp = keys.generate_keypair()
    items = []
    for i in range(b):
        msg = b"shard %d" % i
        items.append(VerifyItem(kp.public_key, msg, kp.sign(msg)))

    # ---- headline: the PRODUCTION sharded backend, end to end -----------
    # (host prepare_packed + pad + packed H2D + shard_map verify + readback
    # — exactly what ShardedTpuBatchVerifier runs per flush)
    backend = ShardedJaxBatchBackend(mesh=mesh, min_device_items=0)
    out = backend._sharded_verify(items)  # compile + warm
    assert all(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = backend._sharded_verify(items)
        best = min(best, time.perf_counter() - t0)
    assert all(out)
    rec: Dict = {
        "metric": "multichip_sharded_verify_throughput",
        "value": round(b / best, 1),
        "unit": "sigs/sec",
        "devices": n_dev,
        "platform": platform,
        "batch_total": b,
        "batch_per_device": batch_per_device,
        "ms": round(best * 1e3, 2),
        "path": "ShardedJaxBatchBackend (packed production path, end-to-end)",
    }

    # ---- comb leg: registered-signer traffic on the same backend --------
    # (the production posture — service --signers-file; ~3x fewer device
    # FLOPs per item, comb.py)
    backend.register_signers([kp.public_key])
    out = backend._sharded_verify(
        items, registry=backend.registry, comb_gen=backend.registry.generation
    )  # compile + warm
    assert all(out)
    comb_best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = backend._sharded_verify(
            items,
            registry=backend.registry,
            comb_gen=backend.registry.generation,
        )
        comb_best = min(comb_best, time.perf_counter() - t0)
    assert all(out)
    rec["comb_sigs_per_sec"] = round(b / comb_best, 1)
    rec["comb_vs_ladder"] = round(best / comb_best, 2)

    # ---- decomposition at the same total batch --------------------------
    y_a, sign_a, y_r, sign_r, s_sc, h_sc, pre_ok = batch_verify.prepare_packed(items)
    assert pre_ok.all()
    packed_np = (y_a, sign_a, y_r, sign_r, s_sc, h_sc)

    sharded_packed = make_sharded_verify_packed(mesh)
    jax.block_until_ready(sharded_packed(*packed_np))  # compile
    rate_sharded_packed = _timed_rate(lambda: sharded_packed(*packed_np), b, iters)

    unsharded = batch_verify._verify_packed_jit
    jax.block_until_ready(unsharded(*packed_np))  # compile
    rate_unsharded_packed = _timed_rate(lambda: unsharded(*packed_np), b, iters)

    # bits form + quorum tally (the round-2 capture's path)
    prep_bits = batch_verify.prepare(items)
    group_ids = (np.arange(b, dtype=np.int32) % n_groups).astype(np.int32)
    arrays, _m = pad_to_multiple(
        tuple(prep_bits[:6]) + (group_ids,), b, n_dev, dead_group=0
    )
    step = make_quorum_step(mesh, n_groups)
    thr = np.int32(1)
    out = jax.block_until_ready(step(*arrays, thr))  # compile
    assert np.asarray(out[0])[:b].all()
    rate_bits_quorum = _timed_rate(lambda: step(*arrays, thr), b, iters)

    rec["decomposition"] = {
        "sharded_packed_sigs_per_sec": round(rate_sharded_packed, 1),
        "unsharded_packed_sigs_per_sec": round(rate_unsharded_packed, 1),
        "bits_quorum_step_sigs_per_sec": round(rate_bits_quorum, 1),
        "shard_tax": round(rate_unsharded_packed / rate_sharded_packed, 2),
        "transfer_form_gain": round(rate_sharded_packed / rate_bits_quorum, 2),
        "note": "shard_tax ~1 => shard_map/psum machinery is free; "
        "transfer_form_gain >1 => the round-2 gap was the bit-tensor H2D "
        "form, not the mesh",
    }

    # ---- batch-size effect (the round-2 capture ran 2048/device) --------
    small = batch_per_device // 4
    if small >= 16:
        items_small = items[: small * n_dev]
        prep_small = batch_verify.prepare_packed(items_small)[:6]
        jax.block_until_ready(sharded_packed(*prep_small))  # compile
        rate_small = _timed_rate(
            lambda: sharded_packed(*prep_small), small * n_dev, iters
        )
        rec["batch_size_effect"] = {
            "per_device": {
                str(small): round(rate_small, 1),
                str(batch_per_device): round(rate_sharded_packed, 1),
            },
            "gain": round(rate_sharded_packed / rate_small, 2),
        }

    # quorum tally remains the distributed-step capability proof: every
    # signature is valid, so the cross-device psum must reproduce the exact
    # per-group membership counts (a mis-tally would be invisible to a
    # weaker >=0 check).
    counts = np.asarray(out[1])
    expected = np.bincount(group_ids, minlength=n_groups)
    rec["quorum_step"] = {
        "n_groups": n_groups,
        "psum_counts_ok": bool((counts[:n_groups] == expected[:n_groups]).all()),
        "sigs_per_sec": round(rate_bits_quorum, 1),
    }
    return rec


if __name__ == "__main__":
    import json

    print(json.dumps(run()))
