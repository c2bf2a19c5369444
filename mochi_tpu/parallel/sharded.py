"""Sharded Ed25519 verification + quorum tally over a device mesh.

BASELINE.json config 5 ("multi-shard batch verify, pmap across 4 TPU chips
over ICI"), done the modern way: ``shard_map`` over a 1-D
``jax.sharding.Mesh`` instead of ``pmap``.  Each chip verifies its slice of
the signature batch (pure VPU/MXU work, zero communication), then the
2f+1 quorum tally — the reference's grant-count check at
``InMemoryDataStore.java:590`` and the client-side per-op tally at
``MochiDBClient.java:378-382`` — becomes a segment-sum of the local validity
bitmap onto quorum slots followed by a single ``psum`` over ICI.  One small
collective per step; the heavy math never leaves the chip.

All shapes are static; callers pad the batch to a multiple of the mesh size
(:func:`pad_to_multiple`) with lanes whose ``group_id`` points at a dead slot.

Multi-host (DCN) scaling is implemented in ``parallel/multihost.py``: the
same program runs unchanged under ``jax.distributed.initialize()`` —
``jax.devices()`` then spans hosts, :func:`make_mesh` builds the global
mesh, and each host feeds its addressable shard of the batch
(``multihost.host_local_to_global``).  Because verification is
embarrassingly parallel with the single ``psum`` tally as the only
collective, the DCN hop costs one small all-reduce per step; each host's
lanes come from its own colocated verifier service (the service already
owns batching, so each host-local service simply becomes one feeder of
the global mesh).  Proven end-to-end by the 2-process CPU-mesh test
(``tests/test_parallel_multiproc.py``); cross-host cluster layout in
``config/multihost5/``.  This mirrors the reference's topology, where the
only cross-host traffic is the client↔replica fan-out (SURVEY.md §2.9 —
it has no server↔server links at all); the data-plane collective is new
capability.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..crypto import curve

BATCH_AXIS = "batch"

# The mesh-sharded twins of batch_verify.LADDER_PROGRAM and comb.COMB_PROGRAM
# (the production multi-chip path), under names of their own: both used to be
# ``jit_verify``, which a trace could not tell apart.
SHARDED_LADDER_PROGRAM = "jit_verify_prepared_packed_sharded"
SHARDED_COMB_PROGRAM = "jit_verify_comb_prepared_sharded"


def make_mesh(n_devices: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """1-D device mesh over the batch axis.

    On a real pod slice the devices arrive in ICI-neighbor order from
    ``jax.devices()``, so the (single) collective rides ICI.
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (BATCH_AXIS,))


def pad_to_multiple(arrays, n: int, multiple: int, dead_group: int):
    """Pad leading dim of each array to a multiple; extra group_ids -> dead slot.

    ``arrays`` is the (y_a, sign_a, y_r, sign_r, s_bits, h_bits, group_ids)
    tuple; padded lanes fail verification (all-zero encodings are fine to
    feed the kernel) and tally into ``dead_group`` which callers ignore.
    """
    m = ((n + multiple - 1) // multiple) * multiple
    if m == n:
        return arrays, n
    out = []
    for i, a in enumerate(arrays):
        pad = [(0, m - n)] + [(0, 0)] * (a.ndim - 1)
        if i == len(arrays) - 1:  # group_ids
            a = np.pad(a, pad, constant_values=dead_group)
        else:
            a = np.pad(a, pad)
        out.append(a)
    return tuple(out), m


def make_sharded_verify(mesh: Mesh):
    """Jitted batch-sharded verify: tensors sharded on axis 0 -> bitmap.

    Embarrassingly parallel (no collective): each device runs the full
    decompress + double-scalar-mul pipeline on its batch slice.
    """
    spec = P(BATCH_AXIS)
    sharding = NamedSharding(mesh, spec)

    @partial(jax.jit, out_shardings=sharding)
    def verify(y_a, sign_a, y_r, sign_r, s_bits, h_bits):
        # check_vma=False: the fori_loop carry starts from broadcast constants
        # (the identity point) and becomes device-varying on the first
        # iteration, which the varying-axis checker rejects; the code is
        # per-device pure so the check is safely skipped.
        f = shard_map(
            curve.verify_prepared,
            mesh=mesh,
            in_specs=(spec,) * 6,
            out_specs=spec,
            check_vma=False,
        )
        return f(y_a, sign_a, y_r, sign_r, s_bits, h_bits)

    return verify


def make_sharded_verify_packed(mesh: Mesh):
    """Batch-sharded verify in the PACKED scalar form (scalars as (B, 32)
    uint8 bytes, unpacked on device — 32x smaller H2D transfer than the
    bit-tensor form; see ``curve.verify_prepared_packed``).  This is the
    production multi-chip path (``verifier.tpu.ShardedJaxBatchBackend``);
    :func:`make_sharded_verify` keeps the bit-tensor form for callers that
    already hold it."""
    spec = P(BATCH_AXIS)
    sharding = NamedSharding(mesh, spec)

    def verify(y_a, sign_a, y_r, sign_r, s_bytes, h_bytes):
        f = shard_map(
            curve.verify_prepared_packed,
            mesh=mesh,
            in_specs=(spec,) * 6,
            out_specs=spec,
            check_vma=False,
        )
        return f(y_a, sign_a, y_r, sign_r, s_bytes, h_bytes)

    return jax.jit(
        curve.named_program(verify, SHARDED_LADDER_PROGRAM), out_shardings=sharding
    )


def make_sharded_verify_comb(mesh: Mesh):
    """Batch-sharded KNOWN-SIGNER comb verify (``crypto/comb.py``): the
    signature tensors shard over the batch axis while the per-signer comb
    table (a few MB for a 64-replica cluster) is REPLICATED to every
    device — each chip gathers from its local copy, so the path stays
    collective-free like the general sharded verify.  ~3x fewer field muls
    per item than the ladder (comb.py docstring)."""
    from ..crypto import comb

    spec = P(BATCH_AXIS)
    rep = P()
    sharding = NamedSharding(mesh, spec)

    def verify(table, key_idx, y_r, sign_r, s_bytes, h_bytes):
        f = shard_map(
            comb.verify_comb_prepared,
            mesh=mesh,
            in_specs=(rep, spec, spec, spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return f(table, key_idx, y_r, sign_r, s_bytes, h_bytes)

    return jax.jit(
        curve.named_program(verify, SHARDED_COMB_PROGRAM), out_shardings=sharding
    )


def make_quorum_step(mesh: Mesh, n_groups: int):
    """Jitted full distributed step: sharded verify + cross-chip quorum tally.

    Inputs (leading dim B, sharded over the mesh):
      * the six prepared signature tensors (see ``crypto.batch_verify.prepare``)
      * ``group_ids``: (B,) int32 — which quorum slot (object/transaction)
        each signature votes for; grants from all replicas for one object
        share a slot (the MultiGrant coalescing of ``InMemoryDataStore
        .processMultiGrantsFromAllServers``, SURVEY.md §2.5).
      * ``threshold``: scalar int32 — 2f+1.

    Returns (bitmap (B,), counts (n_groups,), committed (n_groups,) bool).
    The tally is the only cross-device traffic: an (n_groups,) int32 psum.
    """
    spec = P(BATCH_AXIS)
    rep = P()

    def step(y_a, sign_a, y_r, sign_r, s_bits, h_bits, group_ids, threshold):
        def local(y_a, sign_a, y_r, sign_r, s_bits, h_bits, group_ids, threshold):
            bitmap = curve.verify_prepared(y_a, sign_a, y_r, sign_r, s_bits, h_bits)
            partial_counts = jnp.zeros(n_groups, dtype=jnp.int32).at[group_ids].add(
                bitmap.astype(jnp.int32), mode="drop"
            )
            counts = jax.lax.psum(partial_counts, BATCH_AXIS)
            return bitmap, counts, counts >= threshold

        f = shard_map(
            local,
            mesh=mesh,
            in_specs=(spec,) * 7 + (rep,),
            out_specs=(spec, rep, rep),
            check_vma=False,
        )
        return f(y_a, sign_a, y_r, sign_r, s_bits, h_bits, group_ids, threshold)

    return jax.jit(step)
