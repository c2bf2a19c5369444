"""Multi-host (DCN) feeder path for the sharded verifier mesh.

The single-host story (``sharded.py``) runs ``shard_map`` over the local
devices.  Multi-host runs the SAME compiled program over a global mesh that
spans processes: every host calls :func:`init_process` (one coordinator,
N workers — the ``jax.distributed`` analog of the reference's per-host JVM
boot, ``/root/reference/config/aws_5_config``), builds the global mesh from
the now-global ``jax.devices()``, and feeds only its *addressable* slice of
each batch through :func:`host_local_to_global`.  XLA inserts the DCN
collective for the quorum ``psum``; nothing else crosses hosts — by
design the verifier data plane has exactly one small all-reduce per step
(see ``sharded.make_quorum_step``).

Deployment shape: one verifier-service process per host, each the feeder
for its host's chips; replicas keep talking to their host-local service
over the existing mcode RPC.  The cluster control plane (client↔replica
TCP) is host-agnostic already — ``cluster_config.json`` just lists
cross-host URLs (``config/multihost2.json`` mirrors the reference's
5-host EC2 layout).

Tested without multi-host hardware by running N OS processes on one
machine, each forced to the CPU platform with
``--xla_force_host_platform_device_count`` virtual devices
(``tests/test_parallel_multiproc.py``) — the documented JAX recipe for
exercising the real ``jax.distributed`` + global-mesh code path.
"""

from __future__ import annotations

import json
from typing import Optional, Sequence, Tuple

import numpy as np

import jax

from jax.sharding import NamedSharding, PartitionSpec as P

from .sharded import BATCH_AXIS, make_mesh, make_quorum_step


def init_process(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    local_device_count: Optional[int] = None,
) -> None:
    """Join this process to the distributed runtime (idempotent per process).

    Call BEFORE any other JAX API touches the backend.  ``process_id`` 0
    hosts the coordination service at ``coordinator_address``
    (host:port); every process, coordinator included, blocks here until
    all ``num_processes`` have connected — the same rendezvous the
    reference leaves to its operator scripts (it has no cross-server
    runtime at all, SURVEY.md §2.9).
    """
    kwargs = {}
    if local_device_count is not None:
        kwargs["local_device_ids"] = list(range(local_device_count))
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )


def host_local_to_global(mesh, arrays: Sequence[np.ndarray]) -> Tuple:
    """Assemble global device arrays from this process's local batch slice.

    Each process passes the rows its own devices will hold (1/num_processes
    of the global batch, equal split, already padded to a multiple of the
    GLOBAL device count); ``jax.make_array_from_process_local_data`` places
    them on the local shards of the global ``NamedSharding`` without any
    cross-host transfer.
    """
    sharding = NamedSharding(mesh, P(BATCH_AXIS))
    return tuple(
        jax.make_array_from_process_local_data(sharding, np.asarray(a))
        for a in arrays
    )


def _demo_main(argv: Optional[Sequence[str]] = None) -> None:
    """One process of the 2-process CPU-mesh proof (driven by the test).

    Builds a deterministic mixed valid/invalid signature batch, feeds this
    process's half through the global mesh, runs the sharded
    verify+quorum step, and prints the replicated tally as JSON — the
    test asserts both processes computed identical, correct quorums.
    """
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--coordinator", required=True)
    parser.add_argument("--num-processes", type=int, required=True)
    parser.add_argument("--process-id", type=int, required=True)
    parser.add_argument("--lanes-per-process", type=int, default=8)
    args = parser.parse_args(argv)

    # This CLI is the CPU-mesh demonstration: pin the platform before
    # distributed init touches the backend, whatever the host has attached.
    jax.config.update("jax_platforms", "cpu")
    init_process(args.coordinator, args.num_processes, args.process_id)

    assert jax.process_count() == args.num_processes
    n_local = len(jax.local_devices())
    mesh = make_mesh()  # global: spans every process's devices

    from ..crypto import batch_verify, keys
    from ..verifier.spi import VerifyItem

    # Deterministic cross-process pattern without shared key material:
    # lane i of EVERY process votes for group (i % 3); lanes with
    # i % 4 == 3 carry a corrupted signature.  Expected per-group count is
    # then a closed form of (lanes_per_process, num_processes).
    lanes = args.lanes_per_process
    kp = keys.generate_keypair()
    items = []
    group_ids = np.zeros(lanes, dtype=np.int32)
    for i in range(lanes):
        msg = b"lane-%d-%d" % (args.process_id, i)
        sig = kp.sign(msg)
        if i % 4 == 3:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        items.append(VerifyItem(kp.public_key, msg, sig))
        group_ids[i] = i % 3
    y_a, sign_a, y_r, sign_r, s_bits, h_bits, pre_ok = batch_verify.prepare(items)
    assert pre_ok.all()

    n_groups = 3
    step = make_quorum_step(mesh, n_groups)
    g_arrays = host_local_to_global(
        mesh, (y_a, sign_a, y_r, sign_r, s_bits, h_bits, group_ids)
    )
    bitmap, counts, committed = step(*g_arrays, np.int32(3))
    counts = np.asarray(counts)
    committed = np.asarray(committed)
    # local shard of the global bitmap: rows this process fed
    local_bitmap = np.concatenate(
        [np.asarray(s.data) for s in bitmap.addressable_shards]
    )

    # ---- comb leg across the process boundary ---------------------------
    # The registered-signer fast path (crypto/comb.py) on the SAME global
    # mesh: the signer set is cluster config — identical on every host —
    # so each host builds the same table and replicates it to its local
    # devices (no cross-host transfer; DCN carries nothing).  Keys here:
    # a fixed seed so both processes derive the identical registry.
    from ..crypto import comb as comb_mod
    from .sharded import make_sharded_verify_comb

    ckp = keys.keypair_from_seed(bytes([7]) * 32)
    citems = []
    for i in range(lanes):
        msg = b"comb-lane-%d-%d" % (args.process_id, i)
        sig = ckp.sign(msg)
        if i % 4 == 3:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        citems.append(VerifyItem(ckp.public_key, msg, sig))
    reg = comb_mod.SignerRegistry()
    if reg.register(ckp.public_key) is None:
        raise RuntimeError("registration failed")
    _, _, cy_r, csign_r, cs_sc, ch_sc, cpre_ok = batch_verify.prepare_packed(citems)
    assert cpre_ok.all()
    key_idx = np.zeros(lanes, dtype=np.int32)
    rep = NamedSharding(mesh, P())
    table_np = np.asarray(reg.device_table())
    table_g = jax.make_array_from_process_local_data(rep, table_np)
    cg = host_local_to_global(mesh, (key_idx, cy_r, csign_r, cs_sc, ch_sc))
    comb_fn = make_sharded_verify_comb(mesh)
    cbitmap = comb_fn(table_g, *cg)
    comb_local = np.concatenate(
        [np.asarray(s.data) for s in cbitmap.addressable_shards]
    )
    expect_local = np.asarray([i % 4 != 3 for i in range(lanes)])
    assert (comb_local == expect_local).all(), (comb_local, expect_local)

    print(
        json.dumps(
            {
                "process_id": args.process_id,
                "process_count": jax.process_count(),
                "local_devices": n_local,
                "global_devices": len(jax.devices()),
                "counts": counts.tolist(),
                "committed": committed.tolist(),
                "local_valid": int(local_bitmap.sum()),
                "comb_local_valid": int(comb_local.sum()),
            }
        )
    )


if __name__ == "__main__":
    _demo_main()
