"""Config 3: n=16 f=5 quorum-cert aggregation under YCSB-A.

YCSB workload A: 50% reads / 50% updates over a zipfian key popularity
distribution.  Each update's Write2 carries a certificate of 2f+1 = 11
MultiGrants whose signatures are checked through the batch verifier; each
read response is server-signed.  The measured number is certificate-
aggregation throughput: how many (verify 11-grant certificate + tally) ops
the verifier sustains per second, with signatures batched across concurrent
transactions — the reference's quorum tally (``InMemoryDataStore.java:590``,
``MochiDBClient.java:378-382``) plus the signature checks it never had.
"""

from __future__ import annotations

import time
from typing import Dict


def _zipf_keys(rng, n_keys: int, n_ops: int, s: float = 0.99, prefix: str = "key"):
    ranks = (
        rng.zipf(1.0 + s, size=n_ops * 2) - 1
    )  # oversample, clip to key space
    ranks = ranks[ranks < n_keys][:n_ops]
    while len(ranks) < n_ops:
        more = rng.zipf(1.0 + s, size=n_ops) - 1
        ranks = list(ranks) + list(more[more < n_keys])
        ranks = ranks[:n_ops]
    return [f"{prefix}-{r}" for r in ranks]


def run(n: int = 16, f: int = 5, n_ops: int = 2048, batch: int = 4096) -> Dict:
    import numpy as np

    import jax

    from mochi_tpu.crypto import batch_verify, keys
    from mochi_tpu.crypto.curve import verify_prepared
    from mochi_tpu.parallel.sharded import make_mesh, make_quorum_step
    from mochi_tpu.verifier.spi import VerifyItem

    assert n >= 3 * f + 1
    quorum = 2 * f + 1
    rng = np.random.default_rng(99)
    server_keys = [keys.generate_keypair() for _ in range(n)]
    ycsb_keys = _zipf_keys(rng, n_keys=256, n_ops=n_ops)

    # Build the signature stream: updates contribute `quorum` grant
    # signatures, reads one response signature (50/50 split).
    items = []
    group_ids = []
    group = 0
    for i, key in enumerate(ycsb_keys):
        if i % 2 == 0:  # update: a Write2 certificate of 2f+1 signed grants
            payload = b"grant|%s|ts=%d" % (key.encode(), i)
            for s in range(quorum):
                items.append(
                    VerifyItem(
                        server_keys[s].public_key,
                        payload,
                        server_keys[s].sign(payload),
                    )
                )
                group_ids.append(group)
        else:  # read: one signed response from a random replica
            payload = b"read|%s|rid=%d" % (key.encode(), i)
            sidx = int(rng.integers(0, n))
            items.append(
                VerifyItem(
                    server_keys[sidx].public_key, payload, server_keys[sidx].sign(payload)
                )
            )
            group_ids.append(group)
        group += 1

    n_groups = group
    prep = batch_verify.prepare(items)
    dev = jax.devices()[0]

    mesh = make_mesh(len(jax.devices()[:1]))  # single device: still exercises the step
    step = make_quorum_step(mesh, n_groups)
    # pad to mesh multiple
    from mochi_tpu.parallel.sharded import pad_to_multiple

    arrays, m = pad_to_multiple(
        tuple(prep[:6]) + (np.asarray(group_ids, np.int32),),
        len(items),
        mesh.devices.size,
        dead_group=0,
    )
    args = tuple(jax.device_put(a, dev) for a in arrays)
    thr = np.int32(quorum)

    out = jax.block_until_ready(step(*args, thr))  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        # readback inside the timed region
        out = tuple(np.asarray(x) for x in step(*args, thr))
        best = min(best, time.perf_counter() - t0)
    bitmap, counts, committed = out
    assert bitmap[: len(items)].all()

    rec = {
        "metric": "ycsb_a_quorum_cert_aggregation",
        "value": round(n_groups / best, 1),
        "unit": "certs/sec",
        "sigs_per_sec": round(len(items) / best, 1),
        "n": n,
        "f": f,
        "quorum": quorum,
        "ops": n_groups,
        "sigs": len(items),
        "ms": round(best * 1e3, 2),
    }
    try:
        rec["cluster_ycsb_a"] = run_cluster_ycsb()
    except Exception as exc:  # the device microbench result stands alone
        rec["cluster_ycsb_a"] = {"error": f"{type(exc).__name__}: {exc}"[:200]}
    return rec


def run_cluster_ycsb(
    n_clients: int = 5, n_ops_per_client: int = 60, n_keys: int = 64
):
    """YCSB-A through the REAL cluster in the production verify posture:
    50% reads / 50% updates over a zipfian key distribution, 5 concurrent
    clients against a 5-replica virtual cluster (rf=4, full signing), with
    every replica shipping its signature batches to ONE shared verifier
    service over the mcode RPC — the service runs the TPU batch verifier
    when a chip is present (client→replica→service→device end-to-end,
    VERDICT r2 item 6), and the batching+memoizing CPU path otherwise."""
    import asyncio
    import time as _time

    import numpy as np

    try:
        from benchmarks.config1_cluster import _pct
    except ImportError:  # direct-script run: benchmarks/ is sys.path[0]
        from config1_cluster import _pct
    from mochi_tpu.client.txn import TransactionBuilder
    from mochi_tpu.testing.virtual_cluster import VirtualCluster
    from mochi_tpu.verifier.service import RemoteVerifier, VerifierService
    from mochi_tpu.verifier.spi import CoalescingVerifier, CpuVerifier

    rng = np.random.default_rng(4242)

    async def amain():
        inner = None
        platform = "cpu-service"
        try:
            import jax

            if jax.default_backend() == "tpu":
                from mochi_tpu.verifier.tpu import TpuBatchVerifier

                inner = TpuBatchVerifier(max_delay_s=0.001, warmup_buckets=(16,))
                platform = "tpu-service"
        except Exception:
            inner = None
        if inner is None:
            inner = CpuVerifier()
        service = VerifierService(port=0, verifier=inner)
        await service.start()
        factory = lambda: CoalescingVerifier(RemoteVerifier("127.0.0.1", service.bound_port))
        try:
            return await _ycsb_cluster(factory, platform, service)
        finally:
            await service.close()

    async def _ycsb_cluster(factory, platform, service):
        async with VirtualCluster(5, rf=4, verifier_factory=factory) as vc:
            # Register the replica identities with the service's comb
            # registry: grant-certificate traffic is signed exclusively by
            # these n keys, so it takes the doubling-free comb path
            # (crypto/comb.py) — the production posture for cluster verify.
            if hasattr(service.verifier, "register_signers"):
                service.verifier.register_signers(
                    list(vc.config.public_keys.values())
                )
            # preload the keyspace so reads hit existing keys — batched
            # into multi-write transactions (16 keys each) instead of 64
            # sequential round trips of untimed setup
            seed_client = vc.client()
            for base in range(0, n_keys, 16):
                tb = TransactionBuilder()
                for i in range(base, min(base + 16, n_keys)):
                    tb.write(f"y-{i}", b"init")
                await seed_client.execute_write_transaction(tb.build())
            read_lat: list = []
            update_lat: list = []

            async def worker(ci: int):
                client = vc.client()
                klist = _zipf_keys(
                    rng, n_keys=n_keys, n_ops=n_ops_per_client, prefix="y"
                )
                for j, key in enumerate(klist):
                    t0 = _time.perf_counter()
                    if j % 2 == 0:
                        await client.execute_write_transaction(
                            TransactionBuilder().write(key, b"u%d-%d" % (ci, j)).build()
                        )
                        update_lat.append(_time.perf_counter() - t0)
                    else:
                        await client.execute_read_transaction(
                            TransactionBuilder().read(key).build()
                        )
                        read_lat.append(_time.perf_counter() - t0)
                await client.close()

            t0 = _time.perf_counter()
            await asyncio.gather(*[worker(i) for i in range(n_clients)])
            wall = _time.perf_counter() - t0
            await seed_client.close()

            ops = n_clients * n_ops_per_client
            return {
                "txn_s": round(ops / wall, 1),
                "read_p50_ms": round(_pct(read_lat, 0.5) * 1e3, 2),
                "read_p95_ms": round(_pct(read_lat, 0.95) * 1e3, 2),
                "update_p50_ms": round(_pct(update_lat, 0.5) * 1e3, 2),
                "update_p95_ms": round(_pct(update_lat, 0.95) * 1e3, 2),
                "clients": n_clients,
                "ops": ops,
                "zipf_keys": n_keys,
                # provenance emitted by the harness so --publish republishes
                # it instead of dropping hand-edits
                "platform": platform,
                "service_items": service.items,
                "topology": (
                    "client -> replica -> shared verifier service -> device; "
                    "5-replica virtual cluster, rf=4, full signing"
                ),
            }

    return asyncio.run(amain())


if __name__ == "__main__":
    import json

    print(json.dumps(run()))
