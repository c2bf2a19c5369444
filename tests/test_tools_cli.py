"""CLI-level tests for the operator tools (gen_cluster, reconfigure).

These are the entry points a human operator actually types (the verify
recipe uses them verbatim); everything below them is covered elsewhere —
this pins the argument parsing, file formats and exit behavior.
"""

from __future__ import annotations

import asyncio

from mochi_tpu.cluster.config import ClusterConfig
from mochi_tpu.client.txn import TransactionBuilder
from mochi_tpu.crypto.keys import keypair_from_seed
from mochi_tpu.testing.virtual_cluster import VirtualCluster
from mochi_tpu.tools import gen_cluster, reconfigure


def run(coro):
    asyncio.run(coro)


def test_gen_cluster_cli_produces_loadable_config(tmp_path):
    out = tmp_path / "cluster"
    gen_cluster.main(
        [
            "--out-dir", str(out),
            "--servers", "5",
            "--rf", "4",
            "--base-port", "19301",
            "--with-admin",
        ]
    )
    cfg = ClusterConfig.from_json((out / "cluster_config.json").read_text())
    assert cfg.n_servers == 5 and cfg.rf == 4 and cfg.quorum == 3
    assert cfg.admin_keys, "--with-admin must pin an admin key"
    # every seed file reconstructs the keypair whose public key the
    # config carries
    for sid in cfg.servers:
        seed = bytes.fromhex((out / f"{sid}.seed").read_text().strip())
        kp = keypair_from_seed(seed)
        assert cfg.public_keys[sid] == kp.public_key, sid
    admin_seed = bytes.fromhex((out / "admin.seed").read_text().strip())
    assert keypair_from_seed(admin_seed).public_key in cfg.admin_keys


def test_reconfigure_cli_removes_server_live(tmp_path):
    async def main():
        async with VirtualCluster(5, rf=4) as vc:
            client = vc.client()
            await client.execute_write_transaction(
                TransactionBuilder().write("cli-key", b"v").build()
            )
            cfg_path = tmp_path / "cfg.json"
            cfg_path.write_text(vc.config.to_json())
            out_path = tmp_path / "cfg2.json"
            # reconfigure.main runs its own event loop — give it a thread
            await asyncio.to_thread(
                reconfigure.main,
                [
                    "--config", str(cfg_path),
                    "--remove", "server-4",
                    "--out", str(out_path),
                ],
            )
            new_cfg = ClusterConfig.from_json(out_path.read_text())
            assert "server-4" not in new_cfg.servers
            assert new_cfg.configstamp == vc.config.configstamp + 1
            # the cluster actually installed it and still serves the data
            for r in vc.replicas[:4]:
                assert r.config.configstamp == new_cfg.configstamp
            res = await client.execute_read_transaction(
                TransactionBuilder().read("cli-key").build()
            )
            assert res.operations[0].value == b"v"
            await client.close()

    run(main())
