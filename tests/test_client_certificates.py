"""The SDK builds the certificate of the one answer it returns (PR 44).

A reply's certificate reaches the SDK as the codec's tree
(``messages._Deferred``); the tallies vote on the value and build exactly one
certificate an operation: the returned answer's, before the caller sees it.
An agreeing answer whose tree does not build is never the one returned, and
its sender is marked like an outvoted one.
"""

from __future__ import annotations

import asyncio

import pytest

from mochi_tpu.client.errors import InconsistentRead, InconsistentWrite
from mochi_tpu.client.txn import TransactionBuilder
from mochi_tpu.protocol import (
    OperationResult,
    Status,
    TransactionResult,
    Write2AnsFromServer,
    certificates_deferred,
)
from mochi_tpu.protocol.codec import decode, encode
from mochi_tpu.testing.virtual_cluster import VirtualCluster


def run(coro):
    asyncio.run(asyncio.wait_for(coro, timeout=90))


def _gained(client, before: dict) -> dict:
    now = client.metrics.counters
    return {
        short: now.get(f"client.certificates-{short}", 0) - before.get(f"client.certificates-{short}", 0)
        for short in ("received", "built")
    }


class _Garbled:
    """Stands where a replica's ``WriteCertificate`` would: goes out on the
    wire as a tree no certificate builds from (a MultiGrant of two fields)."""

    def to_obj(self):
        return {"server-0": [1, 2]}


def _garble_reads(replica) -> None:
    """The replica answers reads with the right value and a malformed
    certificate tree."""
    honest = replica.store.process_read

    def process_read(transaction):
        return TransactionResult(
            tuple(
                OperationResult(r.value, _Garbled(), r.existed, r.status)
                if r.current_certificate is not None else r
                for r in honest(transaction).operations
            )
        )

    replica.store.process_read = process_read


@pytest.mark.parametrize("n, quorum", [(64, 43), (4, 3)])
def test_read_builds_one_certificate_of_the_quorum_it_receives(n, quorum):
    """n=64: 43 answers, each with a 43-grant certificate, one built."""

    async def main():
        async with VirtualCluster(n, rf=n) as vc:
            assert vc.config.quorum == quorum
            client = vc.client(timeout_s=60.0)
            await client.execute_write_transaction(
                TransactionBuilder().write("k", b"v" * 1000).build()
            )
            before = dict(client.metrics.counters)
            res = await client.execute_read_transaction(TransactionBuilder().read("k").build())
            op = res.operations[0]
            assert certificates_deferred(op) == 0  # built before the caller has it
            assert "current_certificate" in op.__dict__
            assert op.value == b"v" * 1000 and len(op.current_certificate.grants) == quorum
            assert _gained(client, before) == {"received": quorum, "built": 1}
            # the happy path of an update carries no certificate back
            before = dict(client.metrics.counters)
            await client.execute_write_transaction(TransactionBuilder().write("k", b"w").build())
            assert _gained(client, before) == {"received": 0, "built": 0}

    run(main())


def test_agreeing_answer_with_a_malformed_certificate_is_never_returned():
    async def main():
        async with VirtualCluster(4, rf=4) as vc:
            client = vc.client()
            await client.execute_write_transaction(TransactionBuilder().write("k", b"v").build())
            _garble_reads(vc.replica("server-0"))
            # a tally walks its answers in the order they arrived: have
            # server-0's first whenever it is among them
            fan_out = client._fan_out

            async def server_0_first(*args, **kwargs):
                return dict(sorted((await fan_out(*args, **kwargs)).items()))

            client._fan_out = server_0_first
            before = dict(client.metrics.counters)
            reads = 8  # the rotor leaves server-0 out of a trimmed read one time in four
            for _ in range(reads):
                res = await client.execute_read_transaction(TransactionBuilder().read("k").build())
                op = res.operations[0]
                assert op.value == b"v"
                assert len(op.current_certificate.grants) >= vc.config.quorum
            counters = client.metrics.counters
            marks = counters.get("suspect.bad-certificate.server-0", 0)
            assert marks >= 1  # it agreed, came first, and was passed over
            assert client.suspicion_stats()["server-0"]["bad-certificate"] == marks
            assert not any(
                name.startswith("suspect.") and not name.endswith(".server-0")
                for name in counters
            )
            # three marks pass the threshold: trimmed reads then leave it out
            assert marks < reads
            assert _gained(client, before)["built"] == reads

    run(main())


def test_read_fails_when_no_agreeing_answer_builds():
    async def main():
        async with VirtualCluster(4, rf=4) as vc:
            client = vc.client(timeout_s=2.0)
            await client.execute_write_transaction(TransactionBuilder().write("k", b"v").build())
            for replica in vc.replicas:
                _garble_reads(replica)
            with pytest.raises(InconsistentRead, match="no agreeing answer's certificate builds"):
                await client._read_once(TransactionBuilder().read("k").build(), trim=False)
            assert all(
                client.metrics.counters.get(f"suspect.bad-certificate.server-{i}", 0) == 1
                for i in range(4)
            )
            # and through the public entry point, recovery included
            with pytest.raises(InconsistentRead):
                await client.execute_read_transaction(TransactionBuilder().read("k").build())

    run(main())


def _write2_answer(value: bytes, certificate) -> Write2AnsFromServer:
    """A stale Write2's answer (the one Write2 answer that echoes a
    certificate), as it reaches the SDK: decoded."""
    sent = Write2AnsFromServer(
        TransactionResult((OperationResult(value, certificate, True, Status.OK),)), "rid"
    )
    return Write2AnsFromServer.from_obj(decode(encode(sent.to_obj())))


def test_write2_tally_returns_a_built_answer_or_fails():
    async def main():
        async with VirtualCluster(4, rf=4) as vc:
            client = vc.client()
            await client.execute_write_transaction(TransactionBuilder().write("k", b"v").build())
            cert = vc.replica("server-1").store._get("k").current_certificate
            txn = TransactionBuilder().write("k", b"late").build()
            responses = {
                "server-0": _write2_answer(b"v", _Garbled()),
                "server-1": _write2_answer(b"v", cert),
                "server-2": _write2_answer(b"v", cert),
                "server-3": _write2_answer(b"other", cert),
            }
            before = dict(client.metrics.counters)
            op = client._tally_write2(txn, responses).operations[0]
            assert op is responses["server-1"].result.operations[0]
            assert "current_certificate" in op.__dict__ and op.current_certificate == cert
            assert certificates_deferred(responses["server-2"]) == 1  # tallied, never built
            assert _gained(client, before)["built"] == 1
            assert client.metrics.counters["suspect.bad-certificate.server-0"] == 1
            assert client.metrics.counters["suspect.tally-outvoted.server-3"] == 1
            with pytest.raises(InconsistentWrite, match="no agreeing answer's certificate builds"):
                client._tally_write2(
                    txn, {f"server-{i}": _write2_answer(b"v", _Garbled()) for i in range(3)}
                )

    run(main())
