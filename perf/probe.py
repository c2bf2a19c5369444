"""What the harness sends after the window has closed, to decide ``correct``:

* a seeded handful of bad Write2s — one grant signature altered, or one grant
  short of a quorum — which every replica has to refuse
  (``testing/byzantine_client.py`` has no such strategy, so it is written
  here, from the SDK's own message builders as that module does);
* one batch for each device program (comb, ladder) through the verifier RPC,
  large enough for the product's own routing to send it to the device, with a
  quarter of its items forged: the verdicts have to equal the validity each
  item was made with.  It guarantees that every run drives the device path.

For a cell with a fault schedule it also reads, from a restarted replica
alone, every record that replica owns and the window updated
(``direct_reads``).

Before the load it also offers the service the batch sizes the cell's traffic
can flush (``warm_device_buckets``), so that nothing is built inside the window.
"""

from __future__ import annotations

import asyncio
import hashlib
import random

from mochi_tpu.net.transport import new_msg_id
from mochi_tpu.protocol import (
    Action,
    Operation,
    ReadFromServer,
    ReadToServer,
    Status,
    Transaction,
    Write1OkFromServer,
    Write1ToServer,
    Write2AnsFromServer,
    Write2ToServer,
    WriteCertificate,
    transaction_hash,
)

from ycsb import SDK_TIMEOUT_S, parse_tag, sdk_read

BAD_WRITE2_KINDS = ("altered-signature", "under-quorum")


async def _send_to_all(client, targets, payload) -> list:
    """One payload to every target, as the SDK envelopes it; the answers'
    payloads, None where a target gave none."""

    async def one(info):
        env = client._envelope(payload, new_msg_id(), info.server_id)
        try:
            return (await client.pool.send_and_receive(info, env, SDK_TIMEOUT_S)).payload
        except Exception:
            return None

    return await asyncio.gather(*(one(i) for i in targets))


async def _collect_grants(client, key: str, value: bytes, seed: int):
    """Write1 to the key's whole replica set, as the SDK sends it; returns
    (transaction, targets, a quorum of timestamp-consistent MultiGrants)."""
    txn = Transaction((Operation(Action.WRITE, key, value),))
    targets = client.config.servers_for_key(key)
    await asyncio.gather(*(client._ensure_session(i.server_id, i) for i in targets))
    write1 = Write1ToServer(client.client_id, client._write1_transaction(txn), seed,
                            transaction_hash(txn))
    answers = await _send_to_all(client, targets, write1)
    oks = [p.multi_grant for p in answers if isinstance(p, Write1OkFromServer)]
    chosen = client._quorum_grant_subset(txn, oks)
    if chosen is not None:
        chosen = client._trim_to_quorum_cover(txn, chosen)
    return txn, targets, chosen


async def bad_write2(pc, rng: random.Random, key: str, kind: str) -> dict:
    """Send one bad Write2 for ``key`` to its whole replica set."""
    client = pc.client(timeout_s=SDK_TIMEOUT_S)
    before, _ = await sdk_read(client, key)
    value = b"BAD-WRITE2:" + rng.randbytes(32).hex().encode()
    report = {"kind": kind, "key": key, "sent": 0, "accepted": 0, "unchanged": False}
    chosen = None
    for _ in range(4):  # a seed collision with a grant still held is retried
        txn, targets, chosen = await _collect_grants(client, key, value, rng.randrange(1000))
        if chosen is not None:
            break
        await asyncio.sleep(0.5)
    if chosen is None:
        return report
    grants = list(chosen)
    if kind == "altered-signature":
        victim = rng.randrange(len(grants))
        sig = bytearray(grants[victim].signature)
        sig[rng.randrange(len(sig))] ^= 1 << rng.randrange(8)
        grants[victim] = grants[victim].with_signature(bytes(sig))
    elif kind == "under-quorum":
        del grants[rng.randrange(len(grants))]
    else:
        raise ValueError(kind)
    write2 = Write2ToServer(WriteCertificate({mg.server_id: mg for mg in grants}), txn)
    answers = await _send_to_all(client, targets, write2)
    report["sent"] = len(targets)
    report["accepted"] = sum(1 for p in answers if isinstance(p, Write2AnsFromServer))
    after, _ = await sdk_read(client, key)
    report["unchanged"] = after == before
    return report


async def bad_write2_probe(pc, seed: int, keys: list, count: int) -> list:
    rng = random.Random(f"bad-write2:{seed}")
    picks = rng.sample(keys, min(count, len(keys)))
    out = []
    for n, key in enumerate(picks):
        out.append(await bad_write2(pc, rng, key, BAD_WRITE2_KINDS[n % len(BAD_WRITE2_KINDS)]))
    return out


DIRECT_READ_KEYS = 64  # keys a frame of the direct read-back


async def direct_reads(pc, server_id: str, keys: dict) -> dict:
    """Read ``keys`` ({record: key}, all owned by ``server_id``) from THAT
    replica alone: one frame to one replica, as the SDK envelopes a read, no
    quorum behind the answer.  {record: (writer, seq, crc, grants), or None
    where the replica gave no record}."""
    import zlib

    client = pc.client(timeout_s=SDK_TIMEOUT_S)
    info = client.config.servers[server_id]
    await client._ensure_session(server_id, info)
    out = {}
    records = sorted(keys)
    for i in range(0, len(records), DIRECT_READ_KEYS):
        batch = records[i:i + DIRECT_READ_KEYS]
        txn = Transaction(tuple(Operation(Action.READ, keys[r]) for r in batch))
        nonce = new_msg_id()
        (answer,) = await _send_to_all(client, [info], ReadToServer(client.client_id, txn, nonce))
        results = (answer.result.operations
                   if isinstance(answer, ReadFromServer) and answer.nonce == nonce else ())
        for k, rec in enumerate(batch):
            res = results[k] if k < len(results) else None
            if res is None or res.status != Status.OK or not res.existed or res.value is None:
                out[rec] = None
                continue
            value = bytes(res.value)
            writer, seq = parse_tag(value) or (-1, -1)
            cert = res.current_certificate
            out[rec] = (writer, seq, zlib.crc32(value), len(cert.grants) if cert is not None else 0)
    return out


WARM_STEP = 1.25  # sizes of the warm-up batches grow by this factor


def _no_fallback_verifier(pc):
    from mochi_tpu.verifier.service import RemoteVerifier

    class NoFallback:
        async def verify_batch(self, items):
            raise RuntimeError("verifier RPC failed (the probe has no local fallback)")

        async def close(self):
            pass

    return RemoteVerifier("127.0.0.1", pc.service_port, timeout_s=300.0, fallback=NoFallback())


async def _verdict_mismatches(rv, signers, label: str, size: int) -> int:
    """One batch of ``size`` seeded items, every fourth forged (a valid
    signature over other bytes); how many verdicts differ from the validity
    each item was made with."""
    from mochi_tpu.verifier.spi import VerifyItem

    items, expect = [], []
    for i in range(size):
        kp = signers[i % len(signers)]
        msg = f"perf-probe:{label}:{i}".encode()
        forged = i % 4 == 0
        items.append(VerifyItem(kp.public_key, msg + b"!" if forged else msg, kp.sign(msg)))
        expect.append(not forged)
    got = await rv.verify_batch(items)
    return sum(1 for a, b in zip(got, expect) if bool(a) != b) + abs(len(got) - len(expect))


async def warm_device_buckets(pc, seed: int, lowest: int, highest: int) -> dict:
    """Set-up: offer the service one batch of each size it can send to the
    device, ``lowest`` (the routing's crossover) to ``highest`` (its largest
    ready bucket), so that whatever program the product builds lazily for such
    a batch is built before the window and not inside it."""
    sizes, size = [], float(lowest)
    while size < highest:
        sizes.append(int(size))
        size *= WARM_STEP
    sizes.append(highest)
    rv = _no_fallback_verifier(pc)
    mismatches = 0
    try:
        for n in sizes:
            mismatches += await _verdict_mismatches(
                rv, list(pc.keypairs.values()), f"warm:{seed}:{n}", n)
    finally:
        await rv.close()
    return {"sizes": sizes, "mismatches": mismatches}


async def device_probe(pc, seed: int, size: int) -> dict:
    """Two batches of ``size`` items through the verifier RPC: one signed by
    the replica identities (registered: the comb program) and one by keys the
    service has never seen (the ladder program)."""
    from mochi_tpu.crypto.keys import keypair_from_seed

    strangers = [
        keypair_from_seed(hashlib.sha256(f"perf-stranger:{seed}:{k}".encode()).digest())
        for k in range(8)
    ]
    report = {"items": 0, "mismatches": 0, "batches": {}}
    rv = _no_fallback_verifier(pc)
    try:
        for label, signers in (("comb", list(pc.keypairs.values())), ("ladder", strangers)):
            wrong = await _verdict_mismatches(rv, signers, f"{seed}:{label}", size)
            report["batches"][label] = {"items": size, "mismatches": wrong}
            report["items"] += size
            report["mismatches"] += wrong
    finally:
        await rv.close()
    return report
