"""Paged storage engine (round 17, ``mochi_tpu/storage/paged.py``): engine
selection through the SPI, restart -> page-index rebuild -> on-demand
fault-in under a cache cap far below the data set, per-entry tamper
conviction on self-certifying pages, incremental compaction, and the
cross-process SIGKILL -> restart -> zero-acked-write-loss contract on the
paged engine.

The tamper tests mirror the WAL Byzantine-restart story one layer down: an
adversary who rewrites a page recomputes every CRC and the footer's
transaction hash trivially, so framing is NOT the integrity argument — the
per-entry recheck pins the entry's grants to the transaction they actually
signed, and grant signatures re-verify in batch at audit/compaction (the
DSig posture).  Each tampered entry is convicted with key attribution and
never served; the honest value still answers from the replica quorum.
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import shutil
import tempfile
import zlib

from mochi_tpu.client.txn import TransactionBuilder
from mochi_tpu.protocol import Transaction, transaction_hash
from mochi_tpu.protocol.codec import encode
from mochi_tpu.storage import PagedStorage
from mochi_tpu.storage.durable import DurableStorage
from mochi_tpu.storage.paged import (
    _write_page,
    page_name,
    read_page_entry,
    scan_page_footer,
)
from mochi_tpu.storage.spi import build_storage
from mochi_tpu.testing.invariants import InvariantChecker
from mochi_tpu.testing.process_cluster import ProcessCluster
from mochi_tpu.testing.virtual_cluster import VirtualCluster


@contextlib.contextmanager
def _paged_env(cache_bytes: int = 2048, memtable_bytes: int = 4096):
    """Pin tiny caps for the duration of a test (the engine reads them at
    construction, i.e. at every boot/restart inside the block)."""
    keys = {
        "MOCHI_PAGE_CACHE_BYTES": str(cache_bytes),
        "MOCHI_MEMTABLE_BYTES": str(memtable_bytes),
    }
    saved = {k: os.environ.get(k) for k in keys}
    os.environ.update(keys)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


async def _populated(td: str, n: int = 12):
    vc = VirtualCluster(4, rf=4, storage_dir=td, storage_engine="paged")
    await vc.start()
    client = vc.client()
    for i in range(n):
        await client.execute_write_transaction(
            TransactionBuilder().write(f"pk{i}", b"v%d" % i).build()
        )
    return vc, client


async def _flush_to_pages(replica) -> None:
    """Force the memtable out: every committed key lands in a page and the
    WAL truncates behind the manifest watermark."""
    await replica.storage.flush()
    await replica.storage.snapshot(replica.store)


def _freeze_storage(td: str, server_id: str) -> str:
    src = os.path.join(td, server_id)
    dst = src + ".crash"
    # The replica is live: its group tick may snapshot and truncate the log
    # under the copy.  A ".snap-*" file is an atomic write still in flight
    # (renamed away any moment, read by no boot); a segment that vanished
    # mid-copy means the image is of no one instant, so it is taken again.
    for attempt in range(5):
        try:
            shutil.copytree(src, dst, ignore=shutil.ignore_patterns(".snap-*"))
            break
        except shutil.Error:
            shutil.rmtree(dst)
            if attempt == 4:
                raise
    return dst


def _restore_storage(td: str, server_id: str, frozen: str) -> None:
    dst = os.path.join(td, server_id)
    shutil.rmtree(dst)
    shutil.move(frozen, dst)


def _rewrite_page_with(directory: str, server_id: str, mutate) -> str:
    """Adversarial page rewrite: pick a page holding a data key, decode its
    entries, apply ``mutate(key, entry_obj) -> bool`` to each decoded
    ``[key, txn_obj, cert_obj, epoch]`` until one reports mutation, then
    rewrite the page with every CRC and the footer transaction hash
    RECOMPUTED (an adversary recomputes them trivially).  Returns the
    mutated key."""
    tampered = None
    for name in sorted(os.listdir(directory)):
        if not name.startswith("page-") or not name.endswith(".pg"):
            continue
        path = os.path.join(directory, name)
        page_id, rows, _size = scan_page_footer(path, server_id)
        entries = []
        for key, off, length, crc, _txh, epoch in rows:
            obj = read_page_entry(path, off, length, crc)
            if tampered is None and mutate(key, obj):
                tampered = key
            blob = encode(obj)
            txh = transaction_hash(Transaction.from_obj(obj[1]))
            entries.append((key, blob, zlib.crc32(blob), txh, int(epoch)))
        if tampered is not None:
            _write_page(path, server_id, page_id, entries)
            return tampered
    raise AssertionError("no data page found to tamper with")


# ------------------------------------------------------- engine selection


def test_engine_selection_param_env_and_rejection(tmp_path):
    s = build_storage(str(tmp_path / "a"), "server-0")
    assert isinstance(s, DurableStorage) and not isinstance(s, PagedStorage)
    assert s.name == "durable" and s.pager is False

    p = build_storage(str(tmp_path / "b"), "server-0", engine="paged")
    assert isinstance(p, PagedStorage)
    assert p.name == "paged" and p.pager is True

    saved = os.environ.get("MOCHI_STORAGE_ENGINE")
    os.environ["MOCHI_STORAGE_ENGINE"] = "paged"
    try:
        q = build_storage(str(tmp_path / "c"), "server-0")
        assert isinstance(q, PagedStorage)
        # an explicit param beats the environment
        w = build_storage(str(tmp_path / "d"), "server-0", engine="wal")
        assert not isinstance(w, PagedStorage)
    finally:
        if saved is None:
            os.environ.pop("MOCHI_STORAGE_ENGINE", None)
        else:
            os.environ["MOCHI_STORAGE_ENGINE"] = saved

    try:
        build_storage(str(tmp_path / "e"), "server-0", engine="lsm9000")
    except ValueError as exc:
        assert "lsm9000" in str(exc)
    else:
        raise AssertionError("unknown engine accepted silently")


# ------------------------------------- restart -> fault-in under a tiny cap


def test_paged_recover_faults_in_under_tiny_cache():
    """Restart from pages with a cache cap far below the value bytes: the
    boot rebuilds only the index (no values), every read faults its page
    entry in through the verified sink, the CLOCK keeps residency at the
    cap, and nothing is convicted."""

    async def body(td):
        vc, _client = await _populated(td, n=24)
        try:
            victim = vc.replica("server-1")
            await _flush_to_pages(victim)
            fresh = await vc.restart_replica("server-1")
            report = fresh.storage.replay_report()
            assert report["convicted"] == 0, report
            st = fresh.storage.stats()
            assert st["pages"]["count"] >= 1, st
            for i in range(24):
                sv = fresh.store._get(f"pk{i}")
                assert sv is not None and sv.value == b"v%d" % i, f"pk{i}"
            st = fresh.storage.stats()
            assert st["cache"]["misses"] >= 24, st
            # the cap bounds residency: 24 values cannot all stay resident
            assert st["cache"]["evictions"] > 0, st
            assert st["pages"]["convicted"] == 0, st
            checker = InvariantChecker([fresh])
            checker.check_now()
            rep = checker.report()
            assert rep["ok"], rep["violations"]
        finally:
            await vc.close()

    with _paged_env(cache_bytes=512, memtable_bytes=2048):
        with tempfile.TemporaryDirectory() as td:
            asyncio.run(asyncio.wait_for(body(td), timeout=120))


# --------------------------------------------------- Byzantine page tamper


def test_tampered_page_value_convicted_and_quorum_serves_honest():
    """The round-17 pin: one page entry's committed value mutated on disk
    with ALL integrity frames recomputed (entry CRC, footer row, footer
    transaction hash).  Framing accepts the page at boot — but the entry's
    grants signed the ORIGINAL transaction hash, so the first fault-in (or
    the boot audit, whichever wins the race) refuses it, convicts with
    per-entry attribution, and the tampered value is never served.  The
    honest value still answers from the replica quorum."""

    async def body(td):
        vc, client = await _populated(td)
        try:
            victim = vc.replica("server-1")
            await _flush_to_pages(victim)
            frozen = _freeze_storage(td, "server-1")

            def mutate(key, obj) -> bool:
                if not key.startswith("pk"):
                    return False
                for op in obj[1]:  # txn obj: op list; op: [action, key, value]
                    if op[1] == key and op[2] is not None:
                        op[2] = b"EVIL"
                        return True
                return False

            tampered = _rewrite_page_with(frozen, "server-1", mutate)

            fresh = await vc.restart_replica(
                "server-1",
                before_boot=lambda sid: _restore_storage(td, sid, frozen),
            )
            # first touch faults the tampered entry in -> per-entry recheck
            sv = fresh.store._get(tampered)
            assert sv is None or sv.value != b"EVIL", sv
            report = fresh.storage.replay_report()
            assert report["convicted"] >= 1, report
            assert any(
                c["key"] == tampered for c in report["convictions"]
            ), report
            assert any(
                "rejected" in c["reason"] for c in report["convictions"]
            ), report
            st = fresh.storage.stats()
            assert st["pages"]["convicted"] >= 1, st
            # invariant 5 surfaces the conviction as evidence, not violation
            checker = InvariantChecker([fresh])
            checker.check_now()
            rep = checker.report()
            assert rep["storage_replay_convictions"] >= 1, rep
            assert rep["ok"], rep["violations"]
            # the three honest replicas still answer with the real value
            idx = int(tampered[len("pk"):])
            res = await client.execute_read_transaction(
                TransactionBuilder().read(tampered).build()
            )
            assert res.operations[0].value == b"v%d" % idx
        finally:
            await vc.close()

    with _paged_env():
        with tempfile.TemporaryDirectory() as td:
            asyncio.run(asyncio.wait_for(body(td), timeout=120))


def test_forged_grant_signature_in_page_convicted_by_audit():
    """DSig posture, adversarial leg: a page entry's grant signatures
    zeroed (transaction untouched, so every hash agreement PASSES — the
    fault-in recheck alone cannot see this).  The batch signature sweep
    (boot audit) is exactly the layer that must catch it."""

    async def body(td):
        vc, _client = await _populated(td)
        try:
            victim = vc.replica("server-1")
            await _flush_to_pages(victim)
            frozen = _freeze_storage(td, "server-1")

            def mutate(key, obj) -> bool:
                if not key.startswith("pk"):
                    return False
                for mg_obj in obj[2].values():  # cert obj: {sid: mg_obj}
                    mg_obj[3] = b"\x00" * 64  # MultiGrant signature slot
                return True

            tampered = _rewrite_page_with(frozen, "server-1", mutate)

            fresh = await vc.restart_replica(
                "server-1",
                before_boot=lambda sid: _restore_storage(td, sid, frozen),
            )
            audit = await fresh.storage.audit(fresh.store)
            assert audit["convicted"] >= 1, audit
            report = fresh.storage.replay_report()
            assert any(
                c["key"] == tampered and "signature" in c["reason"]
                for c in report["convictions"]
            ), report
            sv = fresh.store._get(tampered)
            assert sv is None or sv.grants == {}, sv
        finally:
            await vc.close()

    with _paged_env():
        with tempfile.TemporaryDirectory() as td:
            asyncio.run(asyncio.wait_for(body(td), timeout=120))


# ------------------------------------------------------------- compaction


def test_compaction_drops_superseded_and_reverifies():
    """Two generations of the same keys -> two pages, the older one fully
    dead.  Incremental compaction merges the victims into one page, drops
    the superseded versions, re-verifies every surviving entry's grant
    signatures, and every value still reads back."""

    async def body(td):
        vc, client = await _populated(td, n=10)
        try:
            victim = vc.replica("server-1")
            # No snapshot may arm the background compaction (debt ratio 0.5):
            # the group tick takes snapshots of its own, and a compact() it
            # starts inside this test's awaits leaves this one nothing to
            # rewrite, or two compactions of the same victims.
            victim.storage.compact_debt_ratio = float("inf")
            await _flush_to_pages(victim)
            for i in range(10):
                await client.execute_write_transaction(
                    TransactionBuilder().write(f"pk{i}", b"w%d" % i).build()
                )
            await _flush_to_pages(victim)
            st0 = victim.storage.stats()
            assert st0["pages"]["count"] >= 2, st0
            assert st0["compaction"]["debt"] > 0, st0

            done = await victim.storage.compact()
            assert done["rewritten"] >= 1, done
            st1 = victim.storage.stats()
            assert st1["pages"]["count"] < st0["pages"]["count"], (st0, st1)
            assert st1["compaction"]["runs"] >= 1, st1
            assert st1["compaction"]["reverified"] >= 10, st1
            assert st1["compaction"]["debt"] == 0, st1
            assert st1["pages"]["convicted"] == 0, st1

            # restart on the compacted image: everything replays clean
            fresh = await vc.restart_replica("server-1")
            assert fresh.storage.replay_report()["convicted"] == 0
            for i in range(10):
                sv = fresh.store._get(f"pk{i}")
                assert sv is not None and sv.value == b"w%d" % i, f"pk{i}"
        finally:
            await vc.close()

    with _paged_env():
        with tempfile.TemporaryDirectory() as td:
            asyncio.run(asyncio.wait_for(body(td), timeout=120))


# --------------------------------------- cross-process SIGKILL -> recover


def test_paged_sigkill_full_cluster_zero_acked_write_loss():
    """The acceptance pin on the paged engine: ProcessCluster under live
    load, EVERY replica SIGKILLed mid-stream, all four restarted from
    pages + WAL tail, and every acknowledged write must read back."""

    async def body():
        async with ProcessCluster(
            4,
            rf=4,
            n_processes=4,
            storage_dir=True,
            wal_fsync="group",
            storage_engine="paged",
        ) as pc:
            client = pc.client(timeout_s=8.0)
            acked = {}

            async def load():
                i = 0
                while True:
                    key, value = f"gk{i}", b"v%d" % i
                    try:
                        await client.execute_write_transaction(
                            TransactionBuilder().write(key, value).build()
                        )
                    except Exception:
                        return  # in-flight at the kill: indeterminate
                    acked[key] = value
                    i += 1

            writer = asyncio.ensure_future(load())
            while len(acked) < 10:
                await asyncio.sleep(0.02)
            for i in range(4):
                pc.kill_replica(f"server-{i}")
            await writer
            await client.close()

            for i in range(4):
                await pc.restart_replica(f"server-{i}")
            reader = pc.client(timeout_s=8.0)
            lost = []
            for key, value in sorted(acked.items()):
                res = await reader.execute_read_transaction(
                    TransactionBuilder().read(key).build()
                )
                if res.operations[0].value != value:
                    lost.append(key)
            assert not lost, f"{len(lost)} acked writes lost: {lost[:5]}"
            pc.check_alive()

    asyncio.run(asyncio.wait_for(body(), timeout=240))
