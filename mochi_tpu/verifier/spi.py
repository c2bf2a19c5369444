"""The ``SignatureVerifier`` SPI — the north-star seam (BASELINE.json).

In the reference, message ingress goes straight from the dispatcher to the
datastore with zero cryptographic verification (``server/requesthandlers/*``,
SURVEY.md §2.4).  Here every replica routes signature checks through this SPI:

* :class:`CpuVerifier` — the default host path (OpenSSL via ``cryptography``),
  one verify per call, run inline.
* :class:`BatchingVerifier` — an async micro-batching front: concurrent
  requests' signatures accumulate in a queue that flushes to a pluggable
  batch backend either when ``max_batch`` is reached or after
  ``max_delay_s`` (bounding p50 commit latency at low load — SURVEY.md §7
  "batching discipline").  The TPU backend
  (:func:`mochi_tpu.crypto.batch_verify.verify_batch`) plugs in here; on
  backend failure it falls back to the CPU path rather than ever skipping
  verification.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
import time
from collections import OrderedDict, deque
from typing import Callable, Deque, List, NamedTuple, Optional, Sequence, Tuple

from ..crypto import keys as crypto_keys
from ..obs import hostspan
from ..utils.metrics import Metrics
from . import stages

LOG = logging.getLogger(__name__)


class VerifyItem(NamedTuple):
    """One Ed25519 verification: (public key, message, signature).  A named
    tuple: built, hashed and compared by value at C speed, so the tuple of a
    call's items is an exact memo key (:class:`CachingVerifier`)."""

    public_key: bytes  # 32 bytes
    message: bytes
    signature: bytes  # 64 bytes


def aggregate_key(items: Sequence[VerifyItem]) -> bytes:
    """Collision-resistant digest of an ORDERED verification set — the memo
    key for :meth:`SignatureVerifier.verify_aggregate`.  Length-prefixed so
    (pub, msg, sig) boundaries can't be shifted between items; callers that
    want cluster-wide memo hits (round 18: one attestation per write
    certificate) must build the item list deterministically (grant order =
    certificate order)."""
    h = hashlib.sha256(b"mochi.agg.v1\x00")
    for it in items:
        for part in (it.public_key, it.message, it.signature):
            h.update(len(part).to_bytes(4, "big"))
            h.update(part)
    return h.digest()


class SignatureVerifier:
    """SPI: verify a batch, returning a validity bitmap (one bool per item)."""

    async def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        raise NotImplementedError

    async def verify_aggregate(
        self, key: bytes, items: Sequence[VerifyItem]
    ) -> bool:
        """Verify an all-or-nothing attestation SET under one memo key.

        The round-18 certificate fast path: a write certificate's 2f+1
        grants are one logical artifact — every receiving replica needs the
        same yes/no, not 2f+1 independent verdicts.  ``key`` MUST be a
        collision-resistant digest of ``items`` (:func:`aggregate_key`), so
        the verdict is a pure function of the key and caching/memoizing it
        cluster-wide is sound.  Default: one batched ``verify_batch`` round
        trip (batched EdDSA beats pairing aggregation at committee sizes —
        arXiv 2302.00418); :class:`CachingVerifier` overrides with an
        aggregate memo that counts ONE unique check per certificate.
        A False verdict says only "not all valid" — callers that need
        attribution fall back to the per-item path.
        """
        if not items:
            return True
        return all(await self.verify_batch(items))

    async def close(self) -> None:
        pass

    def register_signers(self, pubs: Sequence[bytes]) -> bool:
        """Route known-signer registration (cluster replica identities) to
        every layer of this composition that can exploit it, and report
        whether any did.

        This is how the comb fast path becomes the DEFAULT engine rather
        than an opt-in: the replica calls this once at boot and on every
        reconfiguration with the cluster config's public keys, whatever
        verifier composition it was built with.  The default walks the
        standard composition attributes — ``inner`` (Caching/Coalescing
        wrappers), ``backend`` (BatchingVerifier → JaxBatchBackend, which
        owns the device :class:`~mochi_tpu.crypto.comb.SignerRegistry`) and
        ``fallback`` (the CPU path, whose pure-Python engine keeps per-
        signer window tables) — so registration reaches the device registry
        AND the host fallback through any stack.  Registration is always
        best-effort: an unreachable layer leaves that traffic on the
        general ladder, never unverified.
        """
        routed = False
        for attr in ("inner", "backend", "fallback"):
            target = getattr(self, attr, None)
            if target is None or target is self:
                continue
            reg = getattr(target, "register_signers", None)
            if callable(reg):
                try:
                    # None (e.g. JaxBatchBackend) means "registered"; only
                    # an explicit False ("nothing here uses signer hints",
                    # e.g. the OpenSSL CPU path) leaves `routed` unset.
                    routed = (reg(list(pubs)) is not False) or routed
                except Exception:
                    LOG.exception(
                        "signer registration via %s.%s failed; its traffic "
                        "stays on the general verify path",
                        type(self).__name__, attr,
                    )
        return routed


class CpuVerifier(SignatureVerifier):
    """Inline host verification (the reference-analog CPU path)."""

    async def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        # Deliberately inline on the loop: this IS the metered host path the
        # batching/remote verifiers fall back to, and shipping single-item
        # batches to an executor costs more than the ~120 us verify itself.
        return [
            crypto_keys.verify(it.public_key, it.message, it.signature)  # mochi-lint: disable=async-blocking
            for it in items
        ]

    def register_signers(self, pubs: Sequence[bytes]) -> bool:
        # With OpenSSL installed this is a no-op (per-verify cost is already
        # ~120 us), and likewise on hosts running the native-C engine (no
        # per-signer state); on toolchain-less wheel-less hosts it
        # pre-promotes the pure-Python engine's per-signer window tables
        # (the host analog of the device comb) so the FIRST certificate
        # check runs combed instead of paying two ~380-addition ladders to
        # earn promotion.
        return crypto_keys.register_known_signers(pubs)


class CoalescingVerifier(SignatureVerifier):
    """Coalesce concurrent ``verify_batch`` calls into shared inner calls.

    For verifiers whose per-call cost is dominated by a fixed round trip
    (``RemoteVerifier``: two loopback frames + service-side scheduling per
    call), N concurrent Write2 certificate checks in one replica otherwise
    pay N round trips for what one combined request answers.  Requests that
    arrive while a flush is in flight ride the NEXT flush together, so
    under load a replica ships one RPC per round trip instead of one per
    certificate.  There is no timer: a lone call flushes immediately; the
    only queueing is behind ``max_inflight`` already-overlapping round
    trips (same overlap discipline as :class:`BatchingVerifier`, whose
    sync-backend/thread-executor shape doesn't fit an async inner).
    """

    def __init__(
        self,
        inner: SignatureVerifier,
        max_batch: int = 16384,
        max_inflight: int = 4,
    ):
        self.inner = inner
        self.max_batch = max_batch
        self.max_inflight = max(1, max_inflight)
        self._pending: List[Tuple[VerifyItem, asyncio.Future]] = []
        self._flush_task: Optional[asyncio.Task] = None
        self._inflight: Optional[asyncio.Semaphore] = None
        self._chunk_tasks: set = set()
        self.calls = 0
        self.inner_calls = 0

    async def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        if not items:
            return []
        self.calls += 1
        loop = asyncio.get_running_loop()
        if self._inflight is None:
            self._inflight = asyncio.Semaphore(self.max_inflight)
        futures = [loop.create_future() for _ in items]
        self._pending.extend(zip(items, futures))
        if self._flush_task is None or self._flush_task.done():
            self._flush_task = loop.create_task(self._flush())
        return list(await asyncio.gather(*futures))

    async def _flush(self) -> None:
        assert self._inflight is not None
        while self._pending:
            # Acquire BEFORE popping so a cancellation here leaves items in
            # _pending for close() to cancel rather than hanging callers.
            await self._inflight.acquire()
            if not self._pending:
                self._inflight.release()
                break
            chunk = self._pending[: self.max_batch]
            del self._pending[: len(chunk)]
            task = asyncio.get_running_loop().create_task(self._run_chunk(chunk))
            self._chunk_tasks.add(task)
            task.add_done_callback(self._chunk_tasks.discard)

    async def _run_chunk(
        self, chunk: List[Tuple[VerifyItem, asyncio.Future]]
    ) -> None:
        try:
            items = [it for it, _ in chunk]
            try:
                self.inner_calls += 1
                bitmap = await self.inner.verify_batch(items)
                if len(bitmap) != len(items):
                    raise ValueError("inner bitmap length mismatch")
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                # Propagate to the callers of THIS chunk (same behavior as
                # calling the inner verifier bare); other chunks still run.
                for _, fut in chunk:
                    if not fut.done():
                        fut.set_exception(exc)
                return
            for (_, fut), ok in zip(chunk, bitmap):
                if not fut.done():
                    fut.set_result(bool(ok))
        finally:
            assert self._inflight is not None
            self._inflight.release()

    async def verify_aggregate(
        self, key: bytes, items: Sequence[VerifyItem]
    ) -> bool:
        # Route to the inner verifier so a wrapped CachingVerifier's
        # aggregate memo still answers in one entry; an aggregate is already
        # one round trip, so there is nothing here to coalesce.
        return await self.inner.verify_aggregate(key, items)

    async def close(self) -> None:
        if self._flush_task is not None and not self._flush_task.done():
            try:
                await self._flush_task
            except asyncio.CancelledError:
                # close() did NOT cancel the flusher (it drains it), so a
                # CancelledError here is close() itself being cancelled —
                # propagate, or a wait_for(close(), t) timeout would hang on
                # the gather below.
                raise
            except Exception:
                pass
        if self._chunk_tasks:
            await asyncio.gather(*list(self._chunk_tasks), return_exceptions=True)
        for _, fut in self._pending:
            if not fut.done():
                fut.cancel()
        self._pending.clear()
        await self.inner.close()


_NOBODY = (None, -1)  # no call owns the key


async def _owner_result(owner: asyncio.Future):
    """A single-flight waiter's wait: what the owner call resolved its future
    to, or None (the retry sentinel) where the future was cancelled under
    it.  ``Task.cancel`` cancels the future a task waits on, so one waiter's
    cancellation would otherwise reach every call parked on the same owner;
    a waiter that was itself cancelled still raises."""
    try:
        return await owner
    except asyncio.CancelledError:
        if not owner.cancelled() or asyncio.current_task().cancelling():
            raise
        return None


class CachingVerifier(SignatureVerifier):
    """Bounded memo over any verifier — verification is a pure function of
    (public key, message, signature), so caching is sound.

    Where it pays: the shared verifier service (``verifier/service.py``)
    sees the SAME MultiGrant from every replica of the set within
    milliseconds (each replica independently checks the certificate, as BFT
    requires) — one device/CPU verification serves all rf of them.  Negative
    results are cached too (a forged grant replayed across replicas costs
    one check, not rf).  Two layers, each with its single-flight table: a
    whole call (the tuple of its items: the other rf-1 replicas' request is
    one hash and one lookup) and, behind it, each item.

    Each memo is bounded by ``max_entries`` and drops its oldest INSERTION
    first: neither a hit nor writing a key that is already there renews it.
    The memos are ``OrderedDict``s for that one operation: the front of a
    plain ``dict`` is found by walking the dead slots every earlier eviction
    left (``next(iter(d))``: tens of thousands on a full memo), on the loop
    thread; ``popitem(last=False)`` unlinks it.
    """

    def __init__(
        self,
        inner: SignatureVerifier,
        max_entries: int = 1 << 16,
        metrics: Optional[Metrics] = None,
    ):
        self.inner = inner
        self.max_entries = max_entries
        # stage timers (verifier/stages.py); the service hands in its own
        self.metrics = metrics if metrics is not None else Metrics()
        self._cache: "OrderedDict[Tuple[bytes, bytes, bytes], bool]" = OrderedDict()
        # single-flight: key -> (the owner call's ONE future, the key's index
        # in the verdicts it resolves to) for a verification already
        # dispatched but not yet answered.  All rf replicas of a set check the
        # same certificate within one batching window, so without this the
        # duplicates race past the cache (observed: 0 service cache hits
        # under concurrent cluster load) and each costs a real verification.
        # A waiting call parks on one future per distinct owner (for a
        # certificate: one), not on one per key.
        self._inflight: "dict[Tuple[bytes, bytes, bytes], Tuple[asyncio.Future, int]]" = {}
        # Whole-call memo and single-flight, in front of the per-item ones:
        # tuple(items) -> the call's bitmap / the future of the call that is
        # verifying it.  All rf replicas of a set send the SAME item list, so
        # the rf-1 that are not first cost one tuple hash and one lookup
        # instead of one key and one lookup per item.  The key is the items
        # themselves (the dict compares by value on a hit), no digest; bounded
        # by items held, like the per-item cache.
        self._calls: "OrderedDict[Tuple[VerifyItem, ...], Tuple[bool, ...]]" = OrderedDict()
        self._calls_items = 0
        self._calls_inflight: "dict[Tuple[VerifyItem, ...], asyncio.Future]" = {}
        self.hits = 0
        self.misses = 0
        # verdicts the per-item and aggregate memos dropped to stay within
        # max_entries (the two that `misses` counts: misses less this is what
        # they hold; the whole-call memo holds copies)
        self.memo_evictions = 0
        # Aggregate memo (round 18): cert-hash -> all-valid verdict.  Kept
        # SEPARATE from the per-item cache so one certificate counts as ONE
        # unique check in the hits/misses meter regardless of quorum size —
        # that ratio IS the live verifies/txn meter.
        self._agg: "OrderedDict[bytes, bool]" = OrderedDict()
        self._agg_inflight: "dict[bytes, asyncio.Future]" = {}
        self.agg_hits = 0
        self.agg_misses = 0

    async def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        """``items`` may also be bare (public key, message, signature) triples,
        as a verify request carries them: they equal the VerifyItems they would
        make, so they find the same memo entries, and only an item that has to
        go to the inner verifier is made into one."""
        call = tuple(items)
        plan = None
        # the synchronous stretch before the first await: one tick per call
        with stages.stage(
            self.metrics, stages.MEMO_LOOKUP, stages.SPAN_MEMO, items=len(call)
        ):
            answered = self._calls.get(call)
            asked = None if answered is not None else self._calls_inflight.get(call)
            if asked is not None and asked.cancelled():
                asked = None  # cancelled under its owner: this call verifies for itself
            if answered is None and asked is None:
                plan = self._plan(items)
        self.metrics.mark(stages.MEMO_ITEMS, len(call))
        if answered is not None:
            self.hits += len(call)
            return list(answered)
        if asked is not None:
            self.hits += len(call)
            verdicts = await _owner_result(asked)
            if verdicts is None:  # that call failed: verify ourselves (re-enters)
                return await self.verify_batch(items)
            return list(verdicts)
        out, waiting, new_keys, reps = plan
        if not new_keys and not waiting:
            self._remember(call, out)  # every item was a hit: nothing to wait for
            return out
        mine = asyncio.get_running_loop().create_future()
        self._calls_inflight[call] = mine
        verdicts = None
        try:
            await self._settle(items, out, waiting, new_keys, reps)
            self._remember(call, out)
            verdicts = out
        finally:
            # None on the way out of a failure or a cancellation: the retry
            # sentinel, as for the per-item future in _settle (a call parked
            # on this one must not inherit either)
            if self._calls_inflight.get(call) is mine:
                del self._calls_inflight[call]
            if not mine.done():
                mine.set_result(verdicts)
        return list(out)

    def _remember(self, call: Tuple[VerifyItem, ...], out: List[bool]) -> None:
        if call not in self._calls:
            self._calls_items += len(call)
        self._calls[call] = tuple(out)
        while self._calls_items > self.max_entries and self._calls:
            oldest, _ = self._calls.popitem(last=False)
            self._calls_items -= len(oldest)

    def _plan(self, items: Sequence[VerifyItem]):
        """The per-item lookup (synchronous): verdicts already known, the
        items other calls are verifying, and the keys this call must."""
        out: List[Optional[bool]] = [None] * len(items)
        # owner call's future -> [(index here, index in the owner's verdicts)]
        waiting: "dict[asyncio.Future, List[Tuple[int, int]]]" = {}
        new_keys: "dict[Tuple[bytes, bytes, bytes], List[int]]" = {}
        reps: List[VerifyItem] = []
        for i, it in enumerate(items):
            pk, msg, sig = it  # a VerifyItem, or the bare triple it is made from
            k = (bytes(pk), bytes(msg), bytes(sig))
            cached = self._cache.get(k)
            if cached is not None:
                out[i] = cached
                self.hits += 1
                continue
            owner, j = self._inflight.get(k, _NOBODY)
            # (a future cancelled under its owner answers nobody: this
            # call verifies the key itself)
            if owner is not None and not owner.cancelled():
                waiting.setdefault(owner, []).append((i, j))
                self.hits += 1
            elif k in new_keys:
                new_keys[k].append(i)
                self.hits += 1
            else:
                new_keys[k] = [i]
                reps.append(it if type(it) is VerifyItem else VerifyItem._make(it))
                self.misses += 1
        return out, waiting, new_keys, reps

    async def _settle(self, items, out, waiting, new_keys, reps) -> None:
        """Fill ``out``: verify ``reps`` (this call's new keys) on the inner
        verifier, then collect what other calls were verifying."""
        if new_keys:
            mine = asyncio.get_running_loop().create_future()
            for j, k in enumerate(new_keys):
                self._inflight[k] = (mine, j)
            try:
                bitmap = await self.inner.verify_batch(reps)
                if len(bitmap) != len(reps):
                    # A short/long bitmap would silently truncate the zip
                    # below, leaving the tail keys' verdicts unset.  Route
                    # through the same cleanup path as a dispatch failure.
                    raise RuntimeError(
                        f"inner verifier returned {len(bitmap)} verdicts "
                        f"for {len(reps)} items"
                    )
            except BaseException:
                # Dispatch failed (or owner cancelled): resolve the future
                # with a retry sentinel rather than an exception — a
                # concurrent waiter must not inherit THIS caller's failure
                # (it would have verified independently before single-flight
                # existed), and a sentinel can't trigger "exception never
                # retrieved" warnings when nobody is waiting.
                self._release(new_keys, mine)
                if not mine.done():
                    mine.set_result(None)
                raise
            # the synchronous stretch after the answer: one tick per call
            with stages.stage(
                self.metrics, stages.MEMO_SETTLE, stages.SPAN_MEMO_SETTLE, items=len(new_keys)
            ):
                verdicts = [bool(ok) for ok in bitmap]
                for (k, idxs), ok in zip(new_keys.items(), verdicts):
                    for i in idxs:
                        out[i] = ok
                    if len(self._cache) >= self.max_entries:
                        self._cache.popitem(last=False)
                        self.memo_evictions += 1
                    self._cache[k] = ok
                self._release(new_keys, mine)
                if not mine.done():
                    mine.set_result(verdicts)
        for owner, pairs in waiting.items():
            verdicts = await _owner_result(owner)
            if verdicts is None:
                # the dispatching caller failed before producing a verdict —
                # verify its items ourselves (re-enters cache/single-flight)
                verdicts = await self.verify_batch([items[i] for i, _ in pairs])
                pairs = [(i, j) for j, (i, _) in enumerate(pairs)]
            for i, j in pairs:
                out[i] = verdicts[j]

    def _release(self, keys, mine: asyncio.Future) -> None:
        """The owner call takes its keys out of the single-flight table: only
        the entries that are still its own (a call that found ``mine``
        cancelled has since made itself the owner of that key)."""
        for k in keys:
            if self._inflight.get(k, _NOBODY)[0] is mine:
                del self._inflight[k]

    async def verify_aggregate(
        self, key: bytes, items: Sequence[VerifyItem]
    ) -> bool:
        """One memo entry — and ONE hits/misses tick — per attestation set.

        The miss path dispatches straight to ``self.inner.verify_batch``
        (bypassing the per-item cache) so the 2f+1 constituent checks don't
        ALSO land in the per-item meter: with the fast path on, a write
        certificate is one unique check cluster-wide, which is exactly the
        claim the live meter must be able to falsify.  Single-flight the
        same way as items: all rf replicas of a set ask about the same
        certificate within one batching window.
        """
        if not items:
            return True
        with stages.stage(
            self.metrics, stages.MEMO_LOOKUP, stages.SPAN_MEMO, items=len(items)
        ):
            key = bytes(key)
            cached = self._agg.get(key)
            fut = None if cached is not None else self._agg_inflight.get(key)
            if fut is not None and fut.cancelled():
                fut = None  # cancelled under its owner: verify here (see verify_batch)
        self.metrics.mark(stages.MEMO_ITEMS, len(items))
        if cached is not None:
            self.agg_hits += 1
            self.hits += 1
            return cached
        if fut is not None:
            self.agg_hits += 1
            self.hits += 1
            ok = await _owner_result(fut)
            if ok is None:  # dispatcher failed: verify ourselves (re-enters)
                return await self.verify_aggregate(key, items)
            return bool(ok)
        self.agg_misses += 1
        self.misses += 1
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._agg_inflight[key] = fut
        try:
            bitmap = await self.inner.verify_batch(items)
            if len(bitmap) != len(items):
                raise RuntimeError(
                    f"inner verifier returned {len(bitmap)} verdicts "
                    f"for {len(items)} items"
                )
        except BaseException:
            # same retry-sentinel contract as verify_batch's failure path
            # (single-flight owner: only the caller that registered the
            # future ever pops it)
            if self._agg_inflight.get(key) is fut:
                del self._agg_inflight[key]
            if not fut.done():
                fut.set_result(None)
            raise
        with stages.stage(self.metrics, stages.MEMO_SETTLE, stages.SPAN_MEMO_SETTLE, items=1):
            verdict = all(bool(b) for b in bitmap)
            if len(self._agg) >= self.max_entries:
                self._agg.popitem(last=False)
                self.memo_evictions += 1
            self._agg[key] = verdict
            if self._agg_inflight.get(key) is fut:
                del self._agg_inflight[key]
            if not fut.done():
                fut.set_result(verdict)
        return verdict

    async def close(self) -> None:
        await self.inner.close()


BatchBackend = Callable[[Sequence[VerifyItem]], Sequence[bool]]


class _Call:
    """One ``verify_batch`` call inside :class:`BatchingVerifier`: its items,
    the ONE future its caller awaits, and the bitmap its chunks fill in."""

    __slots__ = ("items", "future", "out", "left", "enqueued")

    def __init__(self, items: Sequence[VerifyItem], future: asyncio.Future, enqueued: float):
        self.items = items
        self.future = future
        self.out: List[bool] = [False] * len(items)
        self.left = len(items)  # items no chunk has answered yet
        self.enqueued = enqueued


# (call, first item, one past the last): the part of a call that rides one chunk
_Segment = Tuple[_Call, int, int]


class BatchingVerifier(SignatureVerifier):
    """Micro-batching front for a (possibly device-backed) batch backend.

    A call enqueues its items and awaits ONE future for its whole bitmap.
    Work changes hands by callback and by call, never by task: the enqueuing
    call arms one loop timer (``max_delay_s``, the longest a lone item waits
    for co-batching; ``call_soon`` when that is 0 or ``max_batch`` items
    wait), the timer's callback cuts ``max_batch``-sized chunks off the queue
    while an in-flight slot is free and hands each straight to the loop's
    thread executor, so the event loop keeps serving traffic while the
    device crunches.  The executor thread hands a finished chunk back with
    ``call_soon_threadsafe``; that callback writes each call's slice of the
    bitmap, resolves the calls whose last slice it was (in item order,
    whichever chunk finishes first), frees the slot and takes the next chunk.
    Up to ``max_inflight`` chunks run concurrently: JAX dispatch is async, so
    in-flight batches overlap the host->device round trip with device
    execution; the loop
    thread does all the counting, so the cap needs no semaphore.  A backend
    exception re-verifies the chunk on the CPU fallback (a coroutine, so
    that path alone spends a task) — never skipped, and counted in
    ``fallback_batches`` so a device path that quietly stopped carrying
    traffic shows in ``verifier_stats``.
    """

    def __init__(
        self,
        backend: BatchBackend,
        max_batch: int = 8192,
        max_delay_s: float = 0.002,
        fallback: Optional[SignatureVerifier] = None,
        max_inflight: int = 4,
        metrics: Optional[Metrics] = None,
    ):
        self.backend = backend
        # stage timers (verifier/stages.py): the backend's registry when it
        # keeps one, so a composition ticks in one place
        if metrics is None:
            metrics = getattr(backend, "metrics", None)
        self.metrics = metrics if metrics is not None else Metrics()
        self._flush_items = self.metrics.histogram(
            stages.FLUSH_ITEMS, stages.FLUSH_ITEMS_BOUNDS
        )
        self._calls_per_flush = self.metrics.histogram(
            stages.CALLS_PER_FLUSH, stages.CALLS_PER_FLUSH_BOUNDS
        )
        self.max_batch = max_batch
        self.max_delay_s = max_delay_s
        self.max_inflight = max(1, max_inflight)
        self.fallback = fallback if fallback is not None else CpuVerifier()
        # All of the below is touched on the loop thread only.
        # calls with items no chunk has taken yet, oldest first; of the first
        # one, items [:_head] are already in a chunk
        self._pending: Deque[_Call] = deque()
        self._head = 0
        self._queued = 0  # items in _pending past _head
        self._running = 0  # chunks at the backend or on the fallback: <= max_inflight
        self._timer: Optional[asyncio.Handle] = None  # the one armed _flush_due
        self._soon = False  # ... armed with call_soon, not the linger
        # the linger is over and what is queued waits only for a slot
        self._backlog = False
        self._fallbacks: set = set()  # the tasks of chunks on the fallback
        self._drained: Optional[asyncio.Future] = None  # close() waiting for _running == 0
        self._closed = False
        # simple counters for observability (see mochi_tpu.utils.metrics)
        self.batches_flushed = 0
        self.fallback_batches = 0

    async def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        if self._closed:
            raise RuntimeError("verifier closed")
        if not items:
            return []
        loop = asyncio.get_running_loop()
        # one future and one clock reading per call, not per item
        call = _Call(items, loop.create_future(), time.perf_counter())
        self._pending.append(call)
        self._queued += len(items)
        if self._backlog:
            pass  # a finishing chunk takes it: every slot is busy
        elif self._queued >= self.max_batch or self.max_delay_s <= 0:
            if not self._soon:
                if self._timer is not None:
                    self._timer.cancel()
                self._timer = loop.call_soon(self._flush_due)
                self._soon = True
        elif self._timer is None:
            # Micro-batching window: let concurrent requests pile on.
            self._timer = loop.call_later(self.max_delay_s, self._flush_due)
        return await call.future

    def _flush_due(self) -> None:
        self._timer = None
        self._soon = False
        self._take()

    def _take(self) -> None:
        """Loop thread: chunks off the queue into the executor while a slot
        is free.  The only place ``_running`` grows."""
        loop = asyncio.get_running_loop()
        while self._queued and self._running < self.max_inflight:
            segments: List[_Segment] = []
            items: List[VerifyItem] = []
            room = min(self.max_batch, self._queued)
            oldest = self._pending[0].enqueued  # calls enqueue in order: the first waited longest
            while room:
                call = self._pending[0]
                lo = self._head
                hi = min(len(call.items), lo + room)
                segments.append((call, lo, hi))
                items.extend(call.items[lo:hi])
                room -= hi - lo
                if hi == len(call.items):
                    self._pending.popleft()
                    self._head = 0
                else:
                    self._head = hi
            self._queued -= len(items)
            self._running += 1
            loop.run_in_executor(None, self._flush_chunk, loop, segments, items, oldest)
        self._backlog = self._queued > 0

    def _flush_chunk(
        self, loop: asyncio.AbstractEventLoop, segments: List[_Segment],
        items: List[VerifyItem], oldest: float,
    ) -> None:
        # on the executor thread: the linger, the wait for a slot and the
        # hand-off are all behind this chunk now
        waited = time.perf_counter() - oldest
        bitmap, failure = None, None
        try:
            with hostspan.span(
                stages.SPAN_CHUNK, items=len(items), wait_us=int(waited * 1e6)
            ):
                bitmap = list(self.backend(items))
            if len(bitmap) != len(items):
                raise ValueError("backend bitmap length mismatch")
        except Exception as exc:
            bitmap, failure = None, exc
        # straight onto the loop, not through the executor future's state
        # copy and done-callback: each of those is a turn of the loop
        loop.call_soon_threadsafe(
            self._chunk_done, segments, items, waited, bitmap, failure, time.perf_counter()
        )

    def _chunk_done(
        self, segments: List[_Segment], items: List[VerifyItem], waited: float,
        bitmap: Optional[List[bool]], failure: Optional[Exception], returned: float,
    ) -> None:
        self.metrics.timers[stages.QUEUE_WAIT].record(waited)
        if failure is None:
            self._resolve(segments, bitmap, None, returned)
            return
        LOG.error("batch backend failed; falling back to CPU verify", exc_info=failure)
        self.fallback_batches += 1
        # the fallback is a coroutine and a callback cannot await
        task = asyncio.get_running_loop().create_task(
            self._fall_back(segments, items, returned)
        )
        self._fallbacks.add(task)
        task.add_done_callback(self._fallbacks.discard)

    async def _fall_back(
        self, segments: List[_Segment], items: List[VerifyItem], returned: float
    ) -> None:
        try:
            bitmap = await self.fallback.verify_batch(items)
            if len(bitmap) != len(items):
                raise ValueError("fallback bitmap length mismatch")
        except asyncio.CancelledError:
            raise  # the loop is going down, and the callers with it
        except Exception as exc:
            # nothing has verified these items: their callers hear why
            LOG.exception("CPU fallback failed after the batch backend did")
            self._resolve(segments, None, exc, returned)
        else:
            self._resolve(segments, bitmap, None, returned)

    def _resolve(
        self, segments: List[_Segment], bitmap: Optional[Sequence[bool]],
        failure: Optional[Exception], returned: float,
    ) -> None:
        """Loop thread, once a chunk: each call's slice written, the calls
        this chunk completes resolved (all of its calls, with ``failure``,
        where nothing verified it), its slot freed, the next chunk taken."""
        done: List[_Call] = []
        pos = 0
        for call, lo, hi in segments:
            if failure is None:
                call.out[lo:hi] = [bool(ok) for ok in bitmap[pos : pos + hi - lo]]
            pos += hi - lo
            call.left -= hi - lo
            if (call.left == 0 or failure is not None) and not call.future.done():
                done.append(call)
        with hostspan.span(
            stages.SPAN_RESOLVE, items=pos, calls=len(done),
            wait_us=int((time.perf_counter() - returned) * 1e6),
        ):
            for call in done:
                if failure is None:
                    call.future.set_result(call.out)
                else:
                    call.future.set_exception(failure)
        self.metrics.timers[stages.RESOLVE_WAIT].record(time.perf_counter() - returned)
        self.batches_flushed += 1
        self._flush_items.observe(pos)
        self._calls_per_flush.observe(len(done))
        self._running -= 1
        if self._closed:
            if not self._running and self._drained is not None and not self._drained.done():
                self._drained.set_result(None)
        elif self._backlog:
            self._take()

    async def close(self) -> None:
        self._closed = True
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
            self._soon = False
        # Let in-flight chunks finish so their calls resolve (their backend
        # work is already running in the executor either way); what is only
        # queued is cancelled.
        if self._running:
            if self._drained is None or self._drained.done():
                self._drained = asyncio.get_running_loop().create_future()
            await self._drained
        for call in self._pending:
            if not call.future.done():
                call.future.cancel()
        self._pending.clear()
        self._head = self._queued = 0
        self._backlog = False


def verifier_stats(verifier) -> dict:
    """Type + counters for any verifier composition, recursively unwrapping
    ``.inner`` (CachingVerifier, BatchingVerifier-over-Remote, ...).  The
    single extractor behind BOTH operator surfaces — the replica admin
    /status and the verifier service's --admin-port — so key names cannot
    drift between them."""
    st: dict = {"type": type(verifier).__name__ if verifier else "CpuVerifier"}
    if verifier is None or isinstance(verifier, CpuVerifier):
        # Which host engine actually runs this node's inline verifies —
        # openssl / native-c / pure-python.  The same provenance string the
        # benchmark records stamp (ISSUE 5 satellite), so an operator can
        # tell a wheel-less node from a scrape instead of from latency.
        st["host_crypto_engine"] = crypto_keys.host_crypto_engine()
    for attr in (
        "batches_flushed",
        "remote_batches",
        "fallback_batches",
        "hits",
        "misses",
        "agg_hits",     # CachingVerifier: one-attestation certificate memo
        "agg_misses",
        "memo_evictions",  # ...verdicts its full memos dropped, oldest insertion first
        "calls",        # CoalescingVerifier: caller-side verify_batch calls
        "inner_calls",  # ...vs inner round trips (calls/inner_calls = merge ratio)
    ):
        v = getattr(verifier, attr, None)
        if isinstance(v, int):
            st[attr] = v
    backend = getattr(verifier, "backend", None)
    backend_stats = getattr(backend, "stats", None)
    if callable(backend_stats):
        # the device as JAX reports it, host- vs device-routed items, and
        # ready/failed compile buckets (JaxBatchBackend.stats): whether the
        # device path is the one carrying this verifier's traffic
        st["device"] = backend_stats()
    registry = getattr(backend, "registry", None)
    if registry is not None:
        # comb fast-path observability (crypto/comb.py): is the registry
        # populated, which buckets have a compiled comb program, and is
        # the path actually carrying traffic
        from ..crypto import batch_verify as _bv
        from ..crypto.comb import comb_dispatch_count

        routed = _bv.comb_routing_counts()
        st["comb"] = {
            "registered_signers": len(registry),
            "ready_buckets": (
                backend.comb_ready_buckets()
                if hasattr(backend, "comb_ready_buckets")
                # foreign backend: copy first so a concurrent insert cannot
                # raise mid-iteration (ADVICE r4)
                else sorted(list(getattr(backend, "_ready_comb", {})))
            ),
            "device_dispatches_process_total": comb_dispatch_count(),
            # mixed-batch routing occupancy (process-global): how many items
            # the router sent down each leg, and how often a single SPI
            # round trip carried both programs (the merged-bitmap case)
            "items_comb_routed_process_total": routed["comb_items"],
            "items_ladder_routed_process_total": routed["ladder_items"],
            "mixed_batches_process_total": routed["mixed_batches"],
        }
    inner = getattr(verifier, "inner", None)
    if inner is not None:
        st["inner"] = verifier_stats(inner)
    return st
