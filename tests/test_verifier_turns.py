"""What a verified chunk costs the service's event loop, in TURNS (ISSUE 25).

``BatchingVerifier`` and ``CachingVerifier`` hand a chunk from the enqueuing
call to the executor thread, and its verdicts back to the calls that wait, by
callback and by call: no flusher task, no task per chunk, no future per item
or per memo key.  The budget is counted here on a loop that counts its turns,
with a backend that the test releases from INSIDE a turn and that holds the
loop in that turn until the executor thread has handed its result over, so
every count is exact and repeatable: nothing below depends on how fast a
thread wakes.  CPU only, no JAX, no real signatures (a verdict is the
signature's first byte).
"""

import asyncio
import threading

import pytest

from mochi_tpu.obs import hostspan
from mochi_tpu.verifier import stages
from mochi_tpu.verifier.spi import (
    BatchingVerifier,
    CachingVerifier,
    SignatureVerifier,
    VerifyItem,
    aggregate_key,
)

LINGER = 0.002  # BatchingVerifier's default max_delay_s
WAIT_S = 30


class CountingLoop(asyncio.SelectorEventLoop):
    """Counts its turns, and notes the turn of every hand-over to the executor,
    of every timer armed and fired, and how many futures it was asked for."""

    def __init__(self):
        super().__init__()
        self.turns = 0
        self.submitted = []  # turn of each run_in_executor
        self.tasks_at_submit = []  # len(asyncio.all_tasks()) then
        self.timers = []  # [delay, turn armed, turn fired or None]
        self.futures = 0
        self.finished = threading.Semaphore(0)  # one release per executor work item run to its end

    def _run_once(self):
        self.turns += 1
        super()._run_once()

    def create_future(self):
        self.futures += 1
        return super().create_future()

    def call_later(self, delay, callback, *args, context=None):
        row = [delay, self.turns, None]
        self.timers.append(row)

        def fired():
            row[2] = self.turns
            callback(*args)

        return super().call_later(delay, fired, context=context)

    def run_in_executor(self, executor, func, *args):
        self.submitted.append(self.turns)
        self.tasks_at_submit.append(len(asyncio.all_tasks(self)))

        def work():
            try:
                return func(*args)
            finally:
                self.finished.release()

        return super().run_in_executor(executor, work)


class GatedBackend:
    """A batch backend whose every call blocks until the test releases it.
    ``release(n)`` runs ON the loop, opens call n's gate and waits, still in
    that turn, until the executor thread has run its work item to the end
    (hand-over included): the turn it returns is the turn the backend
    returned in, and whatever the thread handed to the loop runs in the next."""

    def __init__(self, loop, fail=()):
        self.loop = loop
        self.fail = set(fail)  # calls (by order of arrival) that raise
        self.calls = []  # [n items, gate]
        self.lock = threading.Lock()

    def __call__(self, items):
        gate = threading.Event()
        with self.lock:
            n = len(self.calls)
            self.calls.append((len(items), gate))
        assert gate.wait(WAIT_S)
        if n in self.fail:
            raise RuntimeError("device lost")
        return [verdict(it) for it in items]

    async def entered(self, n):
        """Let the loop turn until n calls are inside the backend."""
        while len(self.calls) < n:
            await asyncio.sleep(0)

    async def release(self, n):
        await self.entered(n + 1)
        while self.loop.finished.acquire(blocking=False):
            pass
        self.calls[n][1].set()
        assert self.loop.finished.acquire(timeout=WAIT_S)  # holds the loop in this turn, on purpose
        return self.loop.turns


def verdict(item):
    return item.signature[0] != 0


def make_items(n, forged=(), tag=b"t"):
    return [
        VerifyItem(b"k" * 32, tag + b"-%d" % i, (b"\x00" if i in forged else b"\x01") + b"s" * 63)
        for i in range(n)
    ]


def drive(main):
    """Run ``main(loop)`` on a counting loop beside a ticker that keeps the
    loop turning without sleeping, as a busy service's does."""
    loop = CountingLoop()

    async def outer():
        stop = False

        async def ticker():
            while not stop:
                await asyncio.sleep(0)

        tick = loop.create_task(ticker())
        try:
            return await asyncio.wait_for(main(loop), timeout=120)
        finally:
            stop = True
            await tick

    try:
        return loop.run_until_complete(outer())
    finally:
        loop.run_until_complete(loop.shutdown_default_executor())
        loop.close()


class FakeSpans:
    def __init__(self):
        self.entered = []  # (name, args, thread)

    def __call__(self, name, **args):
        outer = self

        class Span:
            def __enter__(self):
                outer.entered.append((name, args, threading.get_ident()))

            def __exit__(self, *exc):
                pass

        return Span()


@pytest.fixture
def spans():
    fake = FakeSpans()
    hostspan.install(fake)
    try:
        yield fake
    finally:
        hostspan.install(None)


# ------------------------------------------------- before the flush: enqueue -> backend


@pytest.mark.parametrize("delay", [0.0, LINGER])
def test_the_backend_starts_in_the_turn_the_flush_callback_runs(delay):
    async def main(loop):
        backend = GatedBackend(loop)
        v = BatchingVerifier(backend, max_delay_s=delay)
        mark = {}

        async def call():
            mark["enqueued"] = loop.turns
            return await v.verify_batch(make_items(43, forged={7}))

        task = loop.create_task(call())
        await backend.release(0)
        got = await task
        await v.close()
        return mark["enqueued"], got, loop

    enqueued, got, loop = drive(main)
    assert got == [i != 7 for i in range(43)]
    assert len(loop.submitted) == 1
    lingers = [t for t in loop.timers if t[0] == LINGER]
    if delay == 0:
        # call_soon: the turn after the enqueue, so that calls of the same turn ride together
        assert loop.submitted[0] - enqueued == 1 and not lingers
    else:
        # ONE timer, armed by the enqueuing call in its own turn; the chunk goes to the
        # executor in the turn that timer fires
        assert len(lingers) == 1
        _, armed, fired = lingers[0]
        assert armed == enqueued and loop.submitted[0] == fired


def test_a_full_batch_does_not_wait_for_the_linger():
    async def main(loop):
        backend = GatedBackend(loop)
        v = BatchingVerifier(backend, max_batch=8, max_delay_s=60.0)
        mark = {}

        async def call(n, tag):
            mark.setdefault("first", loop.turns)
            mark["last"] = loop.turns
            return await v.verify_batch(make_items(n, tag=tag))

        tasks = [loop.create_task(call(5, b"a")), loop.create_task(call(3, b"b"))]
        await backend.release(0)
        got = await asyncio.gather(*tasks)
        await v.close()
        return mark, got, loop, backend

    mark, got, loop, backend = drive(main)
    assert got == [[True] * 5, [True] * 3]
    assert mark["first"] == mark["last"] and loop.submitted == [mark["last"] + 1]
    assert [n for n, _ in backend.calls] == [8]  # one chunk of max_batch; the 60 s timer was cancelled


# ------------------------------------------------- after the flush: backend -> the calls


@pytest.mark.parametrize("delay", [0.0, LINGER])
@pytest.mark.parametrize("role,turns", [("bare", 2), ("owner", 2), ("waiter", 3), ("overlap", 3)])
def test_calls_resume_within_the_budget_after_the_backend_returns(role, turns, delay):
    """The backend returns in turn c; what its thread handed over runs in c+1
    and resolves the call's ONE future; the call (bare, or the memo's owner)
    resumes in c+2; the calls parked on the owner resume in c+3: those that
    ask the same list (``waiter``: one whole-call future) and those that
    share keys with it (``overlap``: one per-key future for the lot)."""

    async def main(loop):
        backend = GatedBackend(loop)
        batcher = BatchingVerifier(backend, max_delay_s=delay)
        v = batcher if role == "bare" else CachingVerifier(batcher)
        items = make_items(43, forged={0, 42})
        resumed = {}

        async def call(who, asked=items):
            got = await v.verify_batch(asked)
            resumed.setdefault(who, loop.turns)
            return got + [False] * (43 - len(got))  # the overlap call leaves out item 42, a forged one

        tasks = [loop.create_task(call("bare" if role == "bare" else "owner"))]
        await backend.entered(1)
        if role != "bare":  # the other replicas' RPCs for the same certificate, a turn later
            tasks += [loop.create_task(call("waiter")) for _ in range(5)]
            tasks += [loop.create_task(call("overlap", items[:42]))]
            await asyncio.sleep(0)
        futures_before_waiters = loop.futures
        returned = await backend.release(0)
        got = await asyncio.gather(*tasks)
        await v.close()
        return resumed, returned, got, loop, futures_before_waiters

    resumed, returned, got, loop, futures = drive(main)
    assert all(g == [i not in (0, 42) for i in range(43)] for g in got)
    assert resumed[role] - returned == turns
    assert len(loop.submitted) == 1  # five waiting calls cost no second chunk
    # one future a CALL in the batcher, one for the executor hand-over, and in the memo the owner's
    # two (for calls that ask the same list, and for calls that share some keys) and the overlap
    # call's one (a list of its own, in flight): none per item, none per key, none for the five
    # calls that ask the owner's list
    assert futures == (2 if role == "bare" else 5)


def test_an_all_hit_call_never_suspends():
    async def main(loop):
        backend = GatedBackend(loop)
        cv = CachingVerifier(BatchingVerifier(backend, max_delay_s=0.0))
        items = make_items(43, forged={3})
        first = loop.create_task(cv.verify_batch(items))
        await backend.release(0)
        await first
        coro = cv.verify_batch(items[10:30] + items[:5])
        try:
            coro.send(None)  # run it by hand: a hit on every item never yields to the loop
        except StopIteration as stop:
            got = stop.value
        else:
            raise AssertionError("an all-hit call suspended")
        await cv.close()
        return got, cv, loop

    got, cv, loop = drive(main)
    assert got == [i != 3 for i in list(range(10, 30)) + list(range(5))]
    assert (cv.misses, cv.hits) == (43, 25) and len(loop.submitted) == 1


# ------------------------------------------------- calls that straddle chunks


@pytest.mark.parametrize("order", [(1, 0), (0, 1)])
def test_a_call_straddling_two_chunks_gets_its_verdicts_in_item_order(order):
    async def main(loop):
        backend = GatedBackend(loop)
        v = BatchingVerifier(backend, max_batch=4, max_delay_s=0.0)
        a, b = make_items(3, forged={1}, tag=b"a"), make_items(3, forged={0, 2}, tag=b"b")
        ta, tb = loop.create_task(v.verify_batch(a)), loop.create_task(v.verify_batch(b))
        await backend.entered(2)
        for n in order:
            await backend.release(n)
            for _ in range(3):
                await asyncio.sleep(0)
            if n == order[0]:
                # a's only chunk is chunk 0; b needs both
                assert ta.done() == (n == 0) and not tb.done()
        got = await asyncio.gather(ta, tb)
        await v.close()
        return got, backend, v

    got, backend, v = drive(main)
    assert got == [[True, False, True], [False, True, False]]
    assert [n for n, _ in backend.calls] == [4, 2]
    hist = v.metrics.snapshot()["histograms"][stages.CALLS_PER_FLUSH]
    assert hist["count"] == 2 and hist["sum"] == 2  # a call counts in the chunk that completes it


@pytest.mark.parametrize("order", [(1, 0, 2), (2, 1, 0), (0, 1, 2), (1, 2, 0)])
def test_a_call_larger_than_two_batches_comes_back_in_item_order(order):
    async def main(loop):
        backend = GatedBackend(loop)
        v = BatchingVerifier(backend, max_batch=4, max_delay_s=0.0, max_inflight=3)
        forged = {0, 3, 4, 8}
        task = loop.create_task(v.verify_batch(make_items(2 * 4 + 1, forged=forged)))
        await backend.entered(3)
        for n in order:
            assert not task.done()
            await backend.release(n)
            await asyncio.sleep(0)
        got = await task
        await v.close()
        return got, forged, backend, v

    got, forged, backend, v = drive(main)
    assert got == [i not in forged for i in range(9)]
    assert [n for n, _ in backend.calls] == [4, 4, 1] and v.batches_flushed == 3
    hist = v.metrics.snapshot()["histograms"][stages.CALLS_PER_FLUSH]
    assert hist["count"] == 3 and hist["sum"] == 1 and hist["buckets"].get("1") == 3  # 0, 0 and 1 call


def test_a_failed_chunk_is_reverified_on_the_fallback_and_keeps_item_order():
    class Fallback(SignatureVerifier):
        def __init__(self):
            self.sizes = []

        async def verify_batch(self, items):
            self.sizes.append(len(items))
            await asyncio.sleep(0)
            return [verdict(it) for it in items]

    async def main(loop):
        backend = GatedBackend(loop, fail={0})
        fallback = Fallback()
        v = BatchingVerifier(backend, max_batch=4, max_delay_s=0.0, fallback=fallback)
        task = loop.create_task(v.verify_batch(make_items(6, forged={2, 5})))
        await backend.release(1)
        await backend.release(0)
        got = await task
        await v.close()
        return got, v, fallback

    got, v, fallback = drive(main)
    assert got == [True, True, False, True, True, False]
    assert v.fallback_batches == 1 and fallback.sizes == [4] and v.batches_flushed == 2
    timers = v.metrics.snapshot()["timers"]
    assert timers[stages.RESOLVE_WAIT]["count"] == 2 and timers[stages.QUEUE_WAIT]["count"] == 2


# ------------------------------------------------- the in-flight cap, the backlog, close()


def test_a_backlog_goes_in_the_turn_a_chunk_frees_its_slot_and_costs_no_task():
    async def main(loop):
        backend = GatedBackend(loop)
        v = BatchingVerifier(backend, max_batch=4, max_delay_s=LINGER, max_inflight=2)
        first = loop.create_task(v.verify_batch(make_items(6 * 4)))  # six chunks, two slots
        await backend.entered(2)
        # a late arrival behind a backlog waits for a slot, not for a linger of its own
        late = loop.create_task(v.verify_batch(make_items(2, forged={1}, tag=b"late")))
        await asyncio.sleep(0)
        baseline = len(asyncio.all_tasks())
        handed, tasks_seen = [], []
        for n in range(7):
            assert len(backend.calls) == min(7, n + 2)  # never a third chunk in flight
            handed.append(await backend.release(n))
            tasks_seen.append(len(asyncio.all_tasks()))
            await backend.entered(min(7, n + 3))
        got = await asyncio.gather(first, late)
        await v.close()
        return got, loop, handed, baseline, tasks_seen, backend

    got, loop, handed, baseline, tasks_seen, backend = drive(main)
    assert got == [[True] * 24, [True, False]]
    assert [n for n, _ in backend.calls] == [4] * 6 + [2]
    # chunks 2..6 went to the executor in the turn after an earlier chunk returned: the
    # callback that resolved that chunk took the next one
    assert loop.submitted[2:] == [t + 1 for t in handed[:5]]
    # main, the ticker and the two callers: no flusher task, no task per chunk
    assert max(tasks_seen + loop.tasks_at_submit) == baseline
    assert not [t for t in loop.timers if t[0] == LINGER]  # a full batch went by call_soon


def test_close_lets_running_chunks_finish_and_cancels_a_call_that_is_half_queued():
    async def main(loop):
        backend = GatedBackend(loop)
        v = BatchingVerifier(backend, max_batch=4, max_delay_s=0.0, max_inflight=1)
        whole = loop.create_task(v.verify_batch(make_items(3, tag=b"w")))
        half = loop.create_task(v.verify_batch(make_items(3, tag=b"h")))  # 1 item in chunk 0, 2 queued
        queued = loop.create_task(v.verify_batch(make_items(2, tag=b"q")))
        await backend.entered(1)
        closing = loop.create_task(v.close())
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert not closing.done() and not whole.done()
        await backend.release(0)
        await closing
        results = await asyncio.gather(whole, half, queued, return_exceptions=True)
        with pytest.raises(RuntimeError, match="closed"):
            await v.verify_batch(make_items(1))
        return results, backend

    results, backend = drive(main)
    assert results[0] == [True] * 3
    assert all(isinstance(r, asyncio.CancelledError) for r in results[1:])
    assert [n for n, _ in backend.calls] == [4]  # nothing queued was started after close()


# ------------------------------------------------- the meter


def test_resolve_wait_and_calls_per_flush_tick_once_a_chunk(spans):
    async def main(loop):
        backend = GatedBackend(loop)
        batcher = BatchingVerifier(backend, max_delay_s=LINGER)
        cv = CachingVerifier(batcher, metrics=batcher.metrics)
        cert = make_items(43, forged={9})
        other = make_items(7, tag=b"o")
        calls = [loop.create_task(cv.verify_batch(cert)), loop.create_task(cv.verify_batch(other))]
        await backend.entered(1)
        calls += [loop.create_task(cv.verify_batch(cert)) for _ in range(3)]  # memo waiters: no batcher call
        await backend.release(0)
        await asyncio.gather(*calls)
        calls = [loop.create_task(cv.verify_batch(make_items(5, tag=b"z")))]
        await backend.release(1)
        await asyncio.gather(*calls)
        await cv.close()
        return batcher, loop

    batcher, loop = drive(main)
    snap = batcher.metrics.snapshot()
    assert batcher.batches_flushed == 2
    assert snap["timers"][stages.RESOLVE_WAIT]["count"] == 2 == snap["timers"][stages.QUEUE_WAIT]["count"]
    calls, flushed = snap["histograms"][stages.CALLS_PER_FLUSH], snap["histograms"][stages.FLUSH_ITEMS]
    assert (calls["count"], calls["sum"]) == (2, 3) and calls["buckets"] == {"1": 1, "2": 1}
    assert (flushed["count"], flushed["sum"]) == (2, 55)
    resolved = [(args, tid) for name, args, tid in spans.entered if name == stages.SPAN_RESOLVE]
    assert [(a["items"], a["calls"]) for a, _ in resolved] == [(50, 2), (5, 1)]
    assert all(a["wait_us"] >= 0 for a, _ in resolved)
    chunk_threads = {tid for name, _, tid in spans.entered if name == stages.SPAN_CHUNK}
    # the resolve span is the loop thread's, the chunk span the executor's
    assert {tid for _, tid in resolved} == {threading.get_ident()} and threading.get_ident() not in chunk_threads


# ------------------------------------------------- the whole-call memo


def test_a_list_asked_again_costs_one_lookup_and_no_per_item_work(spans):
    async def main(loop):
        backend = GatedBackend(loop)
        cv = CachingVerifier(BatchingVerifier(backend, max_delay_s=0.0))
        cert = make_items(43, forged={5})
        first = loop.create_task(cv.verify_batch(cert))
        await backend.release(0)
        await first
        planned = []
        plan = cv._plan
        cv._plan = lambda items: planned.append(len(items)) or plan(items)
        # the same list in fresh objects, as the next replica's RPC decodes it
        again = [VerifyItem(bytes(bytearray(i.public_key)), bytes(bytearray(i.message)), i.signature) for i in cert]
        coro = cv.verify_batch(again)
        try:
            coro.send(None)
        except StopIteration as stop:
            got = stop.value
        else:
            raise AssertionError("a whole-call hit suspended")
        # bare triples, as a verify request carries them, are the same key; and another list of
        # known items, bare too, goes per item and is then remembered whole
        raw = cv.verify_batch(tuple((i.public_key, i.message, i.signature) for i in cert))
        try:
            raw.send(None)
        except StopIteration as stop:
            assert stop.value == got
        other = cv.verify_batch([tuple(i) for i in cert[:10]])
        try:
            other.send(None)
        except StopIteration as stop:
            got_other = stop.value
        await cv.close()
        return got, got_other, planned, cv, loop

    got, got_other, planned, cv, loop = drive(main)
    assert got == [i != 5 for i in range(43)] and got_other == [i != 5 for i in range(10)]
    assert planned == [10] and (cv.misses, cv.hits) == (43, 96) and len(loop.submitted) == 1
    assert tuple(make_items(43, forged={5})[:10]) in cv._calls
    snap = cv.metrics.snapshot()
    assert snap["timers"][stages.MEMO_LOOKUP]["count"] == 4 and snap["counters"][stages.MEMO_ITEMS] == 139
    assert [a["items"] for n, a, _ in spans.entered if n == stages.SPAN_MEMO] == [43, 43, 43, 10]


def test_the_whole_call_memo_holds_no_more_items_than_the_per_item_cache():
    async def main(loop):
        cv = CachingVerifier(BatchingVerifier(lambda items: [verdict(i) for i in items], max_delay_s=0.0),
                             max_entries=100)
        for n in range(8):
            assert await cv.verify_batch(make_items(30, forged={n}, tag=b"c%d" % n)) == [i != n for i in range(30)]
        await cv.close()
        return cv

    cv = drive(main)
    assert cv._calls_items == sum(len(k) for k in cv._calls) == 90 and len(cv._cache) == 100
    assert [k[0].message for k in cv._calls] == [b"c5-0", b"c6-0", b"c7-0"]  # the oldest went first


# ------------------------------------------------- single-flight: nobody inherits a cancellation


@pytest.mark.parametrize("aggregate", [False, True])
def test_a_cancelled_waiter_does_not_take_the_other_waiters_with_it(aggregate):
    async def main(loop):
        backend = GatedBackend(loop)
        cv = CachingVerifier(BatchingVerifier(backend, max_delay_s=0.0))
        cert = make_items(43, forged={11})
        key = aggregate_key(cert)

        def ask():
            return cv.verify_aggregate(key, cert) if aggregate else cv.verify_batch(cert)

        owner = loop.create_task(ask())
        await backend.entered(1)
        doomed, survivor = loop.create_task(ask()), loop.create_task(ask())
        await asyncio.sleep(0)
        doomed.cancel()  # Task.cancel cancels the future it waits on: the owner's
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        # the survivor and a late caller find the owner's future cancelled and ask for themselves:
        # an aggregate is verified a second time; a batch's items are still in flight under
        # their per-key future, which nobody cancelled, so they cost no second backend call
        late = loop.create_task(ask())
        await backend.release(0)
        if aggregate:
            await backend.release(1)
        got = await asyncio.gather(owner, doomed, survivor, late, return_exceptions=True)
        await cv.close()
        return got, cv

    got, cv = drive(main)
    want = False if aggregate else [i != 11 for i in range(43)]
    assert isinstance(got[1], asyncio.CancelledError)
    assert got[0] == want and got[2] == want and got[3] == want
    assert not cv._inflight and not cv._agg_inflight and not cv._calls_inflight
