"""``CachingVerifier``'s three bounded memos (ISSUE 30): WHAT they drop, held to
a plain reference, and HOW, counted in container operations.

The policy: the oldest insertion goes first; neither a hit nor writing a key
that is already there renews it; ``max_entries`` bounds the signatures held
(the whole-call memo by the items of the calls it holds).  The reference keeps
each memo as a list of keys in insertion order and walks the same calls, so
the hits, the misses, the verdicts and the surviving keys can be compared after
every step.  The mechanism: the front goes in one container operation, found
by no walk.  CPU only, no JAX, no clock, no real signatures (a verdict is the
signature's first byte).
"""

import asyncio
import random
from collections import OrderedDict

import pytest

from mochi_tpu.verifier import stages
from mochi_tpu.verifier.spi import CachingVerifier, SignatureVerifier, VerifyItem, aggregate_key

MEMOS = ("_cache", "_calls", "_agg")
BOUND = 48


def item(n: int) -> VerifyItem:
    """Item ``n``; every fifth is a bad signature."""
    return VerifyItem(b"k" * 32, b"m%d" % n, bytes([n % 5 != 0]) + b"s" * 63)


def verdict(it) -> bool:
    return bool(it[2][0])


def key(it):
    return (it[0], it[1], it[2])


class Inner(SignatureVerifier):
    """Answers at once, or (``gate``) holds each call until the test lets it
    go, to an answer or to a failure."""

    def __init__(self):
        self.gate = False
        self.held = []

    async def verify_batch(self, items):
        if self.gate:
            self.held.append(asyncio.get_running_loop().create_future())
            await self.held[-1]
        return [verdict(it) for it in items]

    def let_go(self, fail=False):
        fut = self.held.pop(0)
        fut.set_exception(RuntimeError("the backend and its fallback failed")) if fail else fut.set_result(None)


async def turns(n=6):
    for _ in range(n):
        await asyncio.sleep(0)


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=120))


def ask(cv, memo, x):
    """``x`` through the call that writes ``memo``."""
    return cv.verify_aggregate(aggregate_key(x), x) if memo == "_agg" else cv.verify_batch(x)


# ------------------------------------------------------------ the reference


class Fifo:
    """A bounded memo as a list of keys in insertion order beside their
    verdicts.  The per-item and aggregate memos (no ``weight``) make room
    before the write, whether or not the key is new; the whole-call memo
    writes and then drops while its calls hold too many items."""

    def __init__(self, bound, ref, weight=None):
        self.bound, self.ref, self.weight = bound, ref, weight
        self.keys, self.value = [], {}

    def held(self):
        return sum(map(self.weight, self.keys)) if self.weight else len(self.keys)

    def drop_oldest(self):
        del self.value[self.keys.pop(0)]
        self.ref.evictions += self.weight is None  # verdicts dropped: the whole-call memo holds copies

    def put(self, k, v):
        if self.weight is None and len(self.keys) >= self.bound:
            self.drop_oldest()
        if k not in self.value:
            self.keys.append(k)
        self.value[k] = v
        while self.weight is not None and self.held() > self.bound and self.keys:
            self.drop_oldest()


class Reference:
    """What ``CachingVerifier`` answers and keeps, call for call, with nothing
    but lists and dicts: ``begin`` is a call up to its first await, ``answer``
    the inner verifier's reply to it, ``finish`` the rest."""

    def __init__(self, bound):
        self.cache = Fifo(bound, self)
        self.calls = Fifo(bound, self, weight=len)
        self.agg = Fifo(bound, self)
        self.hits = self.misses = self.evictions = 0
        self.owner = {}  # key -> the call verifying it
        self.call_owner = {}  # tuple of items -> the call verifying it
        self.agg_owner = {}

    # -- verify_batch
    def begin(self, items):
        call = tuple(items)
        st = {"call": call, "out": [None] * len(call), "new": {}, "waiting": {}, "failed": False}
        if call in self.calls.value:
            self.hits += len(call)
            st["done"] = list(self.calls.value[call])
        elif call in self.call_owner:
            self.hits += len(call)
            st["asked"] = self.call_owner[call]
        else:
            for i, it in enumerate(call):
                k = key(it)
                if k in self.cache.value:
                    st["out"][i] = self.cache.value[k]
                elif k in self.owner:
                    st["waiting"].setdefault(id(self.owner[k]), (self.owner[k], []))[1].append(i)
                elif k in st["new"]:
                    st["new"][k].append(i)
                else:
                    st["new"][k] = [i]
                    self.misses += 1
                    continue
                self.hits += 1
            if not st["new"] and not st["waiting"]:
                self.calls.put(call, tuple(st["out"]))
                st["done"] = st["out"]
            else:
                self.call_owner[call] = st
                for k in st["new"]:
                    self.owner[k] = st
        return st

    def answer(self, st, fail=False):
        """The inner verifier's reply to the call's new keys."""
        for k, idxs in st["new"].items():
            if not fail:
                for i in idxs:
                    st["out"][i] = bool(k[2][0])
                self.cache.put(k, bool(k[2][0]))
            if self.owner.get(k) is st:
                del self.owner[k]
        if fail:
            st["failed"] = True
            if self.call_owner.get(st["call"]) is st:
                del self.call_owner[st["call"]]

    def finish(self, st):
        """After the owners this call waited for have answered or failed."""
        if "done" in st:
            return st["done"]
        if "asked" in st:
            return self.verify(st["call"]) if st["asked"]["failed"] else list(st["asked"]["out"])
        for owner, idxs in st["waiting"].values():
            got = self.verify([st["call"][i] for i in idxs]) if owner["failed"] else \
                [owner["out"][owner["call"].index(st["call"][i])] for i in idxs]
            for i, ok in zip(idxs, got):
                st["out"][i] = ok
        self.calls.put(st["call"], tuple(st["out"]))
        if self.call_owner.get(st["call"]) is st:
            del self.call_owner[st["call"]]
        return st["out"]

    def verify(self, items):
        st = self.begin(items)
        if st["new"]:
            self.answer(st)
        return self.finish(st)

    # -- verify_aggregate
    def agg_begin(self, k, items):
        st = {"key": k, "items": items, "failed": False}
        if k in self.agg.value:
            self.hits += 1
            st["done"] = self.agg.value[k]
        elif k in self.agg_owner:
            self.hits += 1
            st["asked"] = self.agg_owner[k]
        else:
            self.misses += 1
            self.agg_owner[k] = st
        return st

    def agg_answer(self, st, fail=False):
        st["failed"] = fail
        if not fail:
            st["done"] = all(verdict(it) for it in st["items"])
            self.agg.put(st["key"], st["done"])
        del self.agg_owner[st["key"]]

    def agg_finish(self, st):
        if "done" in st:
            return st["done"]
        return self.aggregate(st["key"], st["items"]) if st["asked"]["failed"] else st["asked"]["done"]

    def aggregate(self, k, items):
        st = self.agg_begin(k, items)
        if "done" not in st:
            self.agg_answer(st)
        return self.agg_finish(st)


# ------------------------------------------------------------ the stream


def stream(memo, seed, steps):
    """Seeded steps that lean on ``memo``: calls of fresh, recent and long
    evicted items with repeats inside a call, whole calls asked again, two
    calls in flight over shared keys, and an owner that fails under a waiter."""
    rng = random.Random(f"{memo}:{seed}")
    fresh, asked = iter(range(10**9)), []

    def items():
        if asked and rng.random() < {"_calls": 0.5, "_cache": 0.1, "_agg": 0.3}[memo]:
            return list(rng.choice(asked[-60:]))  # the same list again
        top = next(fresh)
        picks = []
        for _ in range(rng.randint(1, 7)):
            r = rng.random()
            picks.append(next(fresh) if r < 0.5 else                   # never seen
                         rng.randint(max(0, top - 30), top) if r < 0.8 else  # recent: a hit
                         rng.randint(0, top))                          # any age: evicted or not
        if rng.random() < 0.2:
            picks.append(rng.choice(picks))  # a repeat inside the call
        return [item(n) for n in picks]

    for _ in range(steps):
        a = items()
        asked.append(a)
        aggregate = rng.random() < (0.8 if memo == "_agg" else 0.1)
        r = rng.random()
        if r < 0.7:
            yield ("one", aggregate, a, None)
        else:
            # b is a itself, or shares its first items; a answers or fails under it
            b = a if rng.random() < 0.4 else a[: rng.randint(1, len(a))] + [item(next(fresh))]
            asked.append(b)
            yield ("fail" if r > 0.88 else "two", aggregate, a, b)


async def step(cv, inner, ref, kind, aggregate, a, b):
    """One step on both sides; returns (got, expected)."""
    memo = "_agg" if aggregate else "_cache"
    if aggregate:
        begin, answer, finish = (lambda x: ref.agg_begin(aggregate_key(x), x)), ref.agg_answer, ref.agg_finish
    else:
        begin, answer, finish = ref.begin, ref.answer, ref.finish
    if kind == "one":
        sa = begin(a)
        if "done" not in sa:
            answer(sa)
        return [await ask(cv, memo, a)], [finish(sa)]
    # a is held at the inner verifier while b arrives; what b has to verify for itself is
    # answered at once, and a b that waits for nothing of a's is over before a is answered
    inner.gate = True
    ta = asyncio.ensure_future(ask(cv, memo, a))
    await turns()
    inner.gate = False
    tb = asyncio.ensure_future(ask(cv, memo, b))
    await turns()
    sa, sb = begin(a), begin(b)
    if "done" not in sb and "asked" not in sb and (aggregate or sb["new"]):
        answer(sb)
    waits = "asked" in sb or bool(sb.get("waiting"))
    early = None if waits else finish(sb)
    failed = bool(inner.held) and kind == "fail"  # nothing is held where the memo answered a whole
    if inner.held:
        inner.let_go(fail=failed)
        answer(sa, fail=failed)
    got = await asyncio.gather(ta, tb, return_exceptions=True)
    want_a = RuntimeError if failed else finish(sa)
    want = [want_a, finish(sb) if waits else early]
    return [type(g) if isinstance(g, Exception) else g for g in got], want


@pytest.mark.parametrize("seed", [1, 2, 3, 2147483659])
@pytest.mark.parametrize("memo", MEMOS)
def test_the_policy_is_the_references_at_every_step(memo, seed):
    async def main():
        inner = Inner()
        cv, ref = CachingVerifier(inner, max_entries=BOUND), Reference(BOUND)
        kinds = set()
        for n, (kind, aggregate, a, b) in enumerate(stream(memo, seed, 5 * BOUND)):
            got, want = await step(cv, inner, ref, kind, aggregate, a, b)
            where = f"step {n} {kind} aggregate={aggregate}"
            assert got == want, where
            assert (cv.hits, cv.misses, cv.memo_evictions) == (ref.hits, ref.misses, ref.evictions), where
            assert list(cv._cache) == ref.cache.keys and list(cv._agg) == ref.agg.keys, where
            assert list(cv._calls) == ref.calls.keys and cv._calls_items == ref.calls.held(), where
            assert dict(getattr(cv, memo)) == getattr(ref, memo.strip("_")).value, where
            assert max(len(cv._cache), len(cv._agg), cv._calls_items) <= BOUND, where
            assert not (cv._inflight or cv._calls_inflight or cv._agg_inflight or inner.held), where
            kinds.add((kind, aggregate))
        return cv, ref, kinds

    cv, ref, kinds = run(main())
    # the stream reached what it is for: every kind of step, verdicts of both signs, a memo turned over
    assert kinds >= {(k, memo == "_agg") for k in ("one", "two", "fail")}
    held = getattr(ref, memo.strip("_"))
    assert ref.evictions > 2 * BOUND and held.held() > BOUND - 8 and ref.hits > BOUND
    assert ref.misses - ref.evictions >= len(ref.cache.keys) + len(ref.agg.keys)  # less what a failed owner never wrote
    assert {v if isinstance(v, bool) else all(v) for v in held.value.values()} == {True, False}


# ------------------------------------------------------------ the policy, case by case


async def fill(cv, memo, n):
    """``n`` entries of one signature each, written in order; returns the keys."""
    lists = [[item(1000 + i)] for i in range(n)]
    for x in lists:
        await ask(cv, memo, x)
    return [{"_cache": key(x[0]), "_calls": tuple(x), "_agg": aggregate_key(x)}[memo] for x in lists]


@pytest.mark.parametrize("memo", MEMOS)
def test_a_hit_is_not_renewed(memo):
    async def main():
        cv = CachingVerifier(Inner(), max_entries=4)
        keys = await fill(cv, memo, 4)
        before = (cv.hits, cv.misses)
        first = [item(1000)]
        # the whole-call memo would answer the same list: the per-item memo is hit through another
        hit = first + first if memo == "_cache" else first
        await ask(cv, memo, hit)
        assert (cv.hits - before[0], cv.misses - before[1]) == (len(hit), 0)
        assert list(getattr(cv, memo))[:4] == keys  # still the oldest
        await fill(cv, memo, 5)  # one more entry
        return cv, keys

    cv, keys = run(main())
    held = list(getattr(cv, memo))
    assert keys[0] not in held and keys[1] in held and len(cv._cache) <= 4 and cv._calls_items <= 4


@pytest.mark.parametrize("memo", MEMOS)
def test_a_key_written_again_is_not_renewed(memo):
    """A key is written while present when its owner's future was cancelled
    under it (a waiter's cancellation reaches the future it waits on), another
    call verified the key for itself, and the first owner's answer then
    arrives.  The whole-call memo's two writes are adjacent on that path, so
    there the write is made directly."""
    async def main():
        inner = Inner()
        cv = CachingVerifier(inner, max_entries=8)
        if memo == "_calls":
            c, d = (item(1), item(2)), (item(3),)
            cv._remember(c, [True, True])
            cv._remember(d, [True])
            cv._remember(c, [True, True])
            return cv, [c, d]
        k = [item(1)]

        def with_k(n):  # the aggregate's key is the list itself; a batch shares item 1 and is another list
            return ask(cv, memo, k if memo == "_agg" else k + [item(n)])

        inner.gate = True
        owner = asyncio.ensure_future(with_k(11))
        await turns()
        inner.gate = False
        waiter = asyncio.ensure_future(with_k(12))
        await turns()
        waiter.cancel()
        await turns()
        assert await with_k(13) in (True, [True, True])  # verifies the key itself, and writes it
        other = [item(14)]
        await ask(cv, memo, other)
        inner.let_go()
        assert await owner in (True, [True, True])  # ... and the first owner writes it again
        assert waiter.cancelled()
        if memo == "_agg":
            return cv, [aggregate_key(k), aggregate_key(other)]
        return cv, [key(item(n)) for n in (12, 1, 13, 14, 11)]

    cv, order = run(main())
    assert list(getattr(cv, memo)) == order
    assert cv._calls_items == sum(len(c) for c in cv._calls) and cv.memo_evictions == 0


# ------------------------------------------------------------ the mechanism, counted


class Counted(OrderedDict):
    """Counts the removals of its front and every iteration begun over it."""

    def __init__(self):
        super().__init__()
        self.fronts = self.iters = 0

    def popitem(self, last=True):
        assert last is False, "the memo drops its OLDEST insertion"
        self.fronts += 1
        return super().popitem(last)

    def __iter__(self):
        self.iters += 1
        return super().__iter__()


@pytest.mark.parametrize("memo", MEMOS)
def test_an_eviction_is_one_removal_of_the_front_and_no_walk(memo):
    bound, per_call = 4096, {"_cache": 64, "_calls": 1, "_agg": 1}[memo]
    n_calls = 4 * bound // per_call  # 4 x the bound written: 3 x the bound evicted

    async def main():
        cv = CachingVerifier(Inner(), max_entries=bound)
        counted = {m: Counted() for m in MEMOS}
        for m, c in counted.items():
            setattr(cv, m, c)
        for n in range(n_calls):
            x = [item(n * per_call + i) for i in range(per_call)]
            got = await ask(cv, memo, x)
            assert got == (all(map(verdict, x)) if memo == "_agg" else [verdict(it) for it in x])
        return cv, counted

    cv, counted = run(main())
    c = counted[memo]
    assert c.fronts == n_calls * per_call - bound == 3 * bound and len(c) == bound
    assert cv.misses == n_calls * per_call
    assert [c.iters for c in counted.values()] == [0, 0, 0]
    # the counter is the verdicts dropped: misses less evictions is what the two memos hold
    assert cv.memo_evictions == counted["_cache"].fronts + counted["_agg"].fronts == cv.misses - bound
    if memo == "_agg":
        assert counted["_cache"].fronts == counted["_calls"].fronts == 0 == len(counted["_cache"])
    else:
        assert counted["_agg"].fronts == 0 and cv._calls_items == sum(map(len, cv._calls)) <= bound
    # one tick of the settle stage per call that had something to verify, its items the call's misses
    timer = cv.metrics.snapshot()["timers"][stages.MEMO_SETTLE]
    assert timer["count"] == n_calls
