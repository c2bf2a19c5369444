"""YCSB core workloads as a closed-loop load generator, and its worker process.

The distributions are YCSB's own, written out here (``ZipfianGenerator``,
``ScrambledZipfianGenerator``, ``Utils.fnvhash64`` of YCSB 0.17) and not
``numpy.random.zipf``: a scrambled zipfian draws a rank from a zipfian over
10**10 items with constant 0.99 and spreads the ranks over the key space by an
FNV hash, so the hottest record gets ~3.8% of the requests whatever the record
count, and hot records are not neighbours.

A traffic mix is a data file (``perf/traffic/<name>.json``) of proportions and
a request distribution; this module is the one general generator that reads it.

The worker (``python perf/ycsb.py <spec.json>``) is one of the cell's
``generator_processes`` OS processes.  It never imports ``jax``.  It loads its
share of the records, then runs its callers closed loop for the window and
writes every operation it issued to a file; it decides nothing about
correctness — ``perf/reference.py`` does, from that file.
"""

from __future__ import annotations

import asyncio
import json
import math
import os
import random
import sys
import time
import zlib

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
_MASK64 = (1 << 64) - 1

ZIPFIAN_CONSTANT = 0.99
# ScrambledZipfianGenerator.java: the zipfian it scrambles is over this many
# items, with zeta(ITEM_COUNT, 0.99) precomputed
SCRAMBLED_ITEM_COUNT = 10_000_000_000
SCRAMBLED_ZETAN = 26.46902820178302

VALUE_BYTES = 1000  # YCSB core: 10 fields x 100 B, packed into one value
TAG_BYTES = 16
LOAD_WRITER = 0xFFFFFFFF  # writer id of the load phase; seq is the record index
_POOL_BYTES = 1 << 16

SDK_TIMEOUT_S = 60.0
LOAD_ATTEMPTS = 4  # per record; the load is set-up, not measured
# per operation of the window.  The store leaves the retry of a typed failure
# to the application (client/client.py's docstring): on the zipf head two
# writers' Write2s can split the replicas ("best agreement 2 < quorum 3"), and
# admission control can shed a write.  A caller does what an application does
# and sends the same operation again; its latency spans every attempt.
OP_ATTEMPTS = 6
RETRY_BACKOFF_S = 0.02  # doubled per attempt, jittered


def fnvhash64(val: int) -> int:
    """YCSB ``Utils.fnvhash64``: FNV-1 over the 8 octets of a long, low octet
    first, in Java's signed 64-bit arithmetic, absolute value at the end."""
    h = FNV_OFFSET_BASIS_64
    for _ in range(8):
        octet = val & 0xFF
        val >>= 8
        h ^= octet
        h = (h * FNV_PRIME_64) & _MASK64
    if h >= 1 << 63:  # Math.abs of the signed long
        h = (1 << 64) - h
    return h


def zeta(n: int, theta: float) -> float:
    return sum(1.0 / math.pow(i + 1, theta) for i in range(n))


class Zipfian:
    """YCSB ``ZipfianGenerator`` over ``items`` ranks, rank 0 the most popular
    (Gray et al., "Quickly generating billion-record synthetic databases")."""

    def __init__(self, items: int, rng: random.Random,
                 theta: float = ZIPFIAN_CONSTANT, zetan: float | None = None):
        self.items = items
        self.rng = rng
        self.theta = theta
        self.zetan = zeta(items, theta) if zetan is None else zetan
        self.zeta2theta = zeta(2, theta)
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1 - math.pow(2.0 / items, 1 - theta)) / (
            1 - self.zeta2theta / self.zetan
        )

    def next(self) -> int:
        u = self.rng.random()
        uz = u * self.zetan
        if uz < 1.0:
            return 0
        if uz < 1.0 + math.pow(0.5, self.theta):
            return 1
        return int(self.items * math.pow(self.eta * u - self.eta + 1, self.alpha))


class ScrambledZipfian:
    """YCSB ``ScrambledZipfianGenerator`` over ``[0, items)``."""

    def __init__(self, items: int, rng: random.Random):
        self.items = items
        self.zipf = Zipfian(SCRAMBLED_ITEM_COUNT, rng, zetan=SCRAMBLED_ZETAN)

    def next(self) -> int:
        return fnvhash64(self.zipf.next()) % self.items


class Uniform:
    def __init__(self, items: int, rng: random.Random):
        self.items = items
        self.rng = rng

    def next(self) -> int:
        return self.rng.randrange(self.items)


DISTRIBUTIONS = {"zipfian": ScrambledZipfian, "uniform": Uniform}


def load_traffic(path: str) -> dict:
    """A traffic mix: proportions that sum to 1 and a request distribution."""
    with open(path) as fh:
        mix = json.load(fh)
    read, update = float(mix["readproportion"]), float(mix["updateproportion"])
    if abs(read + update - 1.0) > 1e-9:
        raise ValueError(f"{path}: proportions sum to {read + update}, not 1")
    if mix["requestdistribution"] not in DISTRIBUTIONS:
        raise ValueError(f"{path}: unknown distribution {mix['requestdistribution']!r}")
    return mix


def key_name(i: int) -> str:
    """YCSB's hashed insert order: ``user`` + fnvhash64(record number)."""
    return f"user{fnvhash64(i)}"


def value_pool(seed: int) -> bytes:
    return random.Random(f"perf-values:{seed}").randbytes(_POOL_BYTES)


def make_value(pool: bytes, writer: int, seq: int) -> bytes:
    """The 1,000-byte record written by operation ``(writer, seq)``: a 16-byte
    tag naming the operation (so a reader can say whose write it saw, and no
    two writes are equal) and 984 seeded bytes that depend on the tag."""
    tag = b"%08x%08x" % (writer, seq)
    off = (writer * 2654435761 + seq * 40503) % (_POOL_BYTES - VALUE_BYTES)
    return tag + pool[off:off + VALUE_BYTES - TAG_BYTES]


def parse_tag(value) -> tuple | None:
    """(writer, seq) from a value's tag, or None where it has none."""
    if value is None or len(value) < TAG_BYTES:
        return None
    try:
        return int(value[:8], 16), int(value[8:TAG_BYTES], 16)
    except ValueError:
        return None


class OpStream:
    """One caller's operations: (is_update, record index), from its own rng."""

    def __init__(self, mix: dict, records: int, rng: random.Random):
        self.rng = rng
        self.update_p = float(mix["updateproportion"])
        self.keys = DISTRIBUTIONS[mix["requestdistribution"]](records, rng)

    def next(self) -> tuple:
        return self.rng.random() < self.update_p, self.keys.next()


# ----------------------------------------------------------------- the worker


async def sdk_read(client, key: str):
    """One SDK read: (value bytes or None, grants in its certificate)."""
    from mochi_tpu.client import TransactionBuilder

    res = await client.execute_read_transaction(TransactionBuilder().read(key).build())
    op = res.operations[0]
    cert = op.current_certificate
    value = op.value
    return (bytes(value) if value is not None else None,
            len(cert.grants) if cert is not None else 0)


async def sdk_update(client, key: str, value: bytes) -> None:
    from mochi_tpu.client import TransactionBuilder

    await client.execute_write_transaction(TransactionBuilder().write(key, value).build())


def _timer_counts(clients, names) -> dict:
    return {n: [c.metrics.timers[n].total_count for c in clients] for n in names}


def _timer_samples(clients, names, before: dict) -> dict:
    """The window's samples of each stage timer, in seconds: what each
    client's timer recorded since ``before`` (its deque keeps the last 8192)."""
    out = {}
    for n in names:
        samples = []
        for c, n0 in zip(clients, before[n]):
            t = c.metrics.timers[n]
            new = t.total_count - n0
            if new > 0:
                samples.extend(list(t.samples)[-min(new, len(t.samples)):])
        out[n] = samples
    return out


STAGE_TIMERS = ("write1-phase", "write2-fanout-wait")


# what the SDK's routing score of a replica adds up (client.py ``_suspicion_score``)
SUSPICION_COUNTERS = ("suspect.", "fanout.straggler-timeout.")


def _counters(clients) -> list:
    """Each caller's SDK counters, and beside them how often each of its timers
    ran (``calls.<timer>``: a read that fell back to the full fan-out ran
    ``read-transactions`` twice)."""
    return [dict(c.metrics.counters, **{f"calls.{n}": t.total_count for n, t in c.metrics.timers.items()})
            for c in clients]


def _counter_deltas(clients, before: list) -> dict:
    """What the SDK's counters gained over the window: summed over this
    worker's callers (``sum``), and for each counter how many callers it moved
    in (``callers``).  A caller keeps its own view of every replica (marks of
    suspicion, failed handshakes, straggler time-outs), and that view routes
    its reads for a minute (``marks``: caller by caller, what that score adds
    up against each replica)."""
    total: dict = {}
    moved: dict = {}
    marks: dict = {}  # replica -> each caller's marks of suspicion against it, where it has any
    for now, c0 in zip(_counters(clients), before):
        mine: dict = {}
        for name, value in now.items():
            gained = value - c0.get(name, 0)
            if gained:
                total[name] = total.get(name, 0) + gained
                moved[name] = moved.get(name, 0) + 1
                if name.startswith(SUSPICION_COUNTERS):
                    sid = name.rsplit(".", 1)[1]
                    mine[sid] = mine.get(sid, 0) + gained
        for sid, n in mine.items():
            marks.setdefault(sid, []).append(n)
    return {"sum": total, "callers": moved, "marks": marks}


def _count(errors: dict, exc: Exception) -> None:
    what = f"{type(exc).__name__}: {exc}"[:120]
    errors[what] = errors.get(what, 0) + 1


async def with_retries(call, rng: random.Random, retried: dict):
    """``await call()``, again after a jittered pause where it raises, up to
    ``OP_ATTEMPTS`` times; the last failure is raised.  ``retried`` counts
    what the attempts that were repeated raised."""
    for attempt in range(OP_ATTEMPTS):
        try:
            return await call()
        except Exception as exc:
            if attempt == OP_ATTEMPTS - 1:
                raise
            _count(retried, exc)
            await asyncio.sleep(RETRY_BACKOFF_S * (1 << attempt) * (0.5 + rng.random()))


async def worker(spec: dict) -> None:
    from mochi_tpu.client.client import MochiDBClient
    from mochi_tpu.cluster.config import ClusterConfig

    with open(spec["cluster_config"]) as fh:
        config = ClusterConfig.from_json(fh.read())
    seed, wid = spec["seed"], spec["worker"]
    pool = value_pool(seed)
    mix = spec["traffic"]
    records = spec["records"]
    callers = spec["callers"]  # global caller ids this worker runs
    loaders = max(spec["load_callers"], len(callers))
    clients = [
        MochiDBClient(config=config, timeout_s=SDK_TIMEOUT_S,
                      rng_seed=(seed * 1000003 + wid * 1009 + i) & 0x7FFFFFFF)
        for i in range(loaders)
    ]
    stdin = asyncio.StreamReader()
    loop = asyncio.get_running_loop()
    await loop.connect_read_pipe(lambda: asyncio.StreamReaderProtocol(stdin), sys.stdin)

    # ---- load: this worker's share of the records, single-key inserts
    share = list(spec["load_records"])
    load_failed = []

    async def load_loop(client, idxs):
        for i in idxs:
            for attempt in range(LOAD_ATTEMPTS):
                try:
                    await sdk_update(client, key_name(i), make_value(pool, LOAD_WRITER, i))
                    break
                except Exception as exc:  # shed by admission control: wait, try again
                    error = f"{key_name(i)}: {type(exc).__name__}: {exc}"
                    await asyncio.sleep(0.5 * (attempt + 1))
            else:  # reported, and fails the run
                load_failed.append(error)

    await asyncio.gather(*(load_loop(c, share[k::loaders]) for k, c in enumerate(clients)))
    for c in clients[len(callers):]:
        await c.close()
    clients = clients[:len(callers)]
    # every window caller has read once, so its read path is connected too
    warm_rng = random.Random(f"warm:{seed}:{wid}")
    await asyncio.gather(*(sdk_read(c, key_name(warm_rng.randrange(records))) for c in clients))
    print(json.dumps({"loaded": len(share) - len(load_failed), "load_failed": load_failed[:5],
                      "n_load_failed": len(load_failed)}), flush=True)

    # ---- window: wait for "GO <monotonic start> <seconds>"
    line = (await stdin.readline()).decode().split()
    if not line or line[0] != "GO":
        for c in clients:
            await c.close()
        return
    t_start, seconds = float(line[1]), float(line[2])
    t_end = t_start + seconds
    ops = []  # [kind, record, t_issue, t_done, ok, writer, seq, crc, grants]
    errors: dict = {}  # what the failed operations raised, by type and message
    retried: dict = {}  # what the attempts raised that were made again
    timers0 = _timer_counts(clients, STAGE_TIMERS)
    counters0 = _counters(clients)

    async def caller_loop(client, cid):
        stream = OpStream(mix, records, random.Random(f"ops:{seed}:{cid}"))
        pause = random.Random(f"retry:{seed}:{cid}")  # not the stream's: a retry moves no operation
        seq = 0
        await asyncio.sleep(max(0.0, t_start - time.monotonic()))
        while True:
            t0 = time.monotonic()
            if t0 >= t_end:
                return
            is_update, rec = stream.next()
            key = key_name(rec)
            if is_update:
                value = make_value(pool, cid, seq)
                try:
                    await with_retries(lambda: sdk_update(client, key, value), pause, retried)
                    ok = 1
                except Exception as exc:
                    ok = 0
                    _count(errors, exc)
                ops.append([1, rec, t0, time.monotonic(), ok, cid, seq, zlib.crc32(value), 0])
                seq += 1
            else:
                try:
                    value, grants = await with_retries(lambda: sdk_read(client, key), pause, retried)
                    tag = parse_tag(value)
                    ok = 1
                except Exception as exc:
                    value, grants, tag, ok = None, 0, None, 0
                    _count(errors, exc)
                t1 = time.monotonic()
                w, s = tag if tag is not None else (-1, -1)
                ops.append([0, rec, t0, t1, ok, w, s,
                            zlib.crc32(value) if value is not None else 0, grants])

    await asyncio.sleep(max(0.0, t_start - time.monotonic()))
    cpu0 = time.process_time()
    await asyncio.gather(*(caller_loop(c, cid) for c, cid in zip(clients, callers)))
    cpu1 = time.process_time()
    result = {
        "worker": wid,
        "ops": ops,
        "errors": errors,
        "retried": retried,
        "cpu_seconds": cpu1 - cpu0,
        "busy_until": time.monotonic(),
        "stage_seconds": _timer_samples(clients, STAGE_TIMERS, timers0),
        "sdk_counters": _counter_deltas(clients, counters0),
        "jax_loaded": "jax" in sys.modules,
    }
    _write_json(spec["result_path"], result)
    print(json.dumps({"done": len(ops)}), flush=True)

    # ---- after the window: "READBACK <file of record numbers>" -> each read
    # back once by a caller whose connections are warm; then end of input
    while True:
        line = (await stdin.readline()).decode().split()
        if not line or line[0] != "READBACK":
            break
        with open(line[1]) as fh:
            records_to_read = json.load(fh)
        rows = []  # [record, writer, seq, crc, grants, t_issue], absent where the read failed

        async def readback_loop(client, share):
            for rec in share:
                t0 = time.monotonic()
                try:
                    value, grants = await sdk_read(client, key_name(rec))
                except Exception as exc:
                    _count(errors, exc)
                    continue
                w, s = parse_tag(value) or (-1, -1)
                rows.append([rec, w, s, zlib.crc32(value) if value is not None else 0, grants, t0])

        await asyncio.gather(*(readback_loop(c, records_to_read[k::len(clients)])
                               for k, c in enumerate(clients)))
        _write_json(line[1] + ".out", rows)
        print(json.dumps({"readback": len(rows)}), flush=True)
    for c in clients:
        await c.close()


def _write_json(path: str, obj) -> None:
    with open(path + ".tmp", "w") as fh:
        json.dump(obj, fh)
    os.replace(path + ".tmp", path)


def worker_main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as fh:
        spec = json.load(fh)
    sys.path.insert(0, spec["repo"])
    asyncio.run(worker(spec))
    return 0


if __name__ == "__main__":
    sys.exit(worker_main())
