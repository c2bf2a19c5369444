"""Names of the verifier service's stage timers and host spans, and the one
context manager that ticks both.

A *timer* lives in a ``utils.metrics.Metrics`` registry (one per
``VerifierService``, served as ``stages`` in its ``/status``); a *span* is the
same boundary on the profiler's clock (``obs/hostspan.py``: a no-op until the
service installs ``jax.profiler.TraceAnnotation``).  Each name is written
once, here; docs/OPERATIONS.md "Verifier service stages" and PERF.md section 3
say who reads which.  Importing this module imports no JAX.
"""

from __future__ import annotations

import time
from typing import Optional

from ..obs import hostspan

# ---- timers (seconds), one tick per RPC, call, chunk, launch or build
SERVICE_RPC = "service.rpc"  # envelope in hand -> sealed reply, awaited verify included
MEMO_LOOKUP = "service.memo-lookup"  # CachingVerifier's synchronous key-build + lookup loop
MEMO_SETTLE = "service.memo-settle"  # ...and its synchronous stretch after the answer: verdicts, inserts, evictions
QUEUE_WAIT = "verifier.queue-wait"  # oldest item of a chunk enqueued -> its backend started
RESOLVE_WAIT = "verifier.resolve-wait"  # a chunk's backend returned -> its calls resolved on the loop
FLUSH_HOST = "verifier.flush-host"  # one backend call routed to the host engine
FLUSH_DEVICE = "verifier.flush-device"  # one backend call routed to the device
PREPARE = "verifier.prepare"  # host packing of one launch
DISPATCH = "verifier.dispatch"  # device_put + enqueue of one launch
READBACK = "verifier.readback"  # the blocking np.asarray of one launch
BUILD = "verifier.build"  # one device program built (compile or cache load, plus one run)
GC = "service.gc"  # one pass of the collector in the service process
# ---- counter and histogram
MEMO_ITEMS = "service.memo-items"  # items through the MEMO_LOOKUP loops
FLUSH_ITEMS = "verifier.flush-items"  # items per flushed chunk
FLUSH_ITEMS_BOUNDS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)  # the bucket edges
CALLS_PER_FLUSH = "verifier.calls-per-flush"  # verify_batch calls a flushed chunk resolved
CALLS_PER_FLUSH_BOUNDS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)

# ---- spans: constants, all under one prefix
SPAN_PREFIX = "mochi."
SPAN_RPC_ADMIT = "mochi.service.rpc.admit"  # _handle's synchronous head
SPAN_RPC_REPLY = "mochi.service.rpc.reply"  # _handle's synchronous tail; wait_us = head to here
SPAN_TICK = "mochi.service.tick"  # once a second on the loop thread: loop_cpu_us, epoch_us
SPAN_MEMO = "mochi.verifier.memo"  # items
SPAN_MEMO_SETTLE = "mochi.verifier.memo-settle"  # items: the call's misses
SPAN_CHUNK = "mochi.verifier.chunk"  # executor thread, one flushed chunk: items, wait_us
SPAN_RESOLVE = "mochi.verifier.resolve"  # loop thread, one flushed chunk: items, calls, wait_us
SPAN_FLUSH = "mochi.verifier.flush"  # one backend call: items, route, bucket, epoch_us
SPAN_HOST_VERIFY = "mochi.verifier.host_verify"
SPAN_PREPARE = "mochi.verifier.prepare"
SPAN_DISPATCH = "mochi.verifier.dispatch"
SPAN_READBACK = "mochi.verifier.readback"
SPAN_BUILD = "mochi.verifier.build"  # bucket, program
SPAN_GC = "mochi.gc"


class stage:
    """``with stage(metrics, TIMER, SPAN, **args):`` — one timer tick and one
    profiler span around a synchronous section.  ``lock`` guards the tick
    where several executor threads share the timer (a ``Timer`` is not
    thread-safe; the loop thread needs none)."""

    __slots__ = ("_timer", "_span", "_lock", "_t0")

    def __init__(self, metrics, timer: str, span: str, lock=None, **args) -> None:
        self._timer = metrics.timers[timer]
        self._span = hostspan.span(span, **args)
        self._lock = lock

    def __enter__(self) -> "stage":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> Optional[bool]:
        seconds = time.perf_counter() - self._t0
        if self._lock is None:
            self._timer.record(seconds)
        else:
            with self._lock:
                self._timer.record(seconds)
        self._span.__exit__(exc_type, exc, tb)
        return None
