"""Names of a replica's re-hydration (``MochiReplica.resync``) stage timers,
spans and report keys, and the one object that ticks all three.

By ``verifier/stages.py``'s pattern: a *timer* lives in the replica's
``utils.metrics.Metrics`` registry (``/metrics``), a *span* is the same
boundary in the replica's ``obs/trace.py`` ring (``/trace``; ``t0`` is epoch
seconds, so the rings of several processes merge on one clock), and the
*report* is what ``/status`` shows as ``storage.resync`` beside
``storage.replay``.  Each name is written once, here; docs/OPERATIONS.md §4i
and PERF.md section 3 say who reads which.  Unlike a host span of the verifier
service, a span here is recorded after the fact, so it may cross an ``await``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional

from ..obs import trace as obs_trace

# ---- timers (seconds): one tick a run, a round trip or a page, as stated
RESYNC = "replica.resync"  # one whole run, up to its flush
RESYNC_CONFIG = "replica.resync-config"  # the two _CONFIG_ passes of a run: one tick a run that made them
RESYNC_DIGEST = "replica.resync-digest"  # one digest round trip (shard rollups, or a page of key digests)
RESYNC_DIGEST_LOCAL = "replica.resync-digest-local"  # one walk of this replica's OWN digests: its shard rollups, or its key digests of the shards that differ (one each a peer)
RESYNC_PULL = "replica.resync-pull"  # one entry-page round trip, request sent -> page decoded
RESYNC_VERIFY = "replica.resync-verify"  # one page: this pull's awaits of its certificates' verdicts
RESYNC_APPLY = "replica.resync-apply"  # one page: store.apply_sync_entry over its verified entries
RESYNC_FLUSH = "replica.resync-flush"  # the run's one storage.flush()
SYNC_SERVE = "replica.sync-serve"  # serving side: one SyncRequest or SyncDigestRequest answered
# ---- counters of the serving side
SYNC_PAGES_SERVED = "replica.sync-pages-served"
SYNC_ENTRIES_SERVED = "replica.sync-entries-served"

# ---- spans: constants, all under one prefix
SPAN_PREFIX = "mochi.replica.resync."
SPAN_DIGEST = "mochi.replica.resync.digest"  # peer, entries (digests in the page)
SPAN_DIGEST_LOCAL = "mochi.replica.resync.digest-local"  # peer, entries (own digests walked)
SPAN_PULL = "mochi.replica.resync.pull"  # peer, entries
SPAN_VERIFY = "mochi.replica.resync.verify"  # peer, entries
SPAN_APPLY = "mochi.replica.resync.apply"  # peer, entries
SPAN_FLUSH = "mochi.replica.resync.flush"

# ---- the report's stage keys (milliseconds)
STAGE_KEYS = ("config_ms", "digest_ms", "digest_local_ms", "pull_ms", "verify_ms",
              "verify_wait_ms", "apply_ms", "flush_ms")
# what the digest stage decided, summed over the peers: owned shards a peer has a
# rollup for, and how many of them equal this replica's; owned keys of the shards
# that differ that the peer named, and how many of them equal this replica's (the
# rest, ``keys_compared`` - ``keys_matched``, are asked for: ``entries_pulled``)
DIGEST_KEYS = ("shards_compared", "shards_matched", "keys_compared", "keys_matched")
COUNTER_KEYS = ("pages", "digest_pages", "entries_pulled", "entries_adopted",
                "entries_redundant", "entries_unowned", "bad_certificates", "bytes_pulled",
                *DIGEST_KEYS)
PEER_KEYS = ("pages", "entries", "adopted", "abandoned")


class ResyncRun:
    """One run of ``MochiReplica.resync``: its report, and ``tick``, which
    records one stage's seconds in the timer, the report and the span ring.

    ``full`` (a run that names no keys: the boot's re-hydration, or a
    reconfiguration's) records its spans whatever the sampling says: a
    recovery is the trace an operator asks for afterwards, and it is a few
    hundred spans.  A targeted run (a client's nudge) records only under a
    sampled context, like any other traffic.

    ``verify_wait_ms`` is on the wall clock: the time in which EVERY pull
    still alive stood in an await of a verdict, so nothing of the run could
    be decoded or applied meanwhile.  ``verify_ms``, ``pull_ms`` and
    ``digest_ms`` are summed over pulls that overlap, and can exceed ``ms``.
    """

    def __init__(self, metrics, tracer: obs_trace.Tracer, full: bool) -> None:
        self.metrics = metrics
        self.tracer = tracer
        self.full = full
        ctx = tracer.mint()
        if ctx is None and full:
            ctx = obs_trace.TraceContext(
                tracer.new_span_id(), tracer.new_span_id(), None, False
            )
        self.ctx = ctx
        self.report: Dict[str, object] = {
            "full": full, "complete": False,
            # the run's start on the ``obs/trace.py`` clock: a complete run has
            # caught up with what a quorum of the peers held THEN, no later
            "began_epoch_us": time.time_ns() // 1000,
            "ms": 0.0,
            **{k: 0.0 for k in STAGE_KEYS}, **{k: 0 for k in COUNTER_KEYS},
            "peers": 0, "by_peer": {},
        }
        self._t0 = time.perf_counter()
        self._alive = 0  # pulls between begin_pull and end_pull
        self._waiting = 0  # of those, the ones inside a verdict's await
        self._all_waiting_since: Optional[float] = None

    def peer(self, sid: str) -> Dict[str, int]:
        by_peer = self.report["by_peer"]
        if sid not in by_peer:
            by_peer[sid] = {k: 0 for k in PEER_KEYS}
            self.report["peers"] = len(by_peer)
        return by_peer[sid]

    def count(self, key: str, n: int = 1) -> None:
        self.report[key] += n

    def tick(self, timer: str, span: Optional[str], key: str, wall0: float,
             seconds: float, **args) -> None:
        """``seconds`` of one stage that began at ``wall0`` (epoch seconds)."""
        self.metrics.timers[timer].record(seconds)
        self.report[key] += seconds * 1e3
        if span is not None and (self.full or self.tracer.wants(self.ctx)):
            self.tracer.record(span, self.ctx, wall0, seconds, args=args, force=self.full)

    # ---- the wall-clock verify wait
    def begin_pull(self) -> None:
        self._alive += 1

    def end_pull(self) -> None:
        self._alive -= 1
        self._all_waiting()

    @contextlib.contextmanager
    def waiting(self):
        """``with run.waiting():`` around a pull's await of a verdict."""
        self._waiting += 1
        self._all_waiting()
        try:
            yield
        finally:
            if self._all_waiting_since is not None:
                self.report["verify_wait_ms"] += (
                    time.perf_counter() - self._all_waiting_since
                ) * 1e3
                self._all_waiting_since = None
            self._waiting -= 1

    def _all_waiting(self) -> None:
        if self._all_waiting_since is None and 0 < self._alive == self._waiting:
            self._all_waiting_since = time.perf_counter()

    def finish(self, complete: bool) -> Dict[str, object]:
        self.report["complete"] = complete
        self.report["ms"] = (time.perf_counter() - self._t0) * 1e3
        return self.report

