"""The deployment under test: ``ProcessCluster`` with the service started
through ``perf/service_launch.py``, and readers of the children's ``/status``.

The product is taken as shipped.  The only changes to what ``ProcessCluster``
would start: the service's ``python -m mochi_tpu.verifier.service`` becomes
``python perf/service_launch.py --perf-ctl <dir>`` (which calls the same
``main()``), and the ``--warmup ""`` pair that ``ProcessCluster`` always passes
is dropped, so the service's own default applies.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time
import urllib.request

from mochi_tpu.testing.process_cluster import ProcessCluster

ADMIN_BASE_PORT = 24000  # replica /status ports count up from here
SERVICE_MODULE = "mochi_tpu.verifier.service"


class PerfCluster(ProcessCluster):
    def __init__(self, *args, launcher: str, ctl_dir: str, service_argv=(), **kwargs):
        super().__init__(*args, **kwargs)
        self.launcher = launcher
        self.ctl_dir = ctl_dir
        self.service_argv = list(service_argv)

    async def _spawn(self, sp, env) -> None:
        if sp.index == -1 and SERVICE_MODULE in sp.argv:
            rest = sp.argv[sp.argv.index(SERVICE_MODULE) + 1:]
            i = rest.index("--warmup")
            del rest[i:i + 2]
            sp.argv = [sys.executable, self.launcher, "--perf-ctl", self.ctl_dir,
                       *rest, *self.service_argv]
        await ProcessCluster._spawn(sp, env)

    def replica_statuses(self, strategy_counters: bool = True) -> list:
        """Every replica's ``/status`` and, with ``strategy_counters``, beside it
        what its ``/metrics`` counts of a strategy of its own
        (``byzantine_report``; a second request a replica, which builds every
        timer's percentiles: ~1 s a look at 64 replicas)."""
        out = []
        for sp in self.processes:
            for j in range(len(sp.server_ids)):
                port = ADMIN_BASE_PORT + sp.index * self.n_servers + j
                status = http_json(port)
                if strategy_counters:
                    counters = http_json(port, "/metrics").get("counters", {})
                    status["strategy_counters"] = {
                        k: v for k, v in counters.items() if k.startswith(STRATEGY_COUNTERS)}
                out.append(status)
        return out

    def replica_status(self, server_id: str, wait_s: float = 0.0):
        """One replica's ``/status``, None where it does not answer within
        ``wait_s`` (it is down, or its admin shell is not up yet)."""
        sp = self.host_process[server_id]
        port = ADMIN_BASE_PORT + sp.index * self.n_servers + sp.server_ids.index(server_id)
        deadline = time.monotonic() + wait_s
        while True:
            try:
                return http_json(port)
            except OSError:
                if time.monotonic() >= deadline:
                    return None
                time.sleep(0.05)

    def service_status(self) -> dict:
        return http_json(self.service_admin_port)

    def cluster_config_path(self) -> str:
        return os.path.join(self._tmpdir.name, "cluster_config.json")

    def log_paths(self) -> list:
        return [sp.log_path for sp in [self.service_process, *self.processes] if sp is not None]


def http_json(port: int, path: str = "/status") -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return json.loads(r.read())


def chain_sum(stats: dict, key: str) -> int:
    """Sum an integer counter down a verifier_stats ``inner`` chain."""
    total = 0
    while stats:
        total += int(stats.get(key, 0))
        stats = stats.get("inner")
    return total


def service_counters(status: dict) -> dict:
    """The service's counters that the checks and the per-layer metrics read
    (``chip_smoke.py``'s selection)."""
    v = status["verifier"]  # CachingVerifier -> TpuBatchVerifier
    tpu = v["inner"]
    dev, comb = tpu["device"], tpu["comb"]
    return {
        "requests": status["requests"],
        "items": status["items"],
        "memo_hits": v["hits"],
        "memo_misses": v["misses"],
        "batches_flushed": tpu["batches_flushed"],
        "fallback_batches": chain_sum(v, "fallback_batches"),
        "device_items": dev["device_items"],
        "host_routed_items": dev["host_routed_items"],
        "min_device_items": dev["min_device_items"],
        "ready_buckets": dev["ready_buckets"],
        "failed_buckets": dev["failed_buckets"],
        "comb_ready_buckets": comb["ready_buckets"],
        "comb_failed_buckets": dev["comb_failed_buckets"],
        "registered_signers": comb["registered_signers"],
    }


# what a replica that runs a Byzantine strategy counts of it in its own
# registry (``testing/byzantine.py`` ``ByzantineReplica``: responses it changed
# and signed again, requests it swallowed); an honest replica has neither key
STRATEGY_COUNTERS = "byzantine."


def byzantine_report(status: dict) -> dict:
    """A replica's ``/status`` ``byzantine`` section (the evidence it holds
    against its peers: ``equivocations`` and ``bad_grants`` by peer,
    ``resync_bad_certificates``) and, beside it, what it says of ITSELF:
    ``strategy`` (None: it runs none), ``mutated_responses``,
    ``dropped_requests``.  Where the section names the strategy, that stands;
    today's does not, and a replica is taken to run one (``True``: unnamed)
    where its ``/metrics`` has a strategy's counters."""
    report = dict(status.get("byzantine") or {})
    if "strategy" not in report:
        acts = status.get("strategy_counters") or {}
        report["strategy"] = True if acts else None
        report["mutated_responses"] = acts.get(STRATEGY_COUNTERS + "mutated-responses", 0)
        report["dropped_requests"] = acts.get(STRATEGY_COUNTERS + "dropped-requests", 0)
    return report


def replica_counters(statuses: list) -> dict:
    """Sums over the replicas of what the checks and metrics read."""
    drain = [r["batching"].get("transport.drain-frames", {}) for r in statuses]
    return {
        "replicas": len(statuses),
        "jax_loaded": sum(1 for r in statuses if r["jax_loaded"]),
        "fallback_batches": sum(chain_sum(r["verifier"], "fallback_batches") for r in statuses),
        "remote_batches": sum(chain_sum(r["verifier"], "remote_batches") for r in statuses),
        "fsyncs": sum(int(r["storage"].get("fsyncs", 0)) for r in statuses),
        "drain_count": sum(int(d.get("count", 0)) for d in drain),
        "drain_frames": sum(float(d.get("sum", 0.0)) for d in drain),
        "storage_engines": sorted({r["storage"].get("engine") for r in statuses}),
        "fsync_policies": sorted({str(r["storage"].get("fsync")) for r in statuses}),
        "admission": sorted({str(r["overload"].get("enabled")) for r in statuses}),
        # per replica: what its last boot replayed, and its own verifier chain
        "replay": {r["server_id"]: r["storage"].get("replay") for r in statuses},
        "verifier_chains": {r["server_id"]: r["verifier"] for r in statuses},
        "byzantine": {r["server_id"]: byzantine_report(r) for r in statuses},
    }


def replica_pace(statuses: list) -> dict:
    """Per replica, what tells a slow one from its peers: transport drains and
    the frames in them, the seconds the drains took, log fsyncs and their
    milliseconds, snapshots taken, log entries."""
    out = {}
    for r in statuses:
        b, st = r["batching"], r["storage"]
        drains, lat, fsync = (b.get(k, {}) for k in
                              ("transport.drain-frames", "transport.drain-latency", "storage-fsync-ms"))
        out[r["server_id"]] = {
            "drains": int(drains.get("count", 0)), "frames": float(drains.get("sum", 0.0)),
            "drain_s": float(lat.get("sum", 0.0)),
            "fsyncs": int(fsync.get("count", 0)), "fsync_ms": float(fsync.get("sum", 0.0)),
            "snapshots": int(st.get("snapshots", 0)), "wal_entries": int(st.get("wal_entries", 0)),
        }
    return out


def pace_delta(before: dict, after: dict) -> dict:
    """``replica_pace`` over a window; a replica that was started again inside
    it (its counters began anew) reports what it counted since."""
    out = {}
    for sid, b in after.items():
        a = before.get(sid, {})
        if any(b[k] < a.get(k, 0) for k in b):
            a = {}
        d = {k: b[k] - a.get(k, 0) for k in b}
        out[sid] = {"frames": int(d["frames"]), "drains": d["drains"],
                    "drain_ms": round(1e3 * d["drain_s"] / d["drains"], 3) if d["drains"] else None,
                    "fsyncs": d["fsyncs"],
                    "fsync_ms": round(d["fsync_ms"] / d["fsyncs"], 3) if d["fsyncs"] else None,
                    "snapshots": d["snapshots"], "wal_entries": d["wal_entries"]}
    return out


# the counters of ``replica_counters`` that add up over replicas and time
ADDITIVE = ("fallback_batches", "remote_batches", "fsyncs", "drain_count", "drain_frames")


def replica_view(status) -> dict | None:
    """What a fault event's record keeps of the replica it acted on."""
    if status is None:
        return None
    counters = replica_counters([status])
    return {"store": status["store"], "storage": status["storage"], "verifier": status["verifier"],
            "counters": {k: counters[k] for k in ADDITIVE}}


def cache_entries(cache_dir: str) -> int:
    """Compiled programs in the persistent cache (one file each, flat)."""
    try:
        return sum(1 for e in os.scandir(cache_dir) if e.is_file())
    except FileNotFoundError:
        return 0


_LOG_RECORD = re.compile(r"^\d{4}-\d\d-\d\d \S+ (\S+) (?:ERROR|CRITICAL) (.*)$")


def log_errors(paths) -> dict:
    """ERROR and CRITICAL records in the children's logs, by logger and message."""
    counts: dict = {}
    for path in paths:
        try:
            with open(path, errors="replace") as fh:
                for m in filter(None, map(_LOG_RECORD.match, fh)):
                    what = f"{m.group(1)} {m.group(2)}"[:160]
                    counts[what] = counts.get(what, 0) + 1
        except OSError:
            pass
    return counts
