#!/usr/bin/env python3
"""One run of one benchmark cell: boot the deployment as shipped, load it,
drive it closed loop for the window, decide ``correct``, print the result.

    python perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``perf/configs/<config>.json``) under a traffic mix
(``perf/traffic/<traffic>.json``).  A per-layer metric is a file in
``perf/layer_metrics/``.  A traffic mix may state a fault schedule
(``perf/schedule.py``), each of whose verbs is a file in ``perf/faults/``; it
runs inside the window, and a mix without one takes the control flow it always
took.  A configuration may state Byzantine members (``byzantine``: server id ->
strategy of ``mochi_tpu/testing/byzantine.py``): they boot so, count against
``f`` in the schedule, and the reference holds the run to it
(``check_byzantine``).  Nothing here names a cell, a mix, a metric or a verb.

Processes: replicas are ``python -m mochi_tpu.server`` children, ONE verifier
service owns the chip (started through ``perf/service_launch.py``, which calls
the product's ``main()`` unchanged), the load comes from the cell's
``generator_processes`` workers (``perf/ycsb.py``), and this process stays off
JAX.  No ``MOCHI_*`` variable and no ``--warmup`` is set.

Without a TPU the run exits non-zero and prints no result.  ``--rehearse``,
under an exported ``JAX_PLATFORMS=cpu``, runs the cell at a tiny shape on the
CPU to rehearse the control flow; it prints no device metric.

The last line of standard output is the result object; everything before it
is commentary (the numbers compared for ``correct`` beside their limits,
medians, p99s, sample counts).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse
import asyncio
import collections
import json
import math
import os
import shutil
import sys
import tempfile
import zlib

PERF = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(PERF)
for _p in (REPO, PERF):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import layer_reader  # noqa: E402
import treestate  # noqa: E402

# the profiler's share of the window: its end, or from the command of a
# scheduled verb that brings an end-to-end metric (``trace_from_s``)
TRACE_SECONDS = 5.0
BAD_WRITE2S = 4
WARM_HEADROOM = 2  # flushes pile up: the largest seen was 1.4 x threads x quorum items
READY_TIMEOUT_S = 1150.0  # a first run compiles; the contract allows it 1200 s
# a run still going at 1200 s is ended from outside and leaves no word of where
# it stood (the commentary is on standard output, which nobody keeps): it ends
# itself before that, with the last it said on standard error
RUN_LIMIT_S = 1080.0
FAILED_LATENCY_MS = 1e9  # printed where a percentile falls on a failed operation
# an update acknowledged this long before a kill is in the killed replica's log
# (reference.check_direct); the longest update of any run so far took 0.9 s
DIRECT_SLACK_S = 2.0


class RunFailure(Exception):
    """The run cannot produce a result (no chip, a child died, load failed)."""


SAID = collections.deque(maxlen=8)  # the commentary's last lines, for a run that fails


def say(*parts) -> None:
    SAID.append(" ".join(map(str, parts))[:400])
    print("[perf]", *parts, flush=True)


async def within(work, limit_s: float):
    """``await work``, given up after ``limit_s``: the run's own ``finally``
    stops what it started, and the failure says how far the run had come."""
    try:
        return await asyncio.wait_for(work, limit_s)
    except asyncio.TimeoutError:
        raise RunFailure(f"still running {time.monotonic() - T_PROCESS_START:.0f}s after the "
                         f"process began (the limit is {RUN_LIMIT_S:.0f}s)") from None


# ------------------------------------------------------------------ the data


def load_cell(root: str, workload: str, faults_dir: str | None = None) -> dict:
    """Resolve a cell by name: its entry, configuration and traffic mix (and,
    where the mix states a fault schedule, the schedule's verbs: files in
    ``faults/`` beside ``traffic/``, or in ``faults_dir``, where a control
    keeps broken ones)."""
    import schedule
    import ycsb

    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise RunFailure(f"no workload {workload!r} in BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, config_entry["file"])) as fh:
        config = json.load(fh)
    # the data directories lie beside the one that holds the configuration
    data_dir = os.path.join(root, os.path.dirname(os.path.dirname(config_entry["file"])))
    traffic = ycsb.load_traffic(os.path.join(data_dir, "traffic", cell["traffic"] + ".json"))
    try:
        verbs = (schedule.validate(traffic["faults"], faults_dir or os.path.join(data_dir, "faults"))
                 if "faults" in traffic else [])
    except schedule.ScheduleError as exc:
        raise RunFailure(f"traffic {cell['traffic']!r}: {exc}") from exc
    return {"bench": bench, "cell": cell, "config": config, "traffic": traffic, "verbs": verbs,
            "layer_dir": os.path.join(data_dir, "layer_metrics")}


def metric_applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def read_layer_metrics(layer_dir: str, bench: dict, workload: str, snap: dict) -> dict:
    """Each per-layer metric of this cell, from its own reader file.  A metric
    with no ``workloads`` key belongs to every cell that reports the end-to-end
    metric it moves.  A reader that finds nothing to read returns None and its
    metric is left out."""
    reported = {m["name"] for m in bench["end_to_end"] if metric_applies(m, workload)}
    out = {}
    for entry in bench["per_layer"]:
        if not (workload in entry["workloads"] if "workloads" in entry
                else entry["moves"] in reported):
            continue
        path = os.path.join(layer_dir, entry["name"] + ".py")
        mod = layer_reader.load(path)
        for key in ("name", "unit", "layer", "moves", "source"):
            if getattr(mod, key.upper()) != entry[key]:
                raise RunFailure(f"{path}: {key.upper()} differs from BENCHMARK.json")
        value = mod.read(snap)
        if value is not None and not math.isnan(value):
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


# ------------------------------------------------------------------- the run


def gate(rehearse: bool) -> None:
    pinned = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if rehearse and pinned != "cpu":
        raise RunFailure("--rehearse is the CPU rehearsal: export JAX_PLATFORMS=cpu")
    if not rehearse and pinned and "tpu" not in pinned.split(","):
        raise RunFailure(f"JAX_PLATFORMS={pinned}: no TPU for the benchmark "
                         "(the CPU rehearsal is --rehearse)")


def build_native() -> None:
    from mochi_tpu.native import get_hbatch, get_mcode

    built = {"mcode": get_mcode() is not None, "hbatch": get_hbatch() is not None}
    if not all(built.values()):
        raise RunFailure(f"native modules did not build here: {built}")


class Workers:
    """The generator processes: spawn, wait for the load, start, collect."""

    def __init__(self, script: str, out_dir: str):
        self.script = script
        self.out_dir = out_dir
        self.procs = []
        self.specs = []

    async def spawn(self, specs: list) -> None:
        env = dict(os.environ, JAX_PLATFORMS="cpu")  # a generator never takes the chip
        for spec in specs:
            path = os.path.join(self.out_dir, f"worker-{spec['worker']}.spec.json")
            with open(path, "w") as fh:
                json.dump(spec, fh)
            log = open(os.path.join(self.out_dir, f"worker-{spec['worker']}.log"), "ab")
            try:
                proc = await asyncio.create_subprocess_exec(
                    sys.executable, self.script, path, env=env,
                    stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE, stderr=log)
            finally:
                log.close()
            self.procs.append(proc)
            self.specs.append(spec)

    async def _line(self, i: int, timeout_s: float) -> dict:
        proc = self.procs[i]
        line = await asyncio.wait_for(proc.stdout.readline(), timeout_s)
        if not line:
            rc = await proc.wait()
            raise RunFailure(f"generator process {i} ended early (rc={rc}); see its log")
        return json.loads(line)

    async def lines(self, timeout_s: float) -> list:
        return await asyncio.gather(*(self._line(i, timeout_s) for i in range(len(self.procs))))

    def go(self, t_start: float, seconds: float) -> None:
        for proc in self.procs:
            proc.stdin.write(f"GO {t_start!r} {seconds!r}\n".encode())

    async def read_back(self, records: list) -> dict:
        """Every record read once, by callers whose connections are warm:
        {record: (writer, seq, crc, grants, t_issue)}."""
        paths = []
        for i, proc in enumerate(self.procs):
            path = os.path.join(self.out_dir, f"worker-{i}.readback.json")
            with open(path, "w") as fh:
                json.dump(records[i::len(self.procs)], fh)
            proc.stdin.write(f"READBACK {path}\n".encode())
            paths.append(path + ".out")
        await self.lines(timeout_s=600.0)
        out = {}
        for path in paths:
            with open(path) as fh:
                out.update({row[0]: tuple(row[1:]) for row in json.load(fh)})
        return out

    def results(self) -> list:
        out = []
        for spec in self.specs:
            with open(spec["result_path"]) as fh:
                out.append(json.load(fh))
        return out

    async def close(self) -> None:
        for proc in self.procs:
            if proc.returncode is None:
                try:
                    proc.stdin.close()
                except (OSError, RuntimeError):
                    pass
        for proc in self.procs:
            try:
                await asyncio.wait_for(proc.wait(), 20.0)
            except asyncio.TimeoutError:
                proc.kill()
                await proc.wait()


async def programs_ready(pc, ctl_dir: str, sizes: list, quiet_s: float = 45.0,
                         timeout_s: float = 1000.0) -> str:
    """Wait until the service has a program of each kind for every offered
    size: its ``/status`` lists, for the ladder and for the comb, a ready
    bucket in [size, 2 x size).  Should the product stop naming its buckets
    that way, fall back to quiet: no program built for ``quiet_s`` seconds
    and the idle service under a quarter of a core."""
    import cluster as cl
    from service_launch import request

    if not sizes:
        return "not needed"
    deadline = time.monotonic() + timeout_s
    built, changed = -1, time.monotonic()
    cpu = pc.cpu_seconds().get("verifier-service", 0.0)
    while time.monotonic() < deadline:
        c = cl.service_counters(pc.service_status())
        if all(any(n <= b < 2 * n for b in ready) for n in sizes
               for ready in (c["ready_buckets"], c["comb_ready_buckets"])):
            return f"ready (ladder {c['ready_buckets']}, comb {c['comb_ready_buckets']})"
        now_built = request(ctl_dir, {"op": "stats"})["programs_built"]
        if now_built != built:
            built, changed = now_built, time.monotonic()
        await asyncio.sleep(1.0)
        now_cpu = pc.cpu_seconds().get("verifier-service", 0.0)
        if time.monotonic() - changed > quiet_s and now_cpu - cpu < 0.25:
            return "presumed built (the service went quiet)"
        cpu = now_cpu
    raise RunFailure(f"the service was still building programs {timeout_s}s after the load")


def replay_items(shape: dict, verbs: list) -> int:
    """The signatures a restarted replica's replay can bring the service that
    its memo no longer holds (0: none).  A replica stores ``recordcount x rf /
    replicas`` certificates of a quorum of grants each; where the schedule
    restarts one and they outnumber the memo the configuration STATES
    (``memo_items``; the service's ``/status`` does not give its capacity), the
    memo has dropped some, and which is not the harness's to know.  A
    configuration that states no memo has no replay reach."""
    memo = shape.get("memo_items")
    if memo is None or not any(getattr(v, "RESTARTS", False) for v in verbs):
        return 0
    stored = shape["recordcount"] * shape["rf"] // shape["replicas"] * shape["quorum"]
    return stored if stored > memo else 0


async def traced(ctl_dir: str, trace_dir: str, work, t_start: float = 0.0) -> tuple:
    """The service's profiler around ``await work()``: (what ``work``
    returned, the trace: where it lies, the seconds traced, when it began
    after ``t_start``, and what the profiler took to start and to stop).  The
    requests run in a thread: a stop can take most of a minute, and the fault
    schedule and the load's commentary share this loop."""
    from service_launch import request

    t0 = time.monotonic()
    t_a = await asyncio.to_thread(request, ctl_dir, {"op": "trace_start", "dir": trace_dir})
    t1 = time.monotonic()
    out = await work()
    t2 = time.monotonic()
    t_b = await asyncio.to_thread(request, ctl_dir, {"op": "trace_stop"}, 300.0)
    return out, {"dir": trace_dir, "seconds": t_b["stopped_monotonic"] - t_a["started_monotonic"],
                 # one monotonic clock for every process of the machine
                 "started_s": t_a["started_monotonic"] - t_start,
                 "profiler_s": [t1 - t0, time.monotonic() - t2]}


def warm_reach(lowest: int, ready: set, quorum: int, loaders: int, writers: int,
               replay: int = 0) -> int:
    """The largest batch to offer the service before the load (0: none).
    ``lowest`` is the routing's crossover, ``ready`` the buckets it has both
    programs for; ``writers`` callers update in the window, ``loaders`` write
    the load.  Either can pile up ``WARM_HEADROOM`` certificates each.
    ``replay`` signatures (``replay_items``) reach the service a full request
    at a time, thinned by its memo to any size, so they reach every bucket as
    the callers of a large cluster do."""
    if not ready or lowest <= 0:
        return 0
    if WARM_HEADROOM * writers * quorum >= lowest or replay >= lowest:
        return max(ready)
    if WARM_HEADROOM * loaders * quorum >= lowest:
        return min(WARM_HEADROOM * loaders * quorum, max(ready))
    return 0


def trace_from_s(events: list, seconds: float, trace_len: float) -> float:
    """Where in the window ``--trace 1`` starts the profiler, for ``trace_len``
    seconds: at the command of the first scheduled verb that brings an
    end-to-end metric (the recovery is what such a cell is about), and in a
    cell without one at the window's last ``trace_len`` seconds."""
    timed = [ev["at_s"] for ev in events if getattr(ev["verb"], "END_TO_END", None)]
    return float(min(timed)) if timed else seconds - trace_len


def stated_members(shape: dict) -> dict:
    """The configuration's ``byzantine`` map, {server id: strategy} (empty:
    every member is honest), refused before anything boots where it names a
    server the shape lacks or a strategy the product's catalog lacks."""
    from mochi_tpu.testing.byzantine import make_strategy

    members = shape.get("byzantine") or {}
    if not isinstance(members, dict):
        raise RunFailure(f"'byzantine' is a map of server id to strategy, not {members!r}")
    servers = {f"server-{i}" for i in range(shape["replicas"])}
    for sid, strategy in members.items():
        if sid not in servers:
            raise RunFailure(f"'byzantine' names {sid!r}: the configuration has "
                             f"server-0 to server-{shape['replicas'] - 1}")
        try:
            make_strategy(strategy)
        except (ValueError, TypeError) as exc:
            raise RunFailure(f"'byzantine' gives {sid} {strategy!r}: {exc}") from exc
    return dict(members)


def sdk_counters(gen: list) -> dict:
    """The generators' ``sdk_counters`` (``ycsb._counter_deltas``) added up
    over the worker processes: what each SDK counter gained over the window
    (``sum``), in how many callers it moved (``callers``), and each caller's
    marks of suspicion against each replica (``marks``)."""
    total, callers = collections.Counter(), collections.Counter()
    marks = collections.defaultdict(list)
    for g in gen:
        sdk = g.get("sdk_counters", {})  # a worker of a control may keep none
        total.update(sdk.get("sum", {}))
        callers.update(sdk.get("callers", {}))
        for sid, per_caller in sdk.get("marks", {}).items():
            marks[sid] += per_caller
    return {"sum": dict(total), "callers": dict(callers),
            "marks": {sid: sorted(v) for sid, v in sorted(marks.items())}}


def replica_processes(config: dict) -> int:
    want = config["replica_processes"]
    if want == "cores-2":
        want = max(1, (os.cpu_count() or 1) - 2)
    return max(1, min(int(want), config["replicas"]))


async def run_cell(args, data: dict, launcher: str, worker_script: str, boot=None) -> dict:
    """Everything between process start and the result.  ``launcher`` and
    ``worker_script`` are the service launcher and the generator worker; the
    tests under ``perf/tests`` put broken ones in their place, and with
    ``boot`` a control starts the cluster otherwise than the configuration
    states (keywords of ``PerfCluster`` that replace the stated ones)."""
    import cluster as cl
    import probe
    import reference as ref
    import schedule
    import ycsb
    from service_launch import request

    cell, config, traffic = data["cell"], data["config"], data["traffic"]
    rehearse = args.rehearse
    shape = dict(config, **config["rehearsal"]) if rehearse else config
    n, rf, records = shape["replicas"], shape["rf"], shape["recordcount"]
    threads = shape["threads"]
    gen_procs = min(shape["generator_processes"], threads)
    seed, seconds = args.seed, float(args.seconds)
    members = stated_members(shape)
    events = []
    if "faults" in traffic:
        if rehearse:  # the tiny shape packs its replicas, and a kill takes a whole process
            shape = dict(shape, replica_processes=n)
        procs = replica_processes(shape)
        try:
            events = schedule.bind(traffic["faults"], data["verbs"], seed, seconds, n, shape["f"],
                                   {f"server-{i}": i % procs for i in range(n)}, members)
        except schedule.ScheduleError as exc:
            raise RunFailure(f"traffic {cell['traffic']!r}: {exc}") from exc

    out_dir = os.path.join(PERF, "out", f"{cell['name']}-{seed}")
    shutil.rmtree(out_dir, ignore_errors=True)
    ctl_dir = os.path.join(out_dir, "ctl")
    os.makedirs(ctl_dir)
    removed = [k for k in os.environ if k.startswith("MOCHI_")]
    for k in removed:
        del os.environ[k]  # the product as shipped: nothing tuned from outside
    if removed:
        say("dropped from the environment:", sorted(removed))
    tmp_dir = tempfile.gettempdir()
    uds = len(tmp_dir) < 60  # AF_UNIX paths hold ~100 characters
    say(f"cell {cell['name']}: n={n} rf={rf} records={records} threads={threads} "
        f"generator_processes={gen_procs} transport={'uds' if uds else 'tcp'} "
        f"seed={seed} seconds={seconds} trace={args.trace} rehearse={rehearse}")

    # what the configuration states, applied (and compared with what the
    # replicas report, below); the shipped files state the product's defaults
    stated = {"storage_engine": shape["storage_engine"], "wal_fsync": shape["wal_fsync"],
              "admission": shape["admission"] == "on", "byzantine": members or None}
    if boot:
        say(f"CONTROL: the cluster boots with {boot}, whatever the configuration states")
    pc = cl.PerfCluster(
        n_servers=n, rf=rf, n_processes=replica_processes(shape),
        uds=uds, verifier="service", service_backend="tpu",
        admin_base_port=cl.ADMIN_BASE_PORT, storage_dir=os.path.join(out_dir, "storage"),
        **dict(stated, **(boot or {})),
        seed=seed, ready_timeout_s=READY_TIMEOUT_S,
        # every program the service builds is cached, however quick its
        # compile, so "the window added no cache entry" is exact
        env={"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"},
        launcher=launcher, ctl_dir=ctl_dir,
        service_argv=["--warmup", "16"] if rehearse else [],
    )
    workers = Workers(worker_script, out_dir)
    result: dict = {}
    fault_task = profiler_warm = None
    try:
        t0 = time.monotonic()
        await pc.start()
        say(f"cluster READY in {time.monotonic() - t0:.1f}s "
            f"({pc.n_processes} replica processes, 1 service)")
        status = pc.service_status()
        dev = status.get("device")
        if dev is None:
            raise RunFailure("the service reports no device")
        want_platform = "cpu" if rehearse else "tpu"
        if dev["platform"] != want_platform:
            raise RunFailure(f"the service runs on {dev['platform']!r}, not {want_platform!r}")
        quorum = pc.config.quorum
        if quorum != shape["quorum"] or pc.config.f != shape["f"]:
            raise RunFailure(f"cluster has f={pc.config.f} quorum={quorum}, the "
                             f"configuration states f={shape['f']} quorum={shape['quorum']}")
        cache_dir = dev["compile_cache_dir"]
        say(f"service warm-up {dev['warmup_seconds']}s on {dev['platform']} "
            f"{dev['device_kind']!r} x{dev['n_devices']}, cache {cache_dir}")
        request(ctl_dir, {"op": "stats"})  # starts the count of programs built

        # ---- warm every shape the window can use.  The service builds a
        # program lazily, in a background thread, for the first batch of a
        # size it has none for, and a build inside the window halves the
        # cell's rate.  Where this cell's traffic can reach the routing's
        # crossover at all (every caller's certificate in flight at once,
        # doubled for flushes that pile up behind a slow one), no size up to
        # the largest ready bucket is safe: flushes of over 2,048 items were
        # seen on the chip.  Offer all of them now, so that the building
        # overlaps the load and is over before the window.  A mix that
        # updates nothing sends the service nothing: only the LOAD's writers
        # can reach the crossover, so offer the sizes up to their reach, and
        # what the load starts building is waited for before the window.
        counters = cl.service_counters(status)
        both_ready = set(counters["ready_buckets"]) & set(counters["comb_ready_buckets"])
        warmed = {"sizes": [], "mismatches": 0}
        lowest = counters["min_device_items"]
        reach = warm_reach(lowest, both_ready, quorum, shape["load_threads"],
                           threads if float(traffic["updateproportion"]) > 0 else 0,
                           replay_items(shape, data["verbs"]))
        if reach:
            warmed = await probe.warm_device_buckets(pc, seed, lowest, reach)
            say(f"offered warm-up batches of {warmed['sizes']} items")

        # ---- load, by the generator processes
        callers = list(range(threads))
        t0 = time.monotonic()
        await workers.spawn([
            {
                "repo": REPO, "worker": w, "seed": seed, "records": records,
                "cluster_config": pc.cluster_config_path(), "traffic": traffic,
                "callers": callers[w::gen_procs],
                "load_callers": max(1, shape["load_threads"] // gen_procs),
                "load_records": list(range(records))[w::gen_procs],
                "result_path": os.path.join(out_dir, f"worker-{w}.result.json"),
            }
            for w in range(gen_procs)
        ])
        # the first trace of a process that holds device events takes the
        # profiler 44-65 s to stop, the second 14-17 s, a later one under a
        # second, whatever is in them (PERF.md section 7, third), and the
        # service stalls meanwhile.  A traced run takes the first now, beside
        # the load, so that neither of its two traces pays it after the window
        profiler_warm = (asyncio.ensure_future(traced(
            ctl_dir, os.path.join(out_dir, "trace-warm"),
            # (items of its own: the memo must not answer the probe after the window)
            lambda: probe.device_probe(pc, ~seed, min(both_ready))))
            if args.trace and both_ready else None)
        loaded = await workers.lines(timeout_s=900.0)
        if profiler_warm is not None:
            report, warm = await profiler_warm
            warmed["mismatches"] += report["mismatches"]
            say("profiler warmed beside the load: the trace of one probe took "
                "{:.1f}s to start and {:.1f}s to stop".format(*warm["profiler_s"]))
        n_load_failed = sum(l["n_load_failed"] for l in loaded)
        say(f"loaded {sum(l['loaded'] for l in loaded)} records in "
            f"{time.monotonic() - t0:.1f}s, {n_load_failed} failed")
        if n_load_failed:
            raise RunFailure(f"load failed: {[l['load_failed'] for l in loaded]}")

        # ---- the window
        def snapshot(name: str) -> dict:
            cpu = pc.cpu_seconds()
            status = pc.service_status()
            # whether a replica runs a strategy of its own is asked once, after
            # everything; how often it acted, at the window's ends where one is stated
            statuses = pc.replica_statuses(strategy_counters=bool(members) or name == "final")
            if args.keep:  # every replica's whole /status, as it stood
                with open(os.path.join(out_dir, f"status-{name}.json"), "w") as fh:
                    json.dump({"service": status, "replicas": statuses}, fh)
            return {
                "service": cl.service_counters(status),
                # the stage timers and histograms (verifier/stages.py), as they are
                "service_stages": status.get("stages"),
                "replicas": cl.replica_counters(statuses),
                "pace": cl.replica_pace(statuses),
                "replica_cpu": sum(v for k, v in cpu.items() if k.startswith("proc-")),
                "service_cpu": cpu.get("verifier-service", 0.0),
                "cache_entries": cl.cache_entries(cache_dir),
                "stats": request(ctl_dir, {"op": "stats"}),
            }

        t0 = time.monotonic()
        how = await programs_ready(pc, ctl_dir, warmed["sizes"])
        say(f"programs for the offered sizes {how} after {time.monotonic() - t0:.1f}s more")
        def observe(server_id: str) -> dict:
            """A fault event's look at the service and at its replica (None
            while its process is down)."""
            sp = pc.process_for(server_id)
            up = sp.proc.returncode is None
            status = pc.service_status()
            return {"service": cl.service_counters(status), "service_stages": status.get("stages"),
                    "replica": cl.replica_view(pc.replica_status(server_id, 10.0) if up else None),
                    "process_cpu": sp.cpu_seconds() if up else None}

        def pids() -> dict:  # asked at either end: a restarted replica has another
            return {"harness": os.getpid(), "service": pc.service_process.proc.pid,
                    **{f"replicas-{sp.index}": sp.proc.pid for sp in pc.processes},
                    **{f"generator-{i}": p.pid for i, p in enumerate(workers.procs)}}

        facts = treestate.static_facts(REPO, out_dir, tmp_dir, "uds" if uds else "tcp",
                                       args.natives_before)
        say("tree:", json.dumps(facts))
        before = snapshot("before")
        placed = treestate.places(pids())
        t_start = time.monotonic() + 1.0
        t_end = t_start + seconds
        workers.go(t_start, seconds)
        setup_s = t_start - T_PROCESS_START
        fault_task = (asyncio.ensure_future(schedule.run(pc, events, t_start, observe))
                      if events else None)
        traces = {}
        if args.trace:
            trace_len = min(TRACE_SECONDS, seconds / 2)
            t_from = t_start + trace_from_s(events, seconds, trace_len)
            await asyncio.sleep(max(0.0, t_from - time.monotonic()))
            _, traces["window"] = await traced(
                ctl_dir, os.path.join(out_dir, "trace-window"),
                lambda: asyncio.sleep(max(0.0, min(t_from + trace_len, t_end) - time.monotonic())),
                t_start)
        await asyncio.sleep(max(0.0, t_end - time.monotonic()))
        done = await workers.lines(timeout_s=ycsb.SDK_TIMEOUT_S * 3)
        moved = treestate.window_delta(placed, treestate.places(pids()))
        say("processes over the window:", json.dumps(moved))
        try:
            fault_records = await fault_task if fault_task is not None else []
        except Exception as exc:
            raise RunFailure(f"the fault schedule did not run to its end: {exc!r}") from exc
        for rec in fault_records:
            say(f"fault {rec['do']} {rec['server_id']}: started {rec['started_s']:.3f}s into the "
                f"window, took {rec['seconds']:.3f}s, timed {json.dumps(rec['timed'])}")
        after = snapshot("after")
        for rec in fault_records:
            # a killed process took its counters with it: what it had counted
            # before the kill stays counted in the window's deltas
            if rec["before"]["replica"] is not None and rec["after"]["replica"] is None:
                after["replica_cpu"] += rec["before"]["process_cpu"] or 0.0
                for key, value in rec["before"]["replica"]["counters"].items():
                    after["replicas"][key] += value
        say("replicas over the window:", json.dumps(cl.pace_delta(before["pace"], after["pace"])))
        gen = workers.results()
        ops = [op for g in gen for op in g["ops"]]
        say(f"window closed: {sum(d['done'] for d in done)} operations recorded")
        sdk = sdk_counters(gen)
        say("SDK counters gained in the window [sum, callers moved]:",
            json.dumps({k: [v, sdk["callers"][k]] for k, v in sorted(sdk["sum"].items())}))
        say("marks of suspicion a caller gained against a replica, by replica, sorted:",
            json.dumps(sdk["marks"]))
        for g in gen:
            if g["errors"]:
                say(f"generator {g['worker']} failed operations:", json.dumps(g["errors"]))
            if g.get("retried"):
                say(f"generator {g['worker']} attempts made again:", json.dumps(g["retried"]))

        # ---- a replica that was started again, asked alone for every record
        # it owns that the window updated: first of all, because the SDK's reads
        # nudge a replica they outvote, and the read-back below reads everything
        direct, direct_counts = {}, {}
        restarted = sorted({r["server_id"] for r in schedule.restarted(fault_records)})
        updated = {op[ref.REC] for op in ops if op[ref.KIND] == ref.UPDATE}
        t0 = time.monotonic()
        for sid in restarted:
            owned = {rec: ycsb.key_name(rec) for rec in updated
                     if sid in pc.config.replica_set_for_key(ycsb.key_name(rec))}
            direct[sid] = await probe.direct_reads(pc, sid, owned)
        if restarted:
            say(f"direct read-back from {restarted} alone: {sum(map(len, direct.values()))} "
                f"records in {time.monotonic() - t0:.3f}s, {time.monotonic() - t_end:.1f}s "
                f"after the window closed")

        # ---- after the window: read back, probe the device, send bad Write2s
        pool = ycsb.value_pool(seed)
        hist = ref.build_histories(
            ops, ycsb.LOAD_WRITER, lambda w, s: zlib.crc32(ycsb.make_value(pool, w, s)))
        t0 = time.monotonic()
        readback = await workers.read_back(sorted(hist))
        await workers.close()
        say(f"read back {len(readback)} of {len(hist)} touched records in {time.monotonic() - t0:.1f}s")

        ready = set(before["service"]["ready_buckets"]) & set(before["service"]["comb_ready_buckets"])
        routed = [b for b in ready if b >= before["service"]["min_device_items"]]
        probe_size = min(routed) if routed else min(ready)
        idle0 = cl.service_counters(pc.service_status())
        if args.trace:
            dprobe, traces["probe"] = await traced(
                ctl_dir, os.path.join(out_dir, "trace-probe"),
                lambda: probe.device_probe(pc, seed, probe_size), t_start)
        else:
            dprobe = await probe.device_probe(pc, seed, probe_size)
        idle1 = cl.service_counters(pc.service_status())
        say(f"device probe: {json.dumps(dprobe)}")

        touched_keys = sorted(ycsb.key_name(rec) for rec in hist)
        bad = await probe.bad_write2_probe(pc, seed, touched_keys, BAD_WRITE2S)
        say(f"bad Write2 probe: {json.dumps(bad)}")

        final = snapshot("final")
        errors = cl.log_errors(pc.log_paths())
        if errors:
            say("ERROR records in the children's logs:", json.dumps(errors))
        if args.keep:
            os.makedirs(os.path.join(out_dir, "logs"), exist_ok=True)
            for path in pc.log_paths():
                shutil.copy(path, os.path.join(out_dir, "logs"))

        # ---- the numbers compared, each beside its limit
        checks = []
        checks += ref.check_window(ops, hist, quorum)
        checks += ref.check_readback(readback, hist, quorum)
        checks += ref.check_probe(bad)
        checks += ref.check_deployment(shape, final["replicas"])
        if members:
            checks += ref.check_byzantine(members, before["replicas"], after["replicas"], sdk["sum"])
        if events:
            checks += ref.check_recovery(fault_records)
            direct_checks, direct_counts = ref.check_direct(
                direct, hist, fault_records, DIRECT_SLACK_S, quorum)
            checks += direct_checks
            say(f"direct read-back: {direct_counts['behind']} of {direct_counts['asked']} records "
                "behind the newest acknowledged write on the restarted replica alone "
                "(committed while it was down, or inside the slack; not compared)")
        C = ref.Check
        checks += [
            C("operations_recorded", len(ops), 1, at_least=True),
            C("device_probe_mismatches", dprobe["mismatches"] + warmed["mismatches"], 0),
            C("service_fallback_batches", final["service"]["fallback_batches"], 0),
            C("replica_fallback_batches", final["replicas"]["fallback_batches"], 0),
            C("failed_buckets", len(final["service"]["failed_buckets"])
              + len(final["service"]["comb_failed_buckets"]), 0),
            C("replicas_answering_status", final["replicas"]["replicas"], n, at_least=True),
            C("processes_with_jax_loaded_besides_the_service",
              final["replicas"]["jax_loaded"] + sum(1 for g in gen if g["jax_loaded"])
              + ("jax" in sys.modules), 0),
            C("programs_built_in_window",
              after["stats"]["programs_built"] - before["stats"]["programs_built"], 0),
            C("cache_entries_gained_in_window",
              after["cache_entries"] - before["cache_entries"], 0),
        ]
        if not rehearse:
            checks += [
                C("device_probe_items_on_device",
                  idle1["device_items"] - idle0["device_items"], dprobe["items"], at_least=True),
                C("programs_built_by_device_probe",
                  final["stats"]["programs_built"] - after["stats"]["programs_built"], 0),
            ]
        for c in checks:
            say(c.line())

        summary = ref.summarize(ops, seconds, t_end)
        lat = summary.pop("latency_ms")
        for kind, name in ((ref.READ, "read"), (ref.UPDATE, "update")):
            if lat[kind]:
                say(f"{name}: n={len(lat[kind])} p50={ref.percentile(lat[kind], 50):.3f}ms "
                    f"p95={ref.percentile(lat[kind], 95):.3f}ms p99={ref.percentile(lat[kind], 99):.3f}ms")
        say("the window second by second:", json.dumps(ref.by_second(ops, t_start, seconds)))
        late = max(g["busy_until"] for g in gen) - t_end
        say(f"ops_s={summary['ops_s']:.3f} attempted={summary['attempted']} "
            f"failed={summary['failed']} drain_after_window={late:.3f}s setup_s={setup_s:.1f} "
            f"run so far {time.monotonic() - T_PROCESS_START:.1f}s")

        stats = final["stats"]
        result = {
            "correct": all(c.ok for c in checks),
            "attempted": summary["attempted"],
            "failed": summary["failed"],
            "device": {
                "platform": stats["platform"], "kind": stats["kind"], "count": stats["count"],
                "memory_peak_bytes": max(
                    (m.get("peak_bytes_in_use", 0) for m in stats["memory"]), default=0),
            },
        }

        def pct(values, q):
            v = ref.percentile(values, q)
            return FAILED_LATENCY_MS if math.isinf(v) else v

        e2e = {"ops_s": summary["ops_s"], "setup_s": setup_s,
               **schedule.end_to_end(events, fault_records)}
        if lat[ref.UPDATE]:
            e2e["update_p95_ms"] = pct(lat[ref.UPDATE], 95)
        if lat[ref.READ]:
            e2e["read_p95_ms"] = pct(lat[ref.READ], 95)
            e2e["read_p50_ms"] = pct(lat[ref.READ], 50)
        bench = data["bench"]
        if not args.trace:
            result["metrics"] = {
                m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in bench["end_to_end"]
                if metric_applies(m, cell["name"]) and m["name"] in e2e
            }
        else:
            import hostspans
            import xplane

            # each reduction is a child of its own, and a child's import of JAX
            # is most of its cost: the device planes and the host spans (which
            # the readers would otherwise reduce later, one after the other)
            # are reduced side by side
            t0 = time.monotonic()
            spans_of = {"platform": stats["platform"],
                        "trace": {k: {"window_s": t["seconds"]} for k, t in traces.items()}}
            *planes, host_spans = await asyncio.gather(
                *(asyncio.to_thread(xplane.reduce_dir, t["dir"], t["seconds"]) for t in traces.values()),
                asyncio.to_thread(hostspans.of, spans_of))
            reduced = {k: dict(r, started_s=t["started_s"]) for (k, t), r in zip(traces.items(), planes)}
            for k, r in reduced.items():
                began, ended = traces[k]["profiler_s"]
                say(f"trace {k}: {r['window_s']:.3f}s traced from {r['started_s']:.3f}s into the "
                    f"window, device busy {r['busy_s']:.6f}s, "
                    f"{r['launches']} launches, programs {json.dumps(r['programs'])}; the profiler "
                    f"took {began:.1f}s to start and {ended:.1f}s to stop")
            say(f"traces reduced in {time.monotonic() - t0:.1f}s")
            updates_ok = sum(1 for op in ops if op[ref.KIND] == ref.UPDATE and op[ref.OK])
            snap = {
                "platform": stats["platform"], "window_s": seconds, "ops_ok": summary["attempted"] - summary["failed"],
                "updates_ok": updates_ok, "before": before, "after": after,
                "latency": {k: v for k, v in e2e.items() if k.endswith("_ms")},
                "generator": {
                    "processes": len(gen),
                    "cpu_seconds": sum(g["cpu_seconds"] for g in gen),
                    "sdk_counters": sdk,
                    "stage_seconds": {
                        name: [s for g in gen for s in g["stage_seconds"].get(name, [])]
                        for name in ycsb.STAGE_TIMERS
                    },
                },
                "trace": reduced, "host_spans": host_spans,
                "cluster": {"replicas": n, "rf": rf, "f": shape["f"], "quorum": quorum,
                            "byzantine": members},
                "faults": fault_records, "end_to_end": e2e,
            }
            result["metrics"] = read_layer_metrics(data["layer_dir"], bench, cell["name"], snap)
            if not rehearse:
                result["device"]["busy_s"] = sum(r["busy_s"] for r in reduced.values())
                result["device"]["window_s"] = sum(r["window_s"] for r in reduced.values())
                result["breakdown"] = xplane.breakdown(list(reduced.values()))
                # where the readers reduced the host's spans beside the device's
                # plane, the gaps carry what the host was doing in them
                labelled = [g[:2] for r in (snap.get("host_spans") or {}).values() for g in r["gaps"]]
                if labelled:
                    result["breakdown"]["idle_gaps"] = sorted(
                        labelled, key=lambda g: g[1], reverse=True)[:10]
            if args.keep:
                with open(os.path.join(out_dir, "snapshot.json"), "w") as fh:
                    json.dump(snap, fh)
        if rehearse:
            result["device"].pop("memory_peak_bytes")
            result["rehearsal"] = True
        result["tree"] = treestate.result_object(facts, moved)
        # every number compared beside its limit: the result's last key, and
        # the last lines on standard error
        result["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                                     "rule": ">=" if c.at_least else "<="} for c in checks}
        for c in checks:
            print("[perf]", c.line(), file=sys.stderr)
        sys.stderr.flush()
    finally:
        for task in (fault_task, profiler_warm):
            if task is not None:
                task.cancel()
        await workers.close()
        await pc.close()
        if args.keep:
            say("kept", out_dir)
        else:
            shutil.rmtree(out_dir, ignore_errors=True)
    return result


def main(argv=None, launcher=None, worker_script=None, faults_dir=None, boot=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU rehearsal at a tiny shape (needs JAX_PLATFORMS=cpu)")
    parser.add_argument("--keep", action="store_true",
                        help="keep logs, traces and storage under perf/out/")
    parser.add_argument("--root", default=REPO,
                        help="where BENCHMARK.json and its data files are read from")
    args = parser.parse_args(argv)
    try:
        gate(args.rehearse)
        data = load_cell(args.root, args.workload, faults_dir)
        args.natives_before = treestate.native_modules(REPO)
        build_native()
        result = asyncio.run(within(run_cell(
            args, data,
            launcher or os.path.join(PERF, "service_launch.py"),
            worker_script or os.path.join(PERF, "ycsb.py"), boot,
        ), RUN_LIMIT_S - (time.monotonic() - T_PROCESS_START)))
    except RunFailure as exc:
        for line in SAID:  # how far the run had come
            print("[perf] said:", line, file=sys.stderr)
        print(f"[perf] no result: {exc}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
