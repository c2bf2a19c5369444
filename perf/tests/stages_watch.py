"""Run one benchmark cell and, beside it, read the service's ``/status`` once a
second and the replicas' ``/metrics`` every few seconds; then print the
window's deltas of the service's stage timers (``stages`` in its ``/status``,
``mochi_tpu/verifier/stages.py``) and of the replicas' ``replica.auth-verify``.

    python perf/tests/stages_watch.py --out chiprun_out/stages/<tag>.json -- \
        --workload n64-ycsb-a --seed 7 --seconds 30 --trace 1

``perf/run.py`` takes its snapshots through a fixed selection of counters and
hands the readers no more, so the per-layer metrics read the spans of the
traced five seconds (``perf/hostspans.py``).  This is the other reading of the
same stages: whole window, from the timers' exact ``count`` and ``sum_ms``,
with or without a trace.  It finds the admin ports in the children's command
lines, talks to nobody else, and stays off JAX.  The window is placed by the
result's ``setup_s`` from this process's start of ``run.py``; samples a second
apart bound the error.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
REPLICA_TIMER = "replica.auth-verify"


def get(port: int, path: str) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return json.loads(r.read())


def admin_ports() -> tuple:
    """(service port or None, replica ports) from the children's command lines."""
    service, replicas = None, []
    for path in glob.glob("/proc/[0-9]*/cmdline"):
        try:
            argv = open(path, "rb").read().decode(errors="replace").split("\0")
        except OSError:
            continue
        if "--admin-port" not in argv:
            continue
        port = int(argv[argv.index("--admin-port") + 1])
        if "--perf-ctl" in argv or "mochi_tpu.verifier.service" in argv:
            service = port
        elif "mochi_tpu.server" in argv:
            replicas += [port + j for j in range(argv.count("--server-id"))]
    return service, sorted(replicas)


def service_sample(port: int) -> dict:
    st = get(port, "/status")
    dev = st["verifier"]["inner"].get("device", {})
    return {
        "t": time.monotonic(),
        "loop_thread_cpu_s": st.get("loop_thread_cpu_s"),
        "requests": st["requests"], "items": st["items"],
        "device_items": dev.get("device_items", 0), "host_routed_items": dev.get("host_routed_items", 0),
        "programs_built": st.get("programs_built"), "building": st.get("building"),
        "builds_started": st.get("builds_started"), "builds_finished": st.get("builds_finished"),
        "stages": st.get("stages", {}),
    }


def replica_sample(ports: list) -> dict:
    count = total = 0.0
    for port in ports:
        t = get(port, "/metrics")["timers"].get(REPLICA_TIMER)
        if t:
            count += t["count"]
            total += t.get("sum_ms", t["mean_ms"] * t["count"])
    return {"t": time.monotonic(), "count": count, "sum_ms": total, "replicas": len(ports)}


def watch(stop: threading.Event, service: list, replicas: list) -> None:
    ports, last_replicas = (None, []), 0.0
    while not stop.wait(1.0):
        try:
            if ports[0] is None:
                ports = admin_ports()
                if ports[0] is None:
                    continue
            service.append(service_sample(ports[0]))
            if time.monotonic() - last_replicas >= 5.0:
                last_replicas = time.monotonic()
                replicas.append(replica_sample(ports[1] or admin_ports()[1]))
        except (OSError, KeyError, ValueError):
            ports = (None, [])  # the service is not up yet, or is gone


def inside(samples: list, t0: float, t1: float) -> tuple:
    rows = [s for s in samples if t0 <= s["t"] <= t1]
    return (rows[0], rows[-1]) if len(rows) >= 2 else (None, None)


def window_deltas(service: list, replicas: list, t0: float, t1: float) -> dict:
    a, b = inside(service, t0, t1)
    if a is None:
        return {"error": f"{len(service)} service samples, fewer than two in the window"}
    span = b["t"] - a["t"]

    def timer(name):
        ta, tb = (s["stages"].get("timers", {}).get(name, {}) for s in (a, b))
        return tb.get("count", 0) - ta.get("count", 0), (tb.get("sum_ms", 0.0) - ta.get("sum_ms", 0.0)) / 1e3

    def counter(name):
        return b["stages"].get("counters", {}).get(name, 0) - a["stages"].get("counters", {}).get(name, 0)

    def ratio(num, den, scale=1.0):
        return scale * num / den if den else None

    rpc_n, rpc_s = timer("service.rpc")
    memo_n, memo_s = timer("service.memo-lookup")
    wait_n, wait_s = timer("verifier.queue-wait")
    host_n, host_s = timer("verifier.flush-host")
    dev_n, dev_s = timer("verifier.flush-device")
    prep_n, prep_s = timer("verifier.prepare")
    gc_n, gc_s = timer("service.gc")
    host_items = b["host_routed_items"] - a["host_routed_items"]
    dev_items = b["device_items"] - a["device_items"]
    hist_a, hist_b = (s["stages"].get("histograms", {}).get("verifier.flush-items", {}) for s in (a, b))
    out = {
        "sampled_s": span, "samples": len([s for s in service if t0 <= s["t"] <= t1]),
        "service.loop_cpu_share": ratio(b["loop_thread_cpu_s"] - a["loop_thread_cpu_s"], span, 100.0)
        if a["loop_thread_cpu_s"] is not None else None,
        "service.rpc_ms": ratio(rpc_s, rpc_n, 1e3), "rpcs_per_s": rpc_n / span,
        "service.memo_us_per_item": ratio(memo_s, counter("service.memo-items"), 1e6),
        "memo_items_per_s": counter("service.memo-items") / span,
        "memo_loop_share": 100.0 * memo_s / span,
        "verifier.queue_wait_ms": ratio(wait_s, wait_n, 1e3),
        "verifier.flush_busy_share": 100.0 * (host_s + dev_s) / span,
        "verifier.host_us_per_item": ratio(host_s, host_items, 1e6),
        "verifier.device_us_per_item": ratio(dev_s, dev_items, 1e6),
        "flushes": {"host": host_n, "device": dev_n, "host_items": host_items, "device_items": dev_items},
        "service.gc_pause_share": 100.0 * gc_s / span, "gc_passes": gc_n,
        "prepare.us_per_item": ratio(prep_s, dev_items, 1e6), "prepares": prep_n,
        "flush_items_buckets": {k: v - hist_a.get("buckets", {}).get(k, 0)
                                for k, v in hist_b.get("buckets", {}).items()},
        "programs_built": b["programs_built"] - a["programs_built"] if a["programs_built"] is not None else None,
        "builds_started": (b["builds_started"] or 0) - (a["builds_started"] or 0),
        "building_seen": [s["building"] for s in service if t0 <= s["t"] <= t1 and s["building"]][:3],
    }
    ra, rb = inside(replicas, t0, t1)
    if ra is not None:
        out["replica.verify_wait_ms"] = ratio(rb["sum_ms"] - ra["sum_ms"], rb["count"] - ra["count"])
        out["replica_verifies_per_s"] = (rb["count"] - ra["count"]) / (rb["t"] - ra["t"])
        out["replicas_read"] = rb["replicas"]
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out", required=True)
    parser.add_argument("run_args", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    run_args = [a for a in args.run_args if a != "--"]
    seconds = float(run_args[run_args.index("--seconds") + 1])
    service, replicas, stop = [], [], threading.Event()
    thread = threading.Thread(target=watch, args=(stop, service, replicas), daemon=True)
    t_spawn = time.monotonic()
    child = subprocess.Popen([sys.executable, os.path.join(PERF, "run.py"), *run_args],
                             stdout=subprocess.PIPE, text=True)
    thread.start()
    last, setup = "", None
    for line in child.stdout:
        last = line
        found = re.search(r" setup_s=([0-9.]+) ", line)
        if found:  # the commentary has it in traced runs too
            setup = float(found.group(1))
        sys.stdout.write(line)
        sys.stdout.flush()
    rc = child.wait()
    stop.set()
    thread.join(timeout=15)
    report = {"rc": rc, "args": run_args, "setup_s": setup, "service_samples": len(service)}
    if rc == 0 and last.startswith("{"):
        report["result"] = json.loads(last)
    if setup is not None:
        t0 = t_spawn + setup
        report["window"] = window_deltas(service, replicas, t0, t0 + seconds)
        report["samples"] = {
            "service": [s for s in service if t0 - 5 <= s["t"] <= t0 + seconds + 5],
            "replicas": replicas, "t_window": t0, "seconds": seconds,
        }
        print("[watch]", json.dumps(report["window"]), file=sys.stderr, flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(report, fh)
    return rc


if __name__ == "__main__":
    sys.exit(main())
