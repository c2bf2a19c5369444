"""From a profiler trace to what the host was doing while the device idled.

Beside ``perf/xplane.py`` (imported, not changed): that module reduces the
device's plane alone; this one reduces it together with the ``/host:CPU``
plane, where the verifier service's ``mochi.*`` spans land
(``mochi_tpu/obs/hostspan.py``: ``jax.profiler.TraceAnnotation`` events, one
line per thread, their keyword arguments as the event's stats).  Times on both
planes are nanoseconds from the trace's start.

* launches and seconds per named device program (``XLA Modules``);
* the spans by name: how many, their seconds, and the sums of the numbers they
  carry (items, wait_us, ...);
* idle seconds by cause: every instant in which no operation ran on the device
  goes to the span that covers it, by the precedence of ``CAUSES`` where
  several do, and to ``no_span`` where none does: the service had nothing to
  do.  The causes sum to the idle seconds;
* a label for each of the ten longest gaps: whichever of the causes and
  ``no_span`` holds most of it.

Nothing in ``perf/run.py`` hands a reader the trace's path, so ``of(snapshot)``
finds the run's traces where the harness writes them (``perf/out/<run>/
trace-<kind>``), reduces them in a child pinned to the CPU (the harness stays
off JAX), and keeps the result in the snapshot under ``host_spans``: every
reader shares one reduction, ``--keep`` writes it to ``snapshot.json``, and a
harness that one day fills that key itself needs no reader changed.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import subprocess
import sys

import xplane

HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "mochi."
# The device programs by the names the product pins (crypto/batch_verify.py
# LADDER_PROGRAM, crypto/comb.py COMB_PROGRAM; tests/test_stages.py holds the
# two sides equal).
LADDER_PROGRAM = "jit_verify_prepared_packed"
COMB_PROGRAM = "jit_verify_comb_prepared"

NO_SPAN = "no_span"
# cause of an idle instant by the span over it, highest precedence first
CAUSES = (
    ("build", ("mochi.verifier.build",)),
    ("host_verify", ("mochi.verifier.host_verify",)),
    ("prepare", ("mochi.verifier.prepare",)),
    ("dispatch", ("mochi.verifier.dispatch",)),
    ("readback", ("mochi.verifier.readback",)),
    ("flush", ("mochi.verifier.flush", "mochi.verifier.chunk")),
    ("rpc_memo", ("mochi.service.rpc.admit", "mochi.service.rpc.reply", "mochi.verifier.memo")),
    ("gc", ("mochi.gc",)),
)
TICK = "mochi.service.tick"
FLUSH = "mochi.verifier.flush"
SUMMED = ("items", "wait_us")  # the spans' numbers that add up
KINDS = ("window", "probe")


# ------------------------------------------------------------ interval lists


def intersect(a: list, b: list) -> list:
    """The overlap of two sorted lists of disjoint [start, end) intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """What of ``a`` lies outside ``b`` (both sorted, disjoint)."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append([lo, b[k][0]])
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append([lo, hi])
    return out


def length(intervals: list) -> int:
    return sum(hi - lo for lo, hi in intervals)


# ------------------------------------------------------------- the reduction


def host_spans(data) -> list:
    """(name, start_ns, end_ns, thread, stats) of every ``mochi.*`` event."""
    out = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                                n, dict(ev.stats)))
    return out


def span_table(spans: list) -> dict:
    """By span name: count, seconds, threads, and the sums of its numbers."""
    table: dict = {}
    for name, start, end, thread, stats in spans:
        row = table.setdefault(name, {"count": 0, "seconds": 0.0, "threads": [], "sums": {}})
        row["count"] += 1
        row["seconds"] += (end - start) / 1e9
        if thread not in row["threads"]:
            row["threads"].append(thread)
        for key in SUMMED:
            if key in stats:
                row["sums"][key] = row["sums"].get(key, 0) + stats[key]
    return table


def flush_routes(spans: list) -> dict:
    """Backend calls by route: count, seconds, items, and the buckets seen."""
    routes: dict = {}
    for name, start, end, _, stats in spans:
        if name != FLUSH:
            continue
        row = routes.setdefault(str(stats.get("route")), {"count": 0, "seconds": 0.0, "items": 0, "buckets": []})
        row["count"] += 1
        row["seconds"] += (end - start) / 1e9
        row["items"] += int(stats.get("items", 0))
        if stats.get("bucket") not in row["buckets"]:
            row["buckets"].append(stats.get("bucket"))
    return routes


def ticks(spans: list) -> list:
    """[t_ns, loop_cpu_us, epoch_us] of the loop thread's once-a-second mark."""
    return sorted([start, int(stats["loop_cpu_us"]), int(stats["epoch_us"])]
                  for name, start, _, _, stats in spans
                  if name == TICK and "loop_cpu_us" in stats and "epoch_us" in stats)


def idle_by_cause(busy: list, spans: list, window_ns: int) -> tuple:
    """(seconds by cause, pieces): the device's idle instants in [0, window_ns),
    each given to the span over it.  ``pieces`` are (start, end, cause)."""
    idle = subtract([[0, window_ns]], busy) if window_ns > 0 else []
    rest, pieces, seconds = idle, [], {}
    for cause, names in CAUSES:
        under = intersect(rest, xplane.union((a, b) for n, a, b, _, _ in spans if n in names and b > a))
        seconds[cause] = length(under) / 1e9
        pieces += [(lo, hi, cause) for lo, hi in under]
        rest = subtract(rest, under)
    seconds[NO_SPAN] = length(rest) / 1e9
    pieces += [(lo, hi, NO_SPAN) for lo, hi in rest]
    return idle, seconds, sorted(pieces)


def label_gaps(idle: list, pieces: list, top: int = 10) -> list:
    """[label, seconds, share of the gap under the label] of the longest gaps."""
    order = [c for c, _ in CAUSES] + [NO_SPAN]
    out = []
    for lo, hi in sorted(idle, key=lambda g: g[1] - g[0], reverse=True)[:top]:
        held = {}
        for a, b, cause in pieces:
            if a < hi and b > lo:
                held[cause] = held.get(cause, 0) + min(b, hi) - max(a, lo)
        label = max(held, key=lambda c: (held[c], -order.index(c)))
        out.append([label, (hi - lo) / 1e9, held[label] / (hi - lo)])
    return out


def reduce_file(path: str, window_s: float) -> dict:
    """Host spans and device time of one trace, together."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans = host_spans(data)
    window_ns = int(window_s * 1e9)
    programs, devices = {}, []
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = next((xplane._intervals(lines[n]) for n in xplane.OP_LINES
                    if n in lines and len(list(lines[n].events))), [])
        modules = sorted(xplane._intervals(lines["XLA Modules"])) if "XLA Modules" in lines else []
        for a, b, name in modules:
            row = programs.setdefault(xplane._FINGERPRINT.sub("", name), {"launches": 0, "seconds": 0.0, "ops": {}})
            row["launches"] += 1
            row["seconds"] += (b - a) / 1e9
        for a, b, name in ops:
            # an operation belongs to the launch it started in
            k = bisect.bisect_right(modules, (a, float("inf"), "")) - 1
            prog = xplane._FINGERPRINT.sub("", modules[k][2]) if k >= 0 and a < modules[k][1] else "?"
            row = programs.setdefault(prog, {"launches": 0, "seconds": 0.0, "ops": {}})
            row["ops"][xplane.short_op(name)] = row["ops"].get(xplane.short_op(name), 0.0) + (b - a) / 1e9
        busy = xplane.union(ops)
        # what runs past the harness's clock for the trace still counts
        span_ns = max([window_ns] + [b for _, b in busy])
        idle, seconds, pieces = idle_by_cause(busy, spans, span_ns)
        devices.append({"idle_s": length(idle) / 1e9, "by_cause": seconds,
                        "gaps": label_gaps(idle, pieces)})
    planes = len(devices)
    if not devices:
        # a trace with no device plane is a device that did nothing in it
        idle, seconds, pieces = idle_by_cause([], spans, window_ns)
        devices.append({"idle_s": length(idle) / 1e9, "by_cause": seconds,
                        "gaps": label_gaps(idle, pieces)})
    n = len(devices)
    by_cause = {c: sum(d["by_cause"][c] for d in devices) / n
                for c in [c for c, _ in CAUSES] + [NO_SPAN]}
    return {
        "window_s": window_s,
        "device_planes": planes,
        "programs": programs,
        "spans": span_table(spans),
        "routes": flush_routes(spans),
        "ticks": ticks(spans),
        "idle_s": sum(d["idle_s"] for d in devices) / n,
        "idle_by_cause_s": by_cause,
        "gaps": sorted((g for d in devices for g in d["gaps"]), key=lambda g: g[1], reverse=True)[:10],
    }


# ------------------------------------------------- from a run's snapshot


def run_traces(out_root: str) -> dict:
    """{kind: path of the xplane.pb} of the newest run under ``out_root``."""
    runs = [d for d in glob.glob(os.path.join(out_root, "*")) if os.path.isdir(os.path.join(d, "trace-window"))]
    if not runs:
        return {}
    newest = max(runs, key=lambda d: os.path.getmtime(os.path.join(d, "trace-window")))
    found = {k: xplane.find_trace(os.path.join(newest, "trace-" + k)) for k in KINDS}
    return {k: p for k, p in found.items() if p}


def reduce_in_child(traces: dict) -> dict:
    """{kind: reduction} of {kind: (path, traced seconds)}, in ONE child
    pinned to the CPU: the child's import of JAX is most of the cost."""
    argv = [a for path, window_s in traces.values() for a in (path, repr(window_s))]
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *argv],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, check=True)
    return dict(zip(traces, json.loads(done.stdout.splitlines()[-1])))


def of(snap: dict, out_root: str | None = None) -> dict:
    """The reduced host spans of the run whose snapshot this is, by kind of
    trace; {} where there is none (off the TPU, or an untraced run).  Never
    raises: a reader that finds nothing reports nothing."""
    if "host_spans" in snap:
        return snap["host_spans"]
    reduced: dict = {}
    try:
        traced = snap.get("trace") or {}
        if snap.get("platform") == "tpu" and traced:
            paths = run_traces(out_root or os.path.join(os.path.dirname(os.path.abspath(__file__)), "out"))
            reduced = reduce_in_child({k: (paths[k], traced[k]["window_s"])
                                       for k in KINDS if k in paths and k in traced})
            for kind, r in reduced.items():
                for line in commentary(kind, r):
                    print("[perf]", line, flush=True)
    except Exception as exc:  # the result line then lacks these metrics
        print(f"[perf] host spans not reduced: {type(exc).__name__}: {exc}", flush=True)
        reduced = {}
    snap["host_spans"] = reduced
    return reduced


def commentary(kind: str, r: dict) -> list:
    names = ", ".join(f"{p} x{v['launches']} {1e3 * v['seconds']:.3f}ms" for p, v in sorted(r["programs"].items()))
    lines = [f"trace {kind}: programs {names or 'none'}; "
             f"{sum(v['count'] for v in r['spans'].values())} mochi.* spans"]
    for prog, v in sorted(r["programs"].items()):
        top = sorted(v["ops"].items(), key=lambda kv: kv[1], reverse=True)[:4]
        if top:
            lines.append(f"trace {kind}: {prog}: " + ", ".join(f"{op} {1e3 * s:.3f}ms" for op, s in top))
    if r["idle_s"]:
        table = ", ".join(f"{c} {s:.4f}s ({100 * s / r['idle_s']:.1f}%)"
                          for c, s in r["idle_by_cause_s"].items() if s > 0)
        lines.append(f"trace {kind}: device idle {r['idle_s']:.4f}s by cause: {table}")
        lines.append(f"trace {kind}: longest gaps " + json.dumps(
            [[g[0], round(g[1], 4), round(g[2], 3)] for g in r["gaps"]]))
    for route, v in sorted(r["routes"].items()):
        lines.append(f"trace {kind}: {v['count']} flushes to the {route}, {v['items']} items, "
                     f"{v['seconds']:.4f}s, buckets {sorted(b for b in v['buckets'] if b is not None)}")
    return lines


# what the readers under layer_metrics/ share


def span_row(reduced: dict, kind: str, name: str) -> dict | None:
    return (reduced.get(kind) or {}).get("spans", {}).get(name)


def route_rows(reduced: dict, route: str, kinds=KINDS) -> list:
    return [r["routes"][route] for k in kinds if (r := reduced.get(k)) and route in r["routes"]]


def program_ms_per_launch(reduced: dict, kind: str, program: str) -> float | None:
    row = (reduced.get(kind) or {}).get("programs", {}).get(program)
    return 1e3 * row["seconds"] / row["launches"] if row and row["launches"] else None


if __name__ == "__main__":  # <path> <traced seconds> [<path> <traced seconds> ...]
    print(json.dumps([reduce_file(p, float(w)) for p, w in zip(sys.argv[1::2], sys.argv[2::2])]))
