"""From a profiler trace (``*.xplane.pb``) to device time.

Read with ``jax.profiler.ProfileData`` and nothing else.  A TPU's plane is
named ``/device:TPU:<n>``; its line ``XLA Ops`` has one event per HLO
operation run and its line ``XLA Modules`` one per program launch, named
``<jit name>(<fingerprint>)``.  Times are nanoseconds from the trace's start.

* busy time of a device: the union of its ``XLA Ops`` intervals (of its
  ``XLA Modules`` intervals where a trace has no op line);
* kernel time per named program: the summed durations of its launches;
* idle gaps: the stretches between busy intervals, longest first.  Nothing in
  the program puts host spans on the profiler's clock yet, so a gap is
  labelled ``unattributed``.
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_trace(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _intervals(line) -> list:
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events]


def union(intervals) -> list:
    """Merged [start, end) intervals, sorted."""
    merged = []
    for start, end in sorted((a, b) for a, b, *_ in intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def short_op(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%") or name[:60]


def reduce_file(path: str, window_s: float) -> dict:
    """Device time in one trace.  ``window_s`` is the traced length, by the
    clock of the process that started and stopped the profiler."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = next((_intervals(lines[n]) for n in OP_LINES
                    if n in lines and len(list(lines[n].events))), [])
        modules = _intervals(lines["XLA Modules"]) if "XLA Modules" in lines else []
        busy = union(ops)
        by_op, by_program = {}, {}
        for a, b, name in ops:
            by_op[short_op(name)] = by_op.get(short_op(name), 0.0) + (b - a) / 1e9
        for a, b, name in modules:
            prog = _FINGERPRINT.sub("", name)
            by_program[prog] = by_program.get(prog, 0.0) + (b - a) / 1e9
        gaps = [(b[0] - a[1]) / 1e9 for a, b in zip(busy, busy[1:])]
        devices.append({
            "plane": plane.name,
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "launches": len(modules),
            "ops": by_op,
            "programs": by_program,
            "gaps_s": sorted(gaps, reverse=True)[:10],
            "first_ns": busy[0][0] if busy else None,
            "last_ns": busy[-1][1] if busy else None,
        })
    n = max(1, len(devices))
    out = {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in devices) / n,  # averaged over the chips
        "device_planes": len(devices),
        "launches": sum(d["launches"] for d in devices),
        "ops": {}, "programs": {}, "gaps_s": [],
    }
    for d in devices:
        for key in ("ops", "programs"):
            for name, s in d[key].items():
                out[key][name] = out[key].get(name, 0.0) + s / n
        out["gaps_s"] += d["gaps_s"]
    # the stretches before the first and after the last operation are idle too
    for d in devices:
        if d["first_ns"] is not None:
            out["gaps_s"] += [d["first_ns"] / 1e9, max(0.0, window_s - d["last_ns"] / 1e9)]
    if not any(d["first_ns"] is not None for d in devices):
        out["gaps_s"].append(window_s)
    out["gaps_s"] = sorted(out["gaps_s"], reverse=True)[:10]
    return out


def reduce_dir(trace_dir: str, window_s: float) -> dict:
    """Reduce the trace under ``trace_dir`` in a child process pinned to the
    CPU, so that the caller (the harness, which stays off JAX) never imports
    it."""
    path = find_trace(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__), path, repr(window_s)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, check=True)
    return json.loads(done.stdout.splitlines()[-1])


def idle_share(reduced: dict) -> float:
    """Percent of the traced window in which no operation ran on the device."""
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def breakdown(reduced: list) -> dict:
    """The result line's ``breakdown``: the device operations that took most
    time, and the longest idle gaps by what the host was doing in them."""
    ops: dict = {}
    gaps = []
    for r in reduced:
        for name, s in r["ops"].items():
            ops[name] = ops.get(name, 0.0) + s
        gaps += r["gaps_s"]
    top = sorted(ops.items(), key=lambda kv: kv[1], reverse=True)[:10]
    return {
        "device_ops": [[name, s] for name, s in top],
        "idle_gaps": [["unattributed", s] for s in sorted(gaps, reverse=True)[:10]],
    }


def load_peaks(path: str, device_kind: str) -> dict:
    """The published peaks of a device kind; an unknown device is an error."""
    with open(path) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1], float(sys.argv[2]))))
