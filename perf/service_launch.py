"""Start the product's verifier service, unchanged, with a profiler handle.

No product file has a profiler hook, and only the process that owns the chip
can trace it or read its memory.  So the benchmark starts the service through
this launcher: it calls ``mochi_tpu.verifier.service.main()`` with the argv it
was given, and beside it runs one thread that answers requests left as files
in ``--perf-ctl <dir>``:

* ``{"op": "stats"}`` — programs built in this process since the first
  request (JAX's ``backend_compile`` events; a cache hit still traces, lowers
  and loads, and counts), and each device's memory statistics;
* ``{"op": "trace_start", "dir": ...}`` / ``{"op": "trace_stop"}`` —
  ``jax.profiler`` around whatever the service is doing.

A request is ``req-<id>.json``; the answer is ``rsp-<id>.json``, written whole
and then renamed.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Control(threading.Thread):
    def __init__(self, ctl_dir: str):
        super().__init__(name="perf-control", daemon=True)
        self.ctl_dir = ctl_dir
        self.programs_built = 0
        self._listening = False
        self._tracing = False

    def _listen(self) -> None:
        if self._listening:
            return
        import jax.monitoring

        def on_duration(event: str, duration: float, **kw) -> None:
            if event == BACKEND_COMPILE_EVENT:
                self.programs_built += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        self._listening = True

    def handle(self, req: dict) -> dict:
        import jax

        self._listen()
        op = req["op"]
        if op == "stats":
            devices = jax.local_devices()
            return {
                "programs_built": self.programs_built,
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
                "memory": [d.memory_stats() or {} for d in devices],
            }
        if op == "trace_start":
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            # the programs' HLO is tens of megabytes a trace and nothing here
            # reads it; with it, stopping a trace took ~20 s of the run
            options.enable_hlo_proto = False
            jax.profiler.start_trace(req["dir"], profiler_options=options)
            self._tracing = True
            return {"started_monotonic": time.monotonic()}
        if op == "trace_stop":
            stopped = time.monotonic()
            if self._tracing:
                jax.profiler.stop_trace()
                self._tracing = False
            return {"stopped_monotonic": stopped}
        return {"error": f"unknown op {op!r}"}

    def run(self) -> None:
        while True:
            try:
                names = sorted(n for n in os.listdir(self.ctl_dir)
                               if n.startswith("req-") and n.endswith(".json"))
            except OSError:
                return  # the run's directory is gone: the run is over
            for name in names:
                path = os.path.join(self.ctl_dir, name)
                try:
                    with open(path) as fh:
                        req = json.load(fh)
                    rsp = self.handle(req)
                except Exception as exc:  # answered, so the harness fails fast
                    rsp = {"error": f"{type(exc).__name__}: {exc}"}
                os.unlink(path)
                out = os.path.join(self.ctl_dir, "rsp-" + name[len("req-"):])
                with open(out + ".tmp", "w") as fh:
                    json.dump(rsp, fh)
                os.replace(out + ".tmp", out)
            time.sleep(0.02)


def request(ctl_dir: str, req: dict, timeout_s: float = 120.0) -> dict:
    """The harness's side: leave a request, wait for its answer."""
    rid = f"{time.monotonic_ns()}"
    path = os.path.join(ctl_dir, f"req-{rid}.json")
    with open(path + ".tmp", "w") as fh:
        json.dump(req, fh)
    os.replace(path + ".tmp", path)
    rsp_path = os.path.join(ctl_dir, f"rsp-{rid}.json")
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if os.path.exists(rsp_path):
            with open(rsp_path) as fh:
                rsp = json.load(fh)
            os.unlink(rsp_path)
            if "error" in rsp:
                raise RuntimeError(f"service control {req['op']}: {rsp['error']}")
            return rsp
        time.sleep(0.01)
    raise TimeoutError(f"service control {req['op']}: no answer in {timeout_s}s")


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--perf-ctl")
    ctl_dir = argv[i + 1]
    del argv[i:i + 2]
    Control(ctl_dir).start()
    from mochi_tpu.verifier import service

    service.main(argv)


if __name__ == "__main__":
    main()
