"""Process-level runtime tuning for mochi server/verifier processes.

The asyncio request path allocates heavily (envelopes, futures, frames);
CPython's default generational GC thresholds (700, 10, 10) make gen-0/1
collections fire every few requests, and the collector walks the whole
young set each time.  Relaxing the thresholds and freezing the post-boot
heap into the permanent generation was measured at +15-20% cluster
throughput on the config-1 bench (5 replicas, 40 clients, single core);
20k/50k/200k gen-0 thresholds all measured within noise of each other,
so the value below is not delicate.

This is deliberately a *server-process* knob, called from process entry
points (``server/__main__.py``, ``verifier.service:main``, the benchmark
harnesses) — never on library import, which would impose our GC policy on
embedding applications.
"""

from __future__ import annotations

import gc
import hashlib
import os
import platform


_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cpu_requested() -> bool:
    """Whether the caller explicitly pinned JAX to the CPU backend
    (``JAX_PLATFORMS=cpu`` in the environment).  The one sanctioned way to
    run a device-named path without a chip: tests and dry runs export it;
    a host that merely has no accelerator does not."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def host_cache_dir(base: str) -> str:
    """CPU-backend XLA cache subdirectory keyed by this host's CPU features.

    XLA:CPU's persistent cache key does NOT include the CPU feature set its
    AOT code was specialized for; a cache directory populated on a machine
    with (say) AVX-512 feeds SIGILL-prone code to a host without it.  The
    path is a pure function of the host, so it is as fixed as its parent.
    """
    feat = platform.machine()
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    feat += " " + " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    tag = hashlib.sha256(feat.encode()).hexdigest()[:12]
    return os.path.join(base, f"cpu-{tag}")


def compile_cache_dir() -> str:
    """The one place this checkout's processes keep compiled programs:
    ``JAX_COMPILATION_CACHE_DIR`` when set (an operator, or a chip harness
    that keeps a cache between runs, places it from outside), otherwise
    ``<checkout>/.jax_cache`` by absolute path — the path is part of the
    cache key, so a directory that moves with the cwd never hits — with the
    host-keyed sub-directory under an explicit CPU pin
    (:func:`host_cache_dir`).  Never a temp name, a pid or a time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(_REPO, ".jax_cache")
    return host_cache_dir(path) if cpu_requested() else path


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return the directory.  Every process that
    compiles calls this before its first compile; nothing else in the
    repository sets the option.  With ``JAX_COMPILATION_CACHE_DIR`` set JAX
    reads the variable itself and nothing is set in code."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info(require_accelerator: bool = False) -> dict:
    """``platform`` / ``device_kind`` / ``n_devices`` as JAX reports them
    (initialises the backend — only the process that owns the chip asks).

    ``require_accelerator``: a path that is named for the device refuses to
    run on a host where JAX found none — XLA:CPU under a TPU's name is the
    silent fallback this exists to end — unless the caller pinned the CPU
    explicitly (:func:`cpu_requested`).
    """
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
    }
    if require_accelerator and info["platform"] == "cpu" and not cpu_requested():
        raise SystemExit(
            "JAX found no accelerator (platform 'cpu') and JAX_PLATFORMS=cpu "
            "was not set: refusing to serve a device path from the CPU. "
            "Export JAX_PLATFORMS=cpu for an explicit CPU run."
        )
    return info


def tune_gc_for_server() -> None:
    """Relax GC for allocation-heavy serving; freeze the boot-time heap.

    Reference-cycle garbage still gets collected — only less often, with
    the (acyclic) steady-state request garbage reclaimed by refcounting as
    usual.  Call after imports/boot so ``gc.freeze`` captures module state.
    """
    gc.collect()
    gc.freeze()
    gc.set_threshold(50000, 50, 50)
