"""What a checkout carries besides its code, and what its processes used.

One program read at two levels by the tree it was started from (PERF.md
section 7, first).  Every run therefore says what could differ between two
preparations of one commit: the checkout's path and size, its files' mode and
age, the transport its temporary directory selected, the two native modules as
built here, which decoder the codec bound, the interpreter's flags, the
filesystems the run's files lie on, the CPUs it may use; and, over the window,
each child's CPU seconds by mode and the core it was last seen on.  Nothing
here decides anything: the facts go into the commentary and, shortened, into
the result's ``tree`` object.

The one-chip machine's kernel is a sandbox's: it reports core 0 for every
process, no run-queue times, no context switches and no CPU topology (my chip
runs, PR 43), so none of those is read; the core is, because a machine that
tells it tells how many cores the processes shared.
"""

from __future__ import annotations

import hashlib
import os
import sys
import sysconfig
import time

NATIVE = ("_mcode", "_hbatch")
# names only, of what can steer an interpreter, an allocator or a thread pool
ENVIRONMENT = ("PYTHON", "JAX_", "XLA_", "TPU_", "LIBTPU", "OMP_", "MKL_", "OPENBLAS_", "MALLOC_", "LD_", "TMPDIR",
               "HOME", "XDG_", "CC")
_TICK = os.sysconf("SC_CLK_TCK")


def _read(path: str) -> str | None:
    try:
        with open(path) as fh:
            return fh.read().strip()
    except OSError:
        return None


def native_modules(repo: str) -> dict:
    """Each native module's built file: md5, size, mtime, its source's mtime,
    and whether ``mochi_tpu.native._needs_build`` would still fire on it (a
    source newer than its build: every process that imports it builds again).
    None for a module that is not built."""
    ndir = os.path.join(repo, "mochi_tpu", "native")
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    out = {}
    for name in NATIVE:
        so, src = os.path.join(ndir, name + suffix), os.path.join(ndir, name[1:] + ".c")
        try:
            st, src_mtime = os.stat(so), os.path.getmtime(src)
            with open(so, "rb") as fh:
                md5 = hashlib.md5(fh.read()).hexdigest()
        except OSError:
            out[name] = None
            continue
        out[name] = {"md5": md5, "bytes": st.st_size, "mtime": st.st_mtime,
                     "source_mtime": src_mtime, "stale": st.st_mtime < src_mtime}
    return out


def filesystem_of(path: str) -> dict:
    """The mount that holds ``path``: its point, type and source
    (``/proc/self/mountinfo``'s longest matching mount point)."""
    real = os.path.realpath(path)
    best = {"mount": None, "type": None, "source": None}
    for line in (_read("/proc/self/mountinfo") or "").splitlines():
        left, _, right = line.partition(" - ")
        fields, kind = left.split(), right.split()
        if len(fields) < 5 or len(kind) < 2:
            continue
        point = fields[4]
        if (real == point or real.startswith(point.rstrip("/") + "/")) and \
                len(point) >= len(best["mount"] or ""):
            best = {"mount": point, "type": kind[0], "source": kind[1]}
    return best


WALK_LIMIT = 20000  # a checkout holds some hundreds of files; what else lies in it is not this run's to walk


def tree_size(repo: str, limit: int = WALK_LIMIT) -> dict:
    """Files and bytes under ``repo``, given up (``capped``) after ``limit``
    files: the walk runs inside set-up, on a root that may be a network mount."""
    files = size = 0
    for root, _dirs, names in os.walk(repo):
        for name in names:
            if files >= limit:
                return {"files": files, "bytes": size, "capped": True}
            try:
                size += os.lstat(os.path.join(root, name)).st_size
                files += 1
            except OSError:
                pass
    return {"files": files, "bytes": size}


def static_facts(repo: str, out_dir: str, tmp_dir: str, transport: str, natives_before: dict) -> dict:
    """What is fixed for the run.  ``natives_before`` is ``native_modules``
    as read before the harness asked for the modules: a module whose file
    changed since was built by this run."""
    natives = native_modules(repo)
    for name, now in natives.items():
        if now is not None:
            was = natives_before.get(name)
            now["built_by_this_run"] = was is None or (was["md5"], was["mtime"]) != (now["md5"], now["mtime"])
    bound = None
    codec = sys.modules.get("mochi_tpu.protocol.codec")
    if codec is not None:
        # the native decoder is bound under a dispatcher of this name (codec._bind)
        bound = "native" if codec.decode_env.__name__ == "decode_env_dispatch" else "python"
    st = os.stat(os.path.join(repo, "perf", "run.py"))
    return {
        "path": repo, "path_len": len(repo), "size": tree_size(repo),
        "run_py_mode": oct(st.st_mode & 0o777), "run_py_mtime": st.st_mtime, "now": time.time(),
        "tmp_dir": tmp_dir, "tmp_dir_len": len(tmp_dir), "transport": transport,
        "native": natives, "decode_env": bound,
        "python": {"executable": sys.executable, "optimize": sys.flags.optimize,
                   "hash_randomization": sys.flags.hash_randomization,
                   "dont_write_bytecode": sys.dont_write_bytecode,
                   "PYTHONHASHSEED": os.environ.get("PYTHONHASHSEED")},
        "environment": sorted(k for k in os.environ if k.startswith(ENVIRONMENT)),
        "cpus": sorted(os.sched_getaffinity(0)),
        "filesystem": {"out_dir": filesystem_of(out_dir), "tmp_dir": filesystem_of(tmp_dir)},
    }


def places(pids: dict) -> dict:
    """For each named live process: the core it last ran on (``/proc/<pid>/stat``
    field 39), its user and system CPU seconds and its threads; None for one
    that is gone."""
    out = {}
    for name, pid in pids.items():
        try:
            with open(f"/proc/{pid}/stat", "rb") as fh:
                f = fh.read().rsplit(b")", 1)[1].split()
            out[name] = {"core": int(f[36]), "user_s": int(f[11]) / _TICK, "system_s": int(f[12]) / _TICK,
                         "threads": int(f[17])}
        except (OSError, IndexError, ValueError):
            out[name] = None
    return out


def window_delta(before: dict, after: dict) -> dict:
    """What each process used between two ``places``: CPU seconds by mode, and
    the core at either end; over all of them, how many cores they were last
    seen on.  A process that was started again inside the window (another pid,
    so less CPU time than it began with) reports its end alone."""
    procs = {}
    for name, b in after.items():
        a = before.get(name)
        if b is None:
            continue
        if a is None or b["user_s"] < a["user_s"]:
            a = {"core": None, "user_s": 0.0, "system_s": 0.0}
        procs[name] = {"core": [a["core"], b["core"]], "user_s": round(b["user_s"] - a["user_s"], 2),
                       "system_s": round(b["system_s"] - a["system_s"], 2), "threads": b["threads"]}
    began = {p["core"][0] for p in procs.values()} - {None}
    return {"processes": procs, "distinct_cores": [len(began), len({p["core"][1] for p in procs.values()})]}


def result_object(static: dict, delta: dict) -> dict:
    """The result line's ``tree``: short enough to read in a ledger."""
    procs, native = delta["processes"], static["native"]
    return {
        "path_len": static["path_len"], "tmp_dir_len": static["tmp_dir_len"],
        "transport": static["transport"], "decode_env": static["decode_env"],
        "native_md5": {k: v and v["md5"] for k, v in native.items()},
        "native_built_by_this_run": {k: v and v["built_by_this_run"] for k, v in native.items()},
        "native_stale": {k: v and v["stale"] for k, v in native.items()},
        "filesystem": {k: v["type"] for k, v in static["filesystem"].items()},
        "cpus": len(static["cpus"]), "processes": len(procs), "distinct_cores": delta["distinct_cores"],
        "user_s": round(sum(p["user_s"] for p in procs.values()), 2),
        "system_s": round(sum(p["system_s"] for p in procs.values()), 2),
    }
