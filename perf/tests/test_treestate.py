"""``perf/treestate.py``: what a run says of its checkout and of where its
processes ran, without a cluster."""

import os
import sysconfig
import time

import treestate

__all__ = [
    "test_a_source_newer_than_its_build_is_stale_and_a_missing_build_is_none",
    "test_a_module_whose_file_changed_under_the_run_was_built_by_it",
    "test_the_facts_name_what_two_preparations_of_one_commit_can_differ_in",
    "test_a_process_is_placed_and_a_restarted_one_reports_its_end_alone",
    "test_the_results_tree_object_has_the_keys_a_ledger_line_is_read_by",
    "test_a_replicas_pace_is_taken_over_the_window_and_anew_after_a_restart",
]

SUFFIX = sysconfig.get_config_var("EXT_SUFFIX") or ".so"


def fake_tree(root, stale=(), missing=()):
    ndir = root / "mochi_tpu" / "native"
    ndir.mkdir(parents=True)
    (root / "perf").mkdir()
    (root / "perf" / "run.py").write_text("")
    now = time.time()
    for name in treestate.NATIVE:
        src = ndir / (name[1:] + ".c")
        src.write_text("/* source */")
        os.utime(src, (now - 100, now - 100))
        if name in missing:
            continue
        so = ndir / (name + SUFFIX)
        so.write_bytes(b"built " + name.encode())
        when = now - 200 if name in stale else now - 50
        os.utime(so, (when, when))
    return str(root)


def test_a_source_newer_than_its_build_is_stale_and_a_missing_build_is_none(tmp_path):
    got = treestate.native_modules(fake_tree(tmp_path, stale=("_mcode",), missing=("_hbatch",)))
    assert got["_mcode"]["stale"] is True and got["_hbatch"] is None
    assert got["_mcode"]["bytes"] == len(b"built _mcode") and len(got["_mcode"]["md5"]) == 32


def test_a_module_whose_file_changed_under_the_run_was_built_by_it(tmp_path):
    repo = fake_tree(tmp_path, missing=("_hbatch",))
    before = treestate.native_modules(repo)
    so = tmp_path / "mochi_tpu" / "native" / ("_hbatch" + SUFFIX)
    so.write_bytes(b"built now")
    facts = treestate.static_facts(repo, repo, str(tmp_path), "uds", before)
    assert facts["native"]["_hbatch"]["built_by_this_run"] is True
    assert facts["native"]["_mcode"]["built_by_this_run"] is False
    assert facts["native"]["_hbatch"]["stale"] is False


def test_the_facts_name_what_two_preparations_of_one_commit_can_differ_in(tmp_path):
    repo = fake_tree(tmp_path)
    facts = treestate.static_facts(repo, repo, str(tmp_path), "tcp", treestate.native_modules(repo))
    assert facts["path"] == repo and facts["path_len"] == len(repo)
    assert facts["tmp_dir_len"] == len(str(tmp_path)) and facts["transport"] == "tcp"
    assert facts["size"]["files"] == 5 and facts["size"]["bytes"] > 0 and "capped" not in facts["size"]
    # a checkout that holds far more than a checkout's files is not walked to its end inside set-up
    assert treestate.tree_size(repo, limit=3) == {"files": 3, "bytes": treestate.tree_size(repo, limit=3)["bytes"],
                                                  "capped": True}
    assert set(facts["filesystem"]) == {"out_dir", "tmp_dir"}
    assert facts["filesystem"]["out_dir"]["type"] and facts["filesystem"]["out_dir"]["mount"]
    assert len(facts["cpus"]) >= 1
    assert {"optimize", "hash_randomization", "dont_write_bytecode", "PYTHONHASHSEED"} <= set(facts["python"])
    assert facts["run_py_mode"].startswith("0o") and facts["run_py_mtime"] <= facts["now"]


def test_a_process_is_placed_and_a_restarted_one_reports_its_end_alone():
    here = treestate.places({"harness": os.getpid(), "gone": 2 ** 22 + 12345})
    assert here["gone"] is None
    me = here["harness"]
    assert 0 <= me["core"] < (os.cpu_count() or 1) * 64 and me["threads"] >= 1
    sum(i * i for i in range(200_000))
    later = treestate.places({"harness": os.getpid(), "replicas-0": os.getpid()})
    # a process that was started again inside the window has less CPU time than
    # the one the window began with: its end is all there is to report
    began = dict(me, user_s=me["user_s"] + 1e6)
    delta = treestate.window_delta({"harness": me, "replicas-0": began, "gone": None}, later)
    assert set(delta["processes"]) == {"harness", "replicas-0"}
    assert delta["processes"]["harness"]["user_s"] >= 0 and delta["processes"]["harness"]["core"][0] == me["core"]
    assert delta["processes"]["replicas-0"]["core"][0] is None
    assert delta["processes"]["replicas-0"]["user_s"] == later["replicas-0"]["user_s"]
    assert delta["distinct_cores"][0] == 1 and delta["distinct_cores"][1] == 1


def test_the_results_tree_object_has_the_keys_a_ledger_line_is_read_by(tmp_path):
    repo = fake_tree(tmp_path)
    facts = treestate.static_facts(repo, repo, str(tmp_path), "uds", treestate.native_modules(repo))
    placed = treestate.places({"harness": os.getpid()})
    tree = treestate.result_object(facts, treestate.window_delta(placed, treestate.places({"harness": os.getpid()})))
    # ISSUE 43 step 4: path length, transport, the two modules' md5s, the cores the processes shared
    assert set(tree) == {"path_len", "tmp_dir_len", "transport", "native_md5", "native_built_by_this_run",
                         "native_stale", "decode_env", "filesystem", "cpus", "processes", "distinct_cores",
                         "user_s", "system_s"}
    assert set(tree["native_md5"]) == set(treestate.NATIVE) and all(tree["native_md5"].values())
    assert tree["processes"] == 1 and tree["distinct_cores"] == [1, 1] and tree["transport"] == "uds"





def test_a_replicas_pace_is_taken_over_the_window_and_anew_after_a_restart():
    import cluster as cl

    def status(sid, drains, frames, lat, fsyncs, fsync_ms, snaps=0, wal=0):
        return {"server_id": sid, "storage": {"snapshots": snaps, "wal_entries": wal},
                "batching": {"transport.drain-frames": {"count": drains, "sum": frames},
                             "transport.drain-latency": {"count": drains, "sum": lat},
                             "storage-fsync-ms": {"count": fsyncs, "sum": fsync_ms}}}

    before = cl.replica_pace([status("server-0", 100, 150.0, 0.02, 10, 20.0), status("server-1", 100, 150.0, 0.02, 10, 20.0)])
    after = cl.replica_pace([status("server-0", 300, 650.0, 0.06, 30, 80.0, 1, 500),
                             # server-1 was killed and started again: its counters began anew
                             status("server-1", 40, 200.0, 0.05, 4, 6.0, 0, 90),
                             {"server_id": "server-2", "storage": {}, "batching": {}}])
    got = cl.pace_delta(before, after)
    assert got["server-0"] == {"frames": 500, "drains": 200, "drain_ms": 0.2, "fsyncs": 20, "fsync_ms": 3.0,
                               "snapshots": 1, "wal_entries": 500}
    assert got["server-1"]["drains"] == 40 and got["server-1"]["frames"] == 200 and got["server-1"]["fsync_ms"] == 1.5
    assert got["server-2"]["drain_ms"] is None and got["server-2"]["fsync_ms"] is None
