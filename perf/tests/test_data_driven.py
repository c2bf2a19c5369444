"""A configuration, a traffic mix (with a fault schedule), a fault verb, a
per-layer metric and a cell are each added as new files plus entries, with no
edit to a file that is there."""

import hashlib
import json
import os
import shutil

import pytest

import run

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PERF)
DATA_DIRS = ("configs", "traffic", "layer_metrics", "faults")

THROWAWAY_METRIC = '''
NAME = "gen.ops_per_cpu_s"
UNIT = "ops/s"
LAYER = "load generator"
MOVES = "ops_s"
SOURCE = "host_clock"


def read(snap):
    cpu = snap["generator"]["cpu_seconds"]
    return snap["ops_ok"] / cpu if cpu else None
'''


THROWAWAY_VERB = '''
import signal
import time

KILLS = True


async def run(pc, event, state):
    t0 = time.monotonic()
    pc.kill_replica(event["server_id"], signal.SIGTERM)   # a drain, not a crash
    await pc.process_for(event["server_id"]).proc.wait()
    return {"drained_s": time.monotonic() - t0}
'''


def file_hashes(root):
    out = {}
    for d in DATA_DIRS:
        for name in sorted(os.listdir(os.path.join(root, "perf", d))):
            path = os.path.join(root, "perf", d, name)
            if os.path.isfile(path):
                out[f"{d}/{name}"] = hashlib.sha256(open(path, "rb").read()).hexdigest()
    return out


def add_throwaway(root):
    """Copy the benchmark's data to ``root`` and add one of everything."""
    os.makedirs(os.path.join(root, "perf"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for d in DATA_DIRS:
        shutil.copytree(os.path.join(PERF, d), os.path.join(root, "perf", d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = file_hashes(root)
    config = json.load(open(os.path.join(PERF, "configs", "rf4-n5.json")))
    config.update(name="rf4-n7", replicas=7, replica_processes=7, storage_engine="paged")
    config["rehearsal"].update(replicas=7, recordcount=48)
    with open(os.path.join(root, "perf", "configs", "rf4-n7.json"), "w") as fh:
        json.dump(config, fh)
    with open(os.path.join(root, "perf", "traffic", "ycsb-b-uniform.json"), "w") as fh:
        json.dump({"readproportion": 0.95, "updateproportion": 0.05,
                   "requestdistribution": "uniform",
                   "faults": [{"at_s": 1.0, "do": "drain_replica", "replica": "seeded"},
                              {"at_s": 2.0, "do": "restart_replica", "replica": "same"}]}, fh)
    with open(os.path.join(root, "perf", "faults", "drain_replica.py"), "w") as fh:
        fh.write(THROWAWAY_VERB)
    with open(os.path.join(root, "perf", "layer_metrics", "gen.ops_per_cpu_s.py"), "w") as fh:
        fh.write(THROWAWAY_METRIC)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "rf4-n7", "source": "a test", "file": "perf/configs/rf4-n7.json",
                             "reduced": [], "why": "a throw-away configuration"})
    bench["workloads"].append({"name": "n7-ycsb-b", "config": "rf4-n7", "traffic": "ycsb-b-uniform",
                               "chips": 1, "why": "a throw-away cell"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("n7-ycsb-b")
    bench["per_layer"].append({"name": "gen.ops_per_cpu_s", "unit": "ops/s", "better": "higher",
                               "source": "host_clock", "layer": "load generator", "moves": "ops_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return before


SNAP = {
    "platform": "cpu", "window_s": 10.0, "ops_ok": 1000, "updates_ok": 500,
    "latency": {"update_p95_ms": 900.0, "read_p95_ms": 400.0},
    "generator": {"processes": 2, "cpu_seconds": 5.0,
                  "stage_seconds": {"write1-phase": [0.001, 0.002, 0.003],
                                    "write2-fanout-wait": [0.010, 0.020, 0.030]}},
    "before": {"replica_cpu": 1.0,
               "service": {"batches_flushed": 10, "device_items": 0, "host_routed_items": 100},
               "replicas": {"drain_count": 100, "drain_frames": 150.0, "fsyncs": 40}},
    "after": {"replica_cpu": 11.0,
              "service": {"batches_flushed": 110, "device_items": 512, "host_routed_items": 1636},
              "replicas": {"drain_count": 1100, "drain_frames": 1650.0, "fsyncs": 290}},
    "trace": {},
}


def test_a_cell_a_config_a_mix_and_a_metric_are_added_by_files(tmp_path):
    root = str(tmp_path / "checkout")
    before = add_throwaway(root)
    after = file_hashes(root)
    assert {k: after[k] for k in before} == before       # nothing that was there changed
    assert len(after) == len(before) + 4
    data = run.load_cell(root, "n7-ycsb-b")
    assert data["config"]["replicas"] == 7 and data["config"]["storage_engine"] == "paged"
    assert data["traffic"]["requestdistribution"] == "uniform"
    # the schedule's verbs are the new file and a shipped one, found by name
    assert [v.__name__ for v in data["verbs"]] == ["fault_verb_drain_replica", "fault_verb_restart_replica"]
    metrics = run.read_layer_metrics(data["layer_dir"], data["bench"], "n7-ycsb-b", SNAP)
    assert metrics["gen.ops_per_cpu_s"] == {"value": 200.0, "unit": "ops/s"}
    # and the cells that were there still resolve, with the new metric too
    old = run.load_cell(root, "rf4-ycsb-a")
    assert "gen.ops_per_cpu_s" in run.read_layer_metrics(old["layer_dir"], old["bench"], "rf4-ycsb-a", SNAP)


EXPECT = {
    "gen.busy_share": 25.0,
    "client.write1_p50_ms": 2.0,
    "client.write2_wait_p50_ms": 20.0,
    "transport.frames_per_drain": 1.5,
    "replica.cpu_ms_per_op": 10.0,
    "verifier.items_per_flush": 20.48,
    "verifier.device_item_share": 25.0,
    "store.fsyncs_per_update": 0.5,
}   # device.idle_share: nothing to read off the TPU, so it is left out


def test_the_committed_readers_on_a_canned_snapshot():
    # the cell below capacity reports its update tail end to end, its
    # write-path layers move that tail, and its read tail is a per-layer reading
    data = run.load_cell(REPO, "rf4-ycsb-a")
    got = {k: v["value"] for k, v in
           run.read_layer_metrics(data["layer_dir"], data["bench"], "rf4-ycsb-a", SNAP).items()}
    assert got == dict({k: pytest.approx(v) for k, v in EXPECT.items()}, **{"tail.read_p95_ms": 400.0})
    # the cell at capacity reports the rate alone: the same readers under
    # their ``.ops`` names, and the tails as per-layer readings
    data = run.load_cell(REPO, "n64-ycsb-a")
    got = {k: v["value"] for k, v in
           run.read_layer_metrics(data["layer_dir"], data["bench"], "n64-ycsb-a", SNAP).items()}
    moves_ops = ("gen.busy_share", "transport.frames_per_drain", "replica.cpu_ms_per_op")
    expect = {(k if k in moves_ops else k + ".ops"): pytest.approx(v) for k, v in EXPECT.items()}
    expect.update({"tail.update_p95_ms": 900.0, "tail.read_p95_ms": 400.0})
    assert got == expect


def test_idle_share_reads_the_window_trace_on_a_tpu():
    data = run.load_cell(REPO, "n64-ycsb-a")
    snap = dict(SNAP, platform="tpu",
                trace={"window": {"device_planes": 0, "busy_s": 0.0, "window_s": 5.0}})
    got = run.read_layer_metrics(data["layer_dir"], data["bench"], "n64-ycsb-a", snap)
    assert got["device.idle_share.ops"]["value"] == 100.0
    snap["trace"]["window"].update(device_planes=1, busy_s=0.5)
    got = run.read_layer_metrics(data["layer_dir"], data["bench"], "n64-ycsb-a", snap)
    assert got["device.idle_share.ops"]["value"] == pytest.approx(90.0)
    data = run.load_cell(REPO, "rf4-ycsb-a")
    got = run.read_layer_metrics(data["layer_dir"], data["bench"], "rf4-ycsb-a", snap)
    assert got["device.idle_share"]["value"] == pytest.approx(90.0)


def test_a_metric_moving_an_unreported_metric_is_left_out():
    # n64-ycsb-a reports no update tail, so what moves that tail is not its
    data = run.load_cell(REPO, "n64-ycsb-a")
    got = run.read_layer_metrics(data["layer_dir"], data["bench"], "n64-ycsb-a", SNAP)
    assert "client.write1_p50_ms" not in got and "gen.busy_share" in got
    data = run.load_cell(REPO, "rf4-ycsb-a")
    got = run.read_layer_metrics(data["layer_dir"], data["bench"], "rf4-ycsb-a", SNAP)
    assert "client.write1_p50_ms" in got and "tail.update_p95_ms" not in got


def test_benchmark_json_and_the_files_agree():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    readers = {n[:-3] for n in os.listdir(os.path.join(PERF, "layer_metrics")) if n.endswith(".py")}
    assert readers == {m["name"] for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(REPO, c["file"])))
        assert cfg["name"] == c["name"] and set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["quorum"] == 2 * cfg["f"] + 1 and cfg["f"] == (cfg["rf"] - 1) // 3
    for w in bench["workloads"]:
        run.load_cell(REPO, w["name"])
    with pytest.raises(run.RunFailure):
        run.load_cell(REPO, "no-such-cell")


def test_a_cell_whose_mix_names_a_verb_without_a_file_is_refused_before_the_boot(tmp_path):
    root = str(tmp_path / "checkout")
    add_throwaway(root)
    os.remove(os.path.join(root, "perf", "faults", "drain_replica.py"))
    with pytest.raises(run.RunFailure, match="traffic 'ycsb-b-uniform': unknown fault verb 'drain_replica'"):
        run.load_cell(root, "n7-ycsb-b")
