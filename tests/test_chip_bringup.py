"""Bring-up guards (PR 21): the device path either runs on the device or says
that it does not — never XLA:CPU under a TPU's name, never an uncounted
fallback — and ``chip_smoke.py`` holds its contract off the chip.

Everything here runs on the CPU.  What it pins is control flow and counters;
the chip itself is exercised by ``python chip_smoke.py`` through the chip tool.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import threading

import pytest

from mochi_tpu.crypto import keys
from mochi_tpu.utils import runtime
from mochi_tpu.verifier.spi import BatchingVerifier, VerifyItem, verifier_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# spelled in two halves so that this file is not itself a second site of the
# option (test_one_site_sets_the_compile_cache_dir greps for it)
CACHE_OPTION = "jax_compilation_" "cache_dir"


def _items(n: int, forged=()):
    kp = keys.generate_keypair()
    out = []
    for i in range(n):
        msg = b"bringup %d" % i
        sig = kp.sign(msg)
        out.append(VerifyItem(kp.public_key, msg + (b"!" if i in forged else b""), sig))
    return out


# ------------------------------------------------------------ chip_smoke.py


def _smoke(args, env_overrides, timeout):
    env = dict(os.environ)
    for k, v in env_overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_chip_smoke_tiny_dry_run_passes_and_is_stamped():
    proc = _smoke(["--tiny"], {"JAX_PLATFORMS": "cpu"}, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec, last = map(json.loads, proc.stdout.splitlines()[-2:])
    # the chip check parses the last line: exactly these keys, nothing else
    assert last == {"ok": True, "device": rec["device"]}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert rec["ok"] is True and rec["dry_run"] is True
    assert rec["device"]["platform"] == "cpu" and rec["device"]["count"] >= 1
    assert "platform" not in rec  # the device's identity is written once
    # every ERROR a child logged is counted (the smoke fails on an unknown one)
    assert isinstance(rec["child_log_errors"], dict)
    assert rec["claim"] is None
    assert set(rec["phases"]) == {
        "cold_boot", "load_query", "probe", "kill_restart_recover", "warm_boot",
    }
    assert all(p["ok"] for p in rec["phases"].values())
    assert rec["acked"] == rec["read_back"] == 8 and rec["failed_operations"] == 0
    assert rec["replay"]["convicted"] == 0 and rec["replicas_with_jax_loaded"] == 0
    assert rec["native_built"] == {"mcode": True, "hbatch": True}
    for life in rec["verifier"].values():
        assert life["host_routed_items"] == 0 and life["fallback_batches"] == 0
        assert life["failed_buckets"] == [] and life["comb_failed_buckets"] == []
    for life in rec["probe"].values():
        assert [b["mismatches"] for b in life.values()] == [0, 0, 0, 0]
        # a quarter of every batch is forged, wrong-key or malformed
        assert all(b["host_valid"] < b["items"] for b in life.values())


def test_chip_smoke_without_an_accelerator_fails_and_prints_no_result():
    # the default invocation needs the chip even under an explicit CPU pin...
    proc = _smoke([], {"JAX_PLATFORMS": "cpu"}, timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    # ...and the dry run needs the pin to be explicit
    proc = _smoke(["--tiny"], {"JAX_PLATFORMS": None}, timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# ------------------------------------------------------- one compile cache


def test_compile_cache_env_set_means_nothing_is_set_in_code(monkeypatch, tmp_path):
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_unset_is_the_fixed_absolute_checkout_path(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert runtime.enable_compile_cache() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    pinned = runtime.enable_compile_cache()
    # under a CPU pin: the host-keyed sub-directory, itself a fixed path
    assert os.path.dirname(pinned) == os.path.join(REPO, ".jax_cache")
    assert os.path.basename(pinned).startswith("cpu-")
    assert pinned == runtime.enable_compile_cache()
    monkeypatch.chdir("/")  # never relative to the cwd
    assert runtime.enable_compile_cache() == pinned
    assert calls == [
        (CACHE_OPTION, os.path.join(REPO, ".jax_cache")),
        (CACHE_OPTION, pinned),
        (CACHE_OPTION, pinned),
        (CACHE_OPTION, pinned),
    ]


def test_one_site_sets_the_compile_cache_dir():
    out = subprocess.run(
        ["grep", "-rln", "--include=*.py", "--include=*.sh",
         "--exclude-dir=.git", "--exclude-dir=chiprun_out", "--exclude-dir=scratch",
         CACHE_OPTION, REPO],
        capture_output=True, text=True,
    ).stdout.split()
    assert [os.path.relpath(p, REPO) for p in out] == ["mochi_tpu/utils/runtime.py"]


# ------------------------------------------- no CPU under the device's name


def test_device_backends_refuse_a_cpu_only_host_without_the_explicit_pin(monkeypatch):
    """JAX here finds only the CPU.  With JAX_PLATFORMS=cpu exported that is a
    requested dry run; without it, it is a host that lost its chip — and the
    service and the in-replica TPU verifier both refuse to start."""
    from mochi_tpu.server import __main__ as server_main
    from mochi_tpu.verifier import service

    assert runtime.device_info(require_accelerator=True)["platform"] == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="no accelerator"):
        runtime.device_info(require_accelerator=True)
    args = argparse.Namespace(
        backend="tpu", warmup="16", signers_file=None, secret_file=None,
        host="127.0.0.1", port=0, admin_port=None,
    )
    with pytest.raises(SystemExit, match="no accelerator"):
        asyncio.run(service.amain(args))
    with pytest.raises(SystemExit, match="no accelerator"):
        server_main._build_verifier(argparse.Namespace(verifier="tpu"), config=None)


def test_device_scripts_share_the_one_gate():
    """Every script under scripts/ that measures the device asks
    ``device_info(require_accelerator=True)``: one rule, one override
    (JAX_PLATFORMS=cpu)."""
    scripts = os.path.join(REPO, "scripts")
    for name in sorted(os.listdir(scripts)):
        if not name.endswith(".py"):
            continue
        src = open(os.path.join(scripts, name)).read()
        assert "MOCHI_ALLOW_CPU" not in src and "_bench_common" not in src, name
        if "enable_compile_cache()" in src:  # it compiles, so it measures
            assert "device_info(require_accelerator=True)" in src, name


# ------------------------------------------------- fallbacks are counted


def test_raising_backend_is_counted_and_still_verifies():
    def broken(items):
        raise RuntimeError("device lost")

    async def run():
        v = BatchingVerifier(broken, max_delay_s=0.0)
        try:
            got = await v.verify_batch(_items(6, forged={1, 4}))
        finally:
            await v.close()
        return got, v

    got, v = asyncio.run(run())
    assert got == [True, False, True, True, False, True]
    assert v.fallback_batches == 1
    assert verifier_stats(v)["fallback_batches"] == 1


def _fake_backend(fail_buckets=(), **kwargs):
    """JaxBatchBackend over a host-engine ``verify_fn`` (no compile): real
    verdicts, and a compile failure at the buckets named."""
    from mochi_tpu.crypto.batch_verify import JaxBatchBackend

    def verify_fn(items, device=None, bucket=None, **_):
        if (bucket or len(items)) in fail_buckets:
            raise RuntimeError("compile refused")
        return [keys.verify(i.public_key, i.message, i.signature) for i in items]

    return JaxBatchBackend(verify_fn=verify_fn, **kwargs)


def _join_warm_threads():
    for t in threading.enumerate():
        if t.name.startswith(("verify-warm-", "comb-warm-")):
            t.join(timeout=30)
            assert not t.is_alive()


def test_failed_bucket_compiles_show_in_the_stats(monkeypatch):
    backend = _fake_backend(fail_buckets={32}, min_device_items=0)
    backend._compile_in_background(32)
    backend.register_signers([keys.generate_keypair().public_key])
    monkeypatch.setattr(
        backend, "_warm_comb", lambda b: (_ for _ in ()).throw(RuntimeError("no"))
    )
    monkeypatch.setattr(backend, "_comb_capable", lambda: True)
    backend._comb_compile_in_background(64)
    _join_warm_threads()
    st = verifier_stats(BatchingVerifier(backend))["device"]
    assert st["failed_buckets"] == [32]
    assert st["comb_failed_buckets"] == [64]
    assert st["platform"] == "cpu" and st["n_devices"] >= 1 and st["device_kind"]


def test_host_and_device_routed_items_are_counted():
    backend = _fake_backend(min_device_items=4)
    assert list(backend(_items(3, forged={0}))) == [False, True, True]
    assert list(backend(_items(8))) == [True] * 8
    st = backend.stats()
    assert (st["host_routed_items"], st["device_items"]) == (3, 8)
    assert st["min_device_items"] == 4


def test_warmup_fails_the_boot_on_a_program_that_answers_valid_to_everything():
    from mochi_tpu.crypto.batch_verify import JaxBatchBackend

    yes = JaxBatchBackend(verify_fn=lambda items, **_: [True] * len(items))
    with pytest.raises(RuntimeError, match="wrong verdicts"):
        yes.warmup([16])
    honest = _fake_backend()
    honest.warmup([16])
    assert honest.stats()["ready_buckets"] == [16]


# ------------------------------------------------------ one chip, one owner


def test_process_cluster_pins_replicas_and_restarts_the_service():
    """Every replica child is pinned to the CPU backend and reports that it
    never imported jax; the service is not pinned, and can be stopped (exit
    code 0 on SIGTERM, waited for) and started again in place."""
    import urllib.request

    from mochi_tpu.client import TransactionBuilder
    from mochi_tpu.testing.process_cluster import ProcessCluster

    async def run():
        async with ProcessCluster(
            n_servers=4, rf=4, n_processes=2, verifier="service",
            admin_base_port=24800, seed=7,
        ) as pc:
            assert pc._spawn_env["JAX_PLATFORMS"] == "cpu"
            assert pc._service_env.get("JAX_PLATFORMS") == os.environ.get("JAX_PLATFORMS")
            seeded = {sid: kp.public_key for sid, kp in pc.keypairs.items()}
            client = pc.client()
            await client.execute_write_transaction(
                TransactionBuilder().write("k1", b"v1").build()
            )
            assert await pc.stop_service() == 0
            await pc.start_service()
            await client.execute_write_transaction(
                TransactionBuilder().write("k2", b"v2").build()
            )
            loop = asyncio.get_running_loop()

            def status(port):
                with urllib.request.urlopen(f"http://127.0.0.1:{port}/status") as r:
                    return json.loads(r.read())

            service = await loop.run_in_executor(None, status, pc.service_admin_port)
            assert service["requests"] > 0 and service["device"] is None
            for sp in pc.processes:
                for j in range(len(sp.server_ids)):
                    st = await loop.run_in_executor(
                        None, status, 24800 + sp.index * 4 + j
                    )
                    assert st["jax_loaded"] is False
            return seeded

    seeded = asyncio.run(run())
    # identities come from the seed, not from fresh entropy per start()
    import hashlib

    expect = keys.keypair_from_seed(hashlib.sha256(b"mochi-pc:7:server-0").digest())
    assert seeded["server-0"] == expect.public_key and len(set(seeded.values())) == 4


def test_start_cluster_refuses_many_chip_owners_and_an_unready_service(tmp_path):
    script = os.path.join(REPO, "scripts", "start_cluster.sh")
    env = dict(os.environ, MOCHI_VERIFIER="tpu")
    proc = subprocess.run(
        ["bash", script, "5", "4", "28301", str(tmp_path / "a")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and "one owner" in proc.stderr
    # a service that dies before READY: no replica is started, non-zero exit
    env = dict(os.environ, MOCHI_VERIFIER="remote", MOCHI_VERIFIER_BACKEND="no-such")
    proc = subprocess.run(
        ["bash", script, "5", "4", "28311", str(tmp_path / "b")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and "not READY" in proc.stderr
    assert not list((tmp_path / "b" / "log").glob("server-*.log"))
