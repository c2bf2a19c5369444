"""The verifier service on the record (ISSUE 24): stage timers in one
``Metrics`` registry, host spans through ``obs/hostspan.py``, builds under way
in ``/status``, pinned program names, and the ``/profile`` route."""

import asyncio
import gc
import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from mochi_tpu.crypto import keys
from mochi_tpu.obs import hostspan
from mochi_tpu.utils.metrics import Metrics, Timer
from mochi_tpu.verifier import stages
from mochi_tpu.verifier.service import RemoteVerifier, ServiceAdminServer, VerifierService
from mochi_tpu.verifier.spi import (
    BatchingVerifier, CachingVerifier, CpuVerifier, VerifyItem, verifier_stats,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=120))


def make_items(n, forged=(), tag=b""):
    kp = keys.generate_keypair()
    out = []
    for i in range(n):
        msg = b"stages %d " % i + tag
        out.append(VerifyItem(kp.public_key, msg + (b"!" if i in forged else b""), kp.sign(msg)))
    return out


def fake_backend(**kwargs):
    """JaxBatchBackend over a host-engine ``verify_fn``: real verdicts, real
    routing and counters, no compile."""
    from mochi_tpu.crypto.batch_verify import JaxBatchBackend

    def verify_fn(items, device=None, bucket=None, **_):
        return [keys.verify(i.public_key, i.message, i.signature) for i in items]

    return JaxBatchBackend(verify_fn=verify_fn, **kwargs)


class FakeSpans:
    """A span factory that records enter and exit, in order."""

    def __init__(self):
        self.events = []

    def __call__(self, name, **args):
        outer = self

        class Span:
            def __enter__(self):
                outer.events.append(("enter", name, args))

            def __exit__(self, *exc):
                outer.events.append(("exit", name, args))

        return Span()

    def names(self, kind="enter"):
        return [name for k, name, _ in self.events if k == kind]


@pytest.fixture
def spans():
    fake = FakeSpans()
    hostspan.install(fake)
    try:
        yield fake
    finally:
        hostspan.install(None)


# ---------------------------------------------------------------- the shim


def test_span_is_a_shared_noop_until_a_factory_is_installed():
    assert not hostspan.installed()
    a, b = hostspan.span("mochi.x", items=3), hostspan.span("mochi.y")
    assert a is b
    with a as entered:
        assert entered is a


def test_spans_nest_through_an_installed_factory(spans):
    assert hostspan.installed()
    with hostspan.span("mochi.outer", items=2):
        with hostspan.span("mochi.inner"):
            pass
    assert spans.events == [
        ("enter", "mochi.outer", {"items": 2}), ("enter", "mochi.inner", {}),
        ("exit", "mochi.inner", {}), ("exit", "mochi.outer", {"items": 2}),
    ]
    hostspan.install(None)
    assert not hostspan.installed() and hostspan.span("mochi.outer") is hostspan.span("mochi.z")


def test_a_replica_side_process_stays_off_jax():
    code = (
        "import sys\n"
        "import mochi_tpu.verifier.spi as spi, mochi_tpu.server.replica, mochi_tpu.verifier.stages\n"
        "from mochi_tpu.obs import hostspan\n"
        "v = spi.BatchingVerifier(lambda items: [True] * len(items))\n"
        "with hostspan.span('mochi.x', items=1): pass\n"
        "assert v.metrics.snapshot()['histograms']['verifier.flush-items']['count'] == 0\n"
        "print(sorted(m for m in sys.modules if m == 'jax' or m.startswith('jax.')))\n"
    )
    done = subprocess.run([sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True,
                          env=dict(os.environ, PYTHONPATH=REPO), timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == "[]"


# ----------------------------------------------------------------- timers


def test_timer_snapshot_has_the_exact_lifetime_sum():
    t = Timer(window=4)  # percentiles forget; count and sum do not
    for ms in (1.5, 2.5, 4.0, 8.0, 16.0, 32.0):
        t.record(ms / 1e3)
    snap = t.snapshot()
    assert snap["count"] == 6 and snap["sum_ms"] == pytest.approx(64.0, abs=1e-9)
    assert snap["mean_ms"] == pytest.approx(snap["sum_ms"] / snap["count"])


def test_stage_timers_tick_once_per_chunk_and_agree_with_the_route_counters(spans):
    backend = fake_backend(min_device_items=8)
    v = BatchingVerifier(backend, max_delay_s=0.001)
    assert v.metrics is backend.metrics  # one registry for the composition

    async def main():
        try:
            # three callers inside one linger: one chunk of 12, to the device
            got = await asyncio.gather(*(v.verify_batch(make_items(4, forged={1})) for _ in range(3)))
            # and one small chunk, to the host
            got.append(await v.verify_batch(make_items(3)))
        finally:
            await v.close()
        return got

    got = run(main())
    assert got == [[True, False, True, True]] * 3 + [[True] * 3]
    snap = v.metrics.snapshot()
    timers, hist = snap["timers"], snap["histograms"][stages.FLUSH_ITEMS]
    assert v.batches_flushed == 2
    assert timers[stages.QUEUE_WAIT]["count"] == 2          # per chunk, not per item or call
    assert timers[stages.FLUSH_DEVICE]["count"] == 1 and timers[stages.FLUSH_HOST]["count"] == 1
    assert hist["count"] == 2 and hist["sum"] == 15 and hist["buckets"] == {"16": 2}
    st = backend.stats()
    assert (st["device_items"], st["host_routed_items"]) == (12, 3)
    assert not hasattr(v, "items_verified")                   # the histogram's sum has a reader
    # the linger is in the wait: at least the 1 ms it slept
    assert timers[stages.QUEUE_WAIT]["sum_ms"] >= 2 * 0.9
    # the same boundaries as spans, carrying numbers already in hand
    flushes = [args for k, name, args in spans.events if k == "enter" and name == stages.SPAN_FLUSH]
    assert [(f["items"], f["route"], f["bucket"]) for f in flushes] == [(12, "device", 16), (3, "host", 0)]
    assert all(abs(f["epoch_us"] - time.time_ns() // 1000) < 60e6 for f in flushes)
    chunks = [args for k, name, args in spans.events if k == "enter" and name == stages.SPAN_CHUNK]
    assert [c["items"] for c in chunks] == [12, 3] and all(c["wait_us"] >= 900 for c in chunks)
    assert spans.names().count(stages.SPAN_HOST_VERIFY) == 1


def test_launch_stages_tick_in_the_registry_of_the_backend_that_called(spans):
    import numpy as np

    from mochi_tpu.crypto import batch_verify as bv

    backend = fake_backend()
    items = make_items(5)
    bv._tls.metrics = backend.metrics  # what JaxBatchBackend.__call__ does before it calls down
    try:
        args, pre_ok = bv._prepare_padded(items, 16)
        assert pre_ok.all() and args[0].shape[0] == 16
        assert bv._readback((np.ones(16, bool), np.ones(5, bool)), 5) == [True] * 5
    finally:
        del bv._tls.metrics
    timers = backend.metrics.snapshot()["timers"]
    assert timers[stages.PREPARE]["count"] == 1 and timers[stages.READBACK]["count"] == 1
    assert spans.names() == [stages.SPAN_PREPARE, stages.SPAN_READBACK]
    # a bare call, outside any backend, ticks in the module's own registry
    before = bv._MODULE_METRICS.timers[stages.PREPARE].count
    bv._prepare_padded(items, 16)
    assert bv._MODULE_METRICS.timers[stages.PREPARE].count == before + 1
    assert backend.metrics.timers[stages.PREPARE].count == 1


def test_memo_lookup_ticks_once_per_call_and_counts_its_items(spans):
    cv = CachingVerifier(CpuVerifier())
    items = make_items(6)

    async def main():
        assert await cv.verify_batch(items) == [True] * 6
        assert await cv.verify_batch(items[:4]) == [True] * 4
        assert await cv.verify_aggregate(b"k" * 32, items[:3]) is True

    run(main())
    snap = cv.metrics.snapshot()
    assert snap["timers"][stages.MEMO_LOOKUP]["count"] == 3
    assert snap["counters"][stages.MEMO_ITEMS] == 13
    memo = [args["items"] for k, name, args in spans.events if k == "enter" and name == stages.SPAN_MEMO]
    assert memo == [6, 4, 3]
    # a span never crosses an await: each memo span closes before the next opens
    assert spans.names("enter") == spans.names("exit")


def test_memo_settle_ticks_once_per_settled_call_with_the_calls_misses(spans):
    cv = CachingVerifier(CpuVerifier(), max_entries=8)
    items = make_items(6)

    async def main():
        assert await cv.verify_batch(items) == [True] * 6                      # 6 misses
        assert await cv.verify_batch(items[:4]) == [True] * 4                  # all hits: nothing to settle
        assert await cv.verify_batch(items[2:] + make_items(3, tag=b"n")) == [True] * 7   # 3 misses
        assert await cv.verify_aggregate(b"k" * 32, items[:3]) is True         # one attestation: 1 miss

    run(main())
    snap = cv.metrics.snapshot()
    assert snap["timers"][stages.MEMO_SETTLE]["count"] == 3 and snap["timers"][stages.MEMO_LOOKUP]["count"] == 4
    settled = [args["items"] for k, name, args in spans.events if k == "enter" and name == stages.SPAN_MEMO_SETTLE]
    assert settled == [6, 3, 1] and sum(settled) == cv.misses
    # the ninth verdict pushed the first out, and the one extractor says so on both operator surfaces
    assert cv.memo_evictions == 1 == verifier_stats(cv)["memo_evictions"]
    assert spans.names("enter") == spans.names("exit")  # never across an await


def test_service_rpc_ticks_once_per_rpc_and_spans_only_its_synchronous_ends(spans):
    async def main():
        svc = VerifierService(port=0, verifier=CpuVerifier())
        await svc.start()
        rv = RemoteVerifier("127.0.0.1", svc.bound_port)
        try:
            assert await rv.verify_batch(make_items(7, forged={2})) == [True, True, False] + [True] * 4
            assert await rv.verify_batch(make_items(2)) == [True, True]
            gc.collect()
        finally:
            await rv.close()
            await svc.close()
        return svc

    svc = run(main())
    snap = svc.status()["stages"]
    assert snap["timers"][stages.SERVICE_RPC]["count"] == 2 == svc.requests
    assert snap["timers"][stages.MEMO_LOOKUP]["count"] == 2 and snap["counters"][stages.MEMO_ITEMS] == 9
    assert snap["timers"][stages.MEMO_SETTLE]["count"] == 2 and svc.status()["verifier"]["memo_evictions"] == 0
    assert snap["timers"][stages.GC]["count"] >= 1
    order = [(k, n) for k, n, _ in spans.events if n != stages.SPAN_GC and n != stages.SPAN_TICK]
    one_rpc = [("enter", stages.SPAN_RPC_ADMIT), ("exit", stages.SPAN_RPC_ADMIT),
               ("enter", stages.SPAN_MEMO), ("exit", stages.SPAN_MEMO),
               ("enter", stages.SPAN_MEMO_SETTLE), ("exit", stages.SPAN_MEMO_SETTLE),
               ("enter", stages.SPAN_RPC_REPLY), ("exit", stages.SPAN_RPC_REPLY)]
    assert order == one_rpc * 2
    replies = [a for k, n, a in spans.events if k == "enter" and n == stages.SPAN_RPC_REPLY]
    assert all(r["wait_us"] > 0 for r in replies)
    assert stages.SPAN_TICK in spans.names()  # spans are installed, so the loop marks its CPU time
    assert svc._on_gc not in gc.callbacks     # close() took the callback out again


# ----------------------------------------------------------------- builds


def _join_warm_threads():
    for t in threading.enumerate():
        if t.name.startswith(("verify-warm-", "comb-warm-")):
            t.join(timeout=30)
            assert not t.is_alive()


def test_a_background_build_is_listed_while_it_runs_and_gone_after(spans):
    from mochi_tpu.crypto.batch_verify import LADDER_PROGRAM, JaxBatchBackend

    entered, release = threading.Event(), threading.Event()

    def verify_fn(items, device=None, bucket=None, **_):
        if len(items) == 32:  # the dummy batch of the bucket being built
            entered.set()
            assert release.wait(30)
        return [True] * len(items)

    backend = JaxBatchBackend(verify_fn=verify_fn, min_device_items=0)
    svc = VerifierService(port=0, verifier=BatchingVerifier(backend), cache=False)
    assert svc.metrics is backend.metrics
    st = svc.status()
    assert (st["building"], st["builds_started"], st["builds_finished"], st["programs_built"]) == ([], 0, 0, 0)
    backend._compile_in_background(32)
    assert entered.wait(30)
    st = svc.status()
    assert [(b["bucket"], b["program"]) for b in st["building"]] == [(32, LADDER_PROGRAM)]
    assert st["building"][0]["since_s"] >= 0 and (st["builds_started"], st["builds_finished"]) == (1, 0)
    release.set()
    _join_warm_threads()
    st = svc.status()
    assert (st["building"], st["builds_started"], st["builds_finished"]) == ([], 1, 1)
    assert st["stages"]["timers"][stages.BUILD]["count"] == 1
    assert backend.stats()["ready_buckets"] == [32]
    built = [a for k, n, a in spans.events if k == "enter" and n == stages.SPAN_BUILD]
    assert built == [{"bucket": 32, "program": LADDER_PROGRAM}]


def test_a_failed_build_leaves_the_list_and_is_not_counted_as_finished(monkeypatch):
    from mochi_tpu.crypto.comb import COMB_PROGRAM

    def verify_fn(items, device=None, bucket=None, **_):
        raise RuntimeError("compile refused")

    from mochi_tpu.crypto.batch_verify import JaxBatchBackend

    backend = JaxBatchBackend(verify_fn=verify_fn, min_device_items=0)
    backend._compile_in_background(64)
    backend.register_signers([keys.generate_keypair().public_key])
    seen = []

    def warm_comb(bucket):
        seen.append(backend.build_state()["building"])
        raise RuntimeError("no")

    monkeypatch.setattr(backend, "_warm_comb", warm_comb)
    monkeypatch.setattr(backend, "_comb_capable", lambda: True)
    backend._comb_compile_in_background(128)
    _join_warm_threads()
    state = backend.build_state()
    assert (state["building"], state["builds_started"], state["builds_finished"]) == ([], 2, 0)
    assert [(b["bucket"], b["program"]) for b in seen[0]] == [(128, COMB_PROGRAM)]
    st = backend.stats()
    assert st["failed_buckets"] == [64] and st["comb_failed_buckets"] == [128]
    assert backend.metrics.timers[stages.BUILD].count == 2  # a failed build took time too


def test_boot_warmup_counts_a_build_for_each_program():
    backend = fake_backend()
    backend.warmup([16, 32])
    state = backend.build_state()
    assert (state["building"], state["builds_started"], state["builds_finished"]) == ([], 2, 2)


# ---------------------------------------------------------- program names


def _shapes(m):
    import jax
    import jax.numpy as jnp

    from mochi_tpu.crypto import field as F

    sd = jax.ShapeDtypeStruct
    return (sd((m, F.NLIMBS), jnp.int32), sd((m,), jnp.int32), sd((m, F.NLIMBS), jnp.int32),
            sd((m,), jnp.int32), sd((m, 32), jnp.uint8), sd((m, 32), jnp.uint8))


def _lowered(which):
    import jax
    import jax.numpy as jnp

    from mochi_tpu.crypto import batch_verify as bv, comb
    from mochi_tpu.parallel import sharded

    table = jax.ShapeDtypeStruct((comb.N_WINDOWS * comb.N_ENTRIES, comb.ROW_WIDTH), jnp.int32)
    y_a, sign_a, y_r, sign_r, s, h = _shapes(16)
    key_idx = jax.ShapeDtypeStruct((16,), jnp.int32)
    if which == "ladder":
        return bv.LADDER_PROGRAM, bv._verify_packed_jit.lower(y_a, sign_a, y_r, sign_r, s, h)
    if which == "comb":
        return comb.COMB_PROGRAM, comb._verify_comb_jit.lower(table, key_idx, y_r, sign_r, s, h)
    mesh = sharded.make_mesh(2)
    if which == "sharded-ladder":
        fn = sharded.make_sharded_verify_packed(mesh)
        return sharded.SHARDED_LADDER_PROGRAM, fn.lower(y_a, sign_a, y_r, sign_r, s, h)
    fn = sharded.make_sharded_verify_comb(mesh)
    return sharded.SHARDED_COMB_PROGRAM, fn.lower(table, key_idx, y_r, sign_r, s, h)


@pytest.mark.parametrize("which", ["ladder", "comb", "sharded-ladder", "sharded-comb"])
def test_the_lowered_module_is_named_by_its_constant(which):
    name, lowered = _lowered(which)
    text = lowered.as_text()
    assert text.split("\n", 1)[0].startswith(f"module @{name} ")
    # the phase scopes are metadata: in the locations, not in the program
    assert "mochi_" not in text
    located = lowered.as_text(debug_info=True)
    loop = "mochi_comb" if "comb" in which else "mochi_ladder"
    for scope in ("mochi_scalar_unpack", "mochi_decompress", loop, "mochi_compare"):
        assert scope in located, scope


def test_the_harness_reads_the_programs_by_the_names_the_product_pins():
    sys.path.insert(0, os.path.join(REPO, "perf"))
    try:
        import hostspans
        import layer_reader

        # the reader imports the harness's ``schedule``: loaded while perf/ is on the path, so
        # that the test does not lean on another file of its worker having imported it first
        settle = layer_reader.load(os.path.join(REPO, "perf", "layer_metrics", "recovery.memo_settle_us_per_item.py"))
    finally:
        sys.path.remove(os.path.join(REPO, "perf"))
    from mochi_tpu.crypto import batch_verify as bv, comb

    assert (hostspans.LADDER_PROGRAM, hostspans.COMB_PROGRAM) == (bv.LADDER_PROGRAM, comb.COMB_PROGRAM)
    assert hostspans.SPAN_PREFIX == stages.SPAN_PREFIX
    assert settle.TIMER == stages.MEMO_SETTLE
    named = {n for _, names in hostspans.CAUSES for n in names} | {hostspans.TICK}
    ours = {v for k, v in vars(stages).items() if k.startswith("SPAN_") and k != "SPAN_PREFIX"}
    # every span has a cause, or is the tick; PR 25's resolve span and PR 30's memo-settle span (loop
    # thread both) have none until a `benchmark` PR may edit perf/hostspans.py (ROADMAP A0b): their
    # instants read no_span
    assert named == ours - {stages.SPAN_RESOLVE, stages.SPAN_MEMO_SETTLE}


def test_verdicts_under_the_scopes_match_the_host_engine():
    """Both device programs at bucket 16, on valid, forged and malformed
    lanes, against the host engine: the scopes changed no verdict."""
    from mochi_tpu.crypto import batch_verify as bv, comb

    kps = [keys.generate_keypair() for _ in range(3)]
    items = []
    for i in range(12):
        kp = kps[i % 3]
        msg = b"scoped %d" % i
        sig = kp.sign(msg)
        if i % 4 == 1:
            msg += b"!"                                   # forged
        if i == 6:
            sig = sig[:32] + b"\xff" * 32                 # S >= L
        if i == 10:
            sig = b"\xff" * 32 + sig[32:]                 # R not canonical
        items.append(VerifyItem(kp.public_key, msg, sig))
    want = [keys.verify(it.public_key, it.message, it.signature) for it in items]
    assert want.count(True) == 7
    assert bv.verify_batch(items, bucket=16) == want
    registry = comb.SignerRegistry()
    registry.register_all([kp.public_key for kp in kps])
    assert bv.verify_batch(items, bucket=16, registry=registry) == want


# ------------------------------------------------------------ the surfaces


def test_status_keeps_every_key_the_smoke_test_and_the_benchmark_select():
    from mochi_tpu.verifier.tpu import TpuBatchVerifier

    sys.path.insert(0, os.path.join(REPO, "perf"))
    try:
        import cluster as perf_cluster
    finally:
        sys.path.remove(os.path.join(REPO, "perf"))
    import chip_smoke

    async def main():
        tpu = TpuBatchVerifier(signers=[keys.generate_keypair().public_key])
        svc = VerifierService(port=0, verifier=tpu, device={"platform": "cpu", "warmup_seconds": 0.0})
        assert svc.metrics is tpu.metrics is tpu.backend.metrics is svc.verifier.metrics
        admin = ServiceAdminServer(svc, port=0)
        await svc.start()
        await admin.start()
        try:
            raw = await asyncio.to_thread(
                lambda: urllib.request.urlopen(f"http://127.0.0.1:{admin.bound_port}/status", timeout=5).read())
            prom = (await asyncio.to_thread(
                lambda: urllib.request.urlopen(f"http://127.0.0.1:{admin.bound_port}/metrics.prom", timeout=5).read()
            )).decode()
        finally:
            await admin.close()
            await svc.close()
        return json.loads(raw), prom

    status, prom = run(main())
    smoke, bench = chip_smoke.service_summary(status), perf_cluster.service_counters(status)
    assert set(bench) <= set(smoke) and smoke["registered_signers"] == 1 and bench["min_device_items"] == 384
    for key in ("stages", "building", "builds_started", "builds_finished", "programs_built", "loop_thread_cpu_s"):
        assert key in status, key
    assert status["loop_thread_cpu_s"] > 0 and status["programs_built"] == 0
    assert set(status["stages"]) == {"timers", "counters", "gauges", "histograms"}
    assert "items_verified" not in json.dumps(status)
    # the flattened counters as before, and the stage registry in the shared families
    assert 'mochi_verifier_service{name="requests"} 0' in prom
    assert 'mochi_verifier_service{name="verifier_inner_device_min_device_items"} 384' in prom
    assert 'mochi_verifier_service{name="stages' not in prom
    assert 'mochi_histogram_count{name="verifier.flush-items",service="verifier-service"} 0' in prom


def test_profile_route_writes_one_capture_and_refuses_a_second(tmp_path):
    async def fetch(port, target):
        def get():
            try:
                with urllib.request.urlopen(f"http://127.0.0.1:{port}{target}", timeout=60) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as exc:
                return exc.code, json.loads(exc.read())

        return await asyncio.to_thread(get)

    async def main():
        plain = VerifierService(port=0, verifier=CpuVerifier())
        svc = VerifierService(port=0, verifier=CpuVerifier(), profile_dir=str(tmp_path))
        admins = [ServiceAdminServer(plain, port=0), ServiceAdminServer(svc, port=0)]
        for a in admins:
            await a.start()
        try:
            assert (await fetch(admins[0].bound_port, "/profile?seconds=1"))[0] == 404
            assert (await fetch(admins[1].bound_port, "/profile?seconds=31"))[0] == 400
            assert (await fetch(admins[1].bound_port, "/profile"))[0] == 400
            first = asyncio.create_task(fetch(admins[1].bound_port, "/profile?seconds=1.5"))
            while not svc.profiling:
                await asyncio.sleep(0.01)
            assert (await fetch(admins[1].bound_port, "/profile?seconds=1"))[0] == 409
            assert (await fetch(admins[1].bound_port, "/status"))[0] == 200  # served meanwhile
            code, body = await first
            assert code == 200 and body["seconds"] == 1.5 and not svc.profiling
            return body["path"]
        finally:
            for a in admins:
                await a.close()

    path = run(main())
    assert os.path.dirname(path) == str(tmp_path)
    assert glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb"))
