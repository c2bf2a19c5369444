"""Record a small profiler trace with host spans beside device events, and
print its shape.  Run on the chip; ``perf/tests/test_hostspans.py`` reads the
trace it leaves as ``perf/tests/data/host_spans.xplane.pb`` (copy it from
``chiprun_out/span_shape/``).

Two jitted programs under the names the product pins for its ladder and comb,
each a loop inside ``jax.named_scope`` phases (so the print shows where a
scope's path lands on an ``XLA Ops`` event without the HLO protos).  The spans
are the product's, through its own shim, on two threads:

    main thread                                              second thread
    flush(device, 512) { prepare 20 ms, dispatch ladder, readback }
    flush(host) { host_verify 60 ms }                        rpc.admit, memo, rpc.reply x3 (in the 60 ms)
    flush(device, 512) { prepare 0, dispatch comb, readback }
    sleep 80 ms under no span
    flush(device, 512) { prepare 10 ms, dispatch comb, readback }     tick x2

so one long gap lies under ``host_verify`` (the rpc spans under it lose by
precedence), one under nothing, and the prepares hold the short ones.
"""
import glob
import os
import shutil
import sys
import threading
import time

import jax
import jax.numpy as jnp
from jax import lax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from mochi_tpu.obs import hostspan  # noqa: E402
from mochi_tpu.verifier import stages  # noqa: E402

out = os.path.join("chiprun_out", "span_shape")
shutil.rmtree(out, ignore_errors=True)
os.makedirs(out)


def verify_prepared_packed(x):
    with jax.named_scope("mochi_decompress"):
        y = x @ x
    with jax.named_scope("mochi_ladder"):
        y = lax.fori_loop(0, 64, lambda i, c: c @ x * 0.5, y)
    with jax.named_scope("mochi_compare"):
        return y.sum()


def verify_comb_prepared(x):
    with jax.named_scope("mochi_comb"):
        y = lax.fori_loop(0, 16, lambda i, c: c + x, x)
    with jax.named_scope("mochi_compare"):
        return y.sum()


ladder, comb = jax.jit(verify_prepared_packed), jax.jit(verify_comb_prepared)
x = jnp.ones((512, 512), jnp.float32)
ladder(x).block_until_ready()
comb(x).block_until_ready()
hostspan.install(jax.profiler.TraceAnnotation)


def device_flush(program, prepare_s):
    with hostspan.span(stages.SPAN_CHUNK, items=512, wait_us=2500), \
            hostspan.span(stages.SPAN_FLUSH, items=512, route="device", bucket=512,
                          epoch_us=time.time_ns() // 1000):
        with hostspan.span(stages.SPAN_PREPARE):
            time.sleep(prepare_s)
        with hostspan.span(stages.SPAN_DISPATCH):
            launched = program(x)
        with hostspan.span(stages.SPAN_READBACK):
            launched.block_until_ready()


def loop_thread(go, done):
    go.wait()
    for _ in range(3):
        with hostspan.span(stages.SPAN_RPC_ADMIT):
            time.sleep(0.002)
        with hostspan.span(stages.SPAN_MEMO, items=43):
            time.sleep(0.003)
        with hostspan.span(stages.SPAN_RPC_REPLY, wait_us=7000):
            time.sleep(0.001)
    done.wait()
    for _ in range(2):
        with hostspan.span(stages.SPAN_TICK, loop_cpu_us=int(time.thread_time() * 1e6),
                           epoch_us=time.time_ns() // 1000):
            pass
        time.sleep(0.004)


opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
opts.enable_hlo_proto = False
go, done = threading.Event(), threading.Event()
second = threading.Thread(target=loop_thread, args=(go, done), name="loop-like")
second.start()
t0 = time.monotonic()
jax.profiler.start_trace(out, profiler_options=opts)
device_flush(ladder, 0.020)
with hostspan.span(stages.SPAN_CHUNK, items=43, wait_us=2100), \
        hostspan.span(stages.SPAN_FLUSH, items=43, route="host", bucket=0,
                      epoch_us=time.time_ns() // 1000), \
        hostspan.span(stages.SPAN_HOST_VERIFY):
    go.set()
    time.sleep(0.060)
device_flush(comb, 0.0)
time.sleep(0.080)
done.set()
device_flush(comb, 0.010)
second.join()
jax.profiler.stop_trace()
traced = time.monotonic() - t0
print("traced seconds", traced)
print("devices", jax.devices())
path = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
shutil.copy(path, os.path.join(out, "host_spans.xplane.pb"))
with open(os.path.join(out, "traced_seconds.txt"), "w") as fh:
    fh.write(repr(traced))
print(path, os.path.getsize(path))
pd = jax.profiler.ProfileData.from_file(path)
for plane in pd.planes:
    print("PLANE", repr(plane.name))
    for line in plane.lines:
        events = list(line.events)
        mochi = [ev for ev in events if ev.name.startswith("mochi.")]
        print("  LINE", repr(line.name), len(events), "events,", len(mochi), "mochi.*")
        shown = mochi if plane.name == "/host:CPU" else events
        for ev in shown[:14]:
            print("     ", repr(ev.name)[:100], ev.start_ns, ev.duration_ns, dict(ev.stats))
sys.path.insert(0, os.path.join(REPO, "perf"))
import hostspans  # noqa: E402

print(hostspans.reduce_file(path, traced))
