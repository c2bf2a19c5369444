"""Record a tiny profiler trace of two jitted programs and print its shape:
planes, lines, and the first events of each line.  Run on the chip to see what
``perf/xplane.py`` has to read; writes the trace under ``chiprun_out/``."""
import glob
import os
import sys
import time

import jax
import jax.numpy as jnp

out = os.path.join("chiprun_out", "trace_shape")
os.makedirs(out, exist_ok=True)


@jax.jit
def small_mul(x):
    return (x @ x).sum()


@jax.jit
def small_add(x):
    return (x + 1).sum()


x = jnp.ones((512, 512), jnp.float32)
small_mul(x).block_until_ready()
small_add(x).block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
t0 = time.monotonic()
jax.profiler.start_trace(out, profiler_options=opts)
for _ in range(3):
    small_mul(x).block_until_ready()
    time.sleep(0.05)
    small_add(x).block_until_ready()
jax.profiler.stop_trace()
print("traced seconds", time.monotonic() - t0)
print("devices", jax.devices(), jax.devices()[0].memory_stats())
path = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
print(path, os.path.getsize(path))
pd = jax.profiler.ProfileData.from_file(path)
for plane in pd.planes:
    print("PLANE", repr(plane.name))
    for line in plane.lines:
        events = list(line.events)
        print("  LINE", repr(line.name), len(events))
        for ev in events[:6]:
            print("     ", repr(ev.name), ev.start_ns, ev.duration_ns, dict(list(ev.stats)[:4]) if hasattr(ev, "stats") else "")
