"""Print what a profiler trace of this machine looks like: planes, lines,
event names. Run once by hand on the chip before trusting trace.py."""

import collections
import glob
import os
import sys
import time

import jax
import jax.numpy as jnp


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace_probe"
    os.makedirs(out, exist_ok=True)
    from rocket_tpu.ops.flash_native import flash_fused

    @jax.jit
    def step_probe(x, w):
        return jnp.tanh(x @ w)

    @jax.jit
    def flash_probe(fused):
        return flash_fused(fused, 16, causal=True)

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    fused = jnp.ones((2, 1024, 3 * 1024), jnp.bfloat16)
    jax.block_until_ready((step_probe(x, x), flash_probe(fused)))
    jax.profiler.start_trace(out)
    t0 = time.perf_counter()
    for i in range(5):
        with jax.profiler.TraceAnnotation("bench/module"):
            y = step_probe(x, x)
        with jax.profiler.TraceAnnotation("bench/data_wait"):
            time.sleep(0.01)
        z = flash_probe(fused)
    jax.block_until_ready((y, z))
    print("window_s", time.perf_counter() - t0)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "plugins/profile/*/*.xplane.pb")))[-1]
    print("file", path, os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            print("  LINE", repr(line.name), len(events))
            for name, n in names.most_common(12):
                print("     ", n, repr(name[:110]))
            if events and ("bench/" in " ".join(names) or "XLA" in line.name):
                e = events[0]
                print("      first:", e.start_ns, e.duration_ns,
                      {k: str(v)[:60] for k, v in list(e.stats)[:8]})


if __name__ == "__main__":
    main()
