"""The gated delta rule alone, on the chip, at a cell's own widths: the
Pallas kernels against the literal ``lax.scan`` they stand in for, a chunk
and a wave.

    python -m benchmark.tools.gdn_probe --workload Qwen3-Next-80B-A3B-Instruct-ep4.longchat [--reps 20]

For the chunk (one slot, ``prefill_chunk`` rows, all real and half real)
and the wave (``max_slots`` rows, all running and two
thirds running) it prints the microseconds a call takes (median of
``--reps`` timed calls after a warm one, each ended by
``block_until_ready``) and the largest difference between the kernel's
outputs and the literal recurrence's, for ``o`` relative to its spread and
for ``S`` per head as ``state_gap`` measures it. One JSON
object per line. A one-off tool: the benchmark's own runs never run
this."""

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import run
from benchmark.drivers import serve_linear_moe as driver


def timed(fn, s_all, *args, reps):
    """``fn`` donates the state array, as the engine's programs do: each
    call gets the one before's."""
    o, s_all = jax.block_until_ready(fn(jnp.copy(s_all), *args))
    first = (np.asarray(o), np.asarray(s_all))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        o, s_all = jax.block_until_ready(fn(s_all, *args))
        times.append(time.perf_counter() - t)
    return first, statistics.median(times) * 1e6


def main(argv=None):
    from rocket_tpu.nn import gdn

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--reps", type=int, default=20)
    args = parser.parse_args(argv)
    _, cell, config = run.load_cell(args.workload)
    devices = run.find_devices(int(cell["chips"]))
    base = driver.transformer_config(config).gdn
    slots, chunk = cell["engine"]["max_slots"], cell["engine"]["prefill_chunk"]
    layer = 1

    def operands(s, t, dtype=jnp.bfloat16):
        ks = jax.random.split(jax.random.key(0), 6)
        unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
        heads = lambda k: jax.random.normal(
            k, (s, t, base.num_k_heads, base.head_k_dim), jnp.float32)
        rate = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(0.5), base.num_v_heads))
        return (
            0.1 * jax.random.normal(ks[0], (2, slots, base.num_v_heads, base.head_k_dim,
                                            base.head_v_dim), jnp.float32),
            (unit(heads(ks[1])) * base.head_k_dim ** -0.5).astype(dtype).reshape(s, t, -1),
            unit(heads(ks[2])).astype(dtype).reshape(s, t, -1),
            jax.random.normal(ks[3], (s, t, base.value_dim), jnp.float32).astype(dtype),
            -rate * jax.nn.softplus(jax.random.normal(ks[4], (s, t, base.num_v_heads)) + 0.54),
            jax.nn.sigmoid(jax.random.normal(ks[5], (s, t, base.num_v_heads))),
        )

    def report(name, how, got, want, us):
        (o, s_all), (want_o, want_s) = got, want
        print(json.dumps({
            "case": name, "how": how, "us": us, "device": devices[0].device_kind,
            "o_max_diff_over_std": float(np.abs(o - want_o).max() / want_o.std()),
            "state_gap": max(driver.state_distance(a, b)
                             for a, b in zip(s_all[layer, :4], want_s[layer, :4])),
        }), flush=True)

    for name, valid in (("chunk_all_rows", chunk), ("chunk_half_rows", chunk // 2)):
        ops = operands(1, chunk)
        where = (layer, jnp.asarray([3]), jnp.asarray([valid]), jnp.zeros((1,), bool))
        want = None
        for how in ("scan", "kernel"):
            kernel = how == "kernel"
            call = jax.jit(lambda *a, kernel=kernel: gdn.gdn_chunk(
                *a, cfg=base, kernel=kernel, interpret=False), donate_argnums=0)
            got, us = timed(call, *ops, *where, reps=args.reps if kernel else 2)
            got = (got[0][:, :valid], got[1][:, 3:4])
            want = want or got
            report(name, how, got, want, us)

    for name, running in (("wave_all_slots", slots), ("wave_two_thirds", 2 * slots // 3)):
        ops = operands(slots, 1)
        rows = tuple(a[:, 0] for a in ops[1:])
        valid = (jnp.arange(slots) % 3 != 2).astype(jnp.int32) if running < slots \
            else jnp.ones((slots,), jnp.int32)
        want = None
        for how in ("plain", "kernel"):
            call = jax.jit(lambda *a, how=how: gdn.gdn_step(
                *a, cfg=base, kernel=how == "kernel", interpret=False), donate_argnums=0)
            got, us = timed(call, ops[0], *rows, layer, valid,
                            jnp.zeros((slots,), bool), reps=args.reps)
            want = want or got
            report(name, how, got, want, us)


if __name__ == "__main__":
    main()
