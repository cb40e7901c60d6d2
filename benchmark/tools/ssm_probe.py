"""The recurrence alone, on the chip, at a cell's own widths: the Pallas
kernel against the ``lax.scan`` it stands in for, a chunk and a wave.

    python -m benchmark.tools.ssm_probe --workload AI21-Jamba2-3B.reason [--reps 20]

For the chunk (one slot, ``prefill_chunk`` rows, all real and half real) and
the wave (``max_slots`` rows, all running) it prints the microseconds a call
takes (median of ``--reps`` timed calls after a warm one, each ended by
``block_until_ready``) and the largest difference between the two
implementations' outputs. One JSON object per line. A one-off tool: the
benchmark's own runs never run this."""

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp

from benchmark import run


def timed(fn, h_all, *args, reps):
    """``fn`` donates the state array, as the engine's programs do: each
    call gets the one before's."""
    y, h_all = jax.block_until_ready(fn(jnp.copy(h_all), *args))
    first = (y, jnp.copy(h_all))    # the next call takes ``h_all`` away
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        y, h_all = jax.block_until_ready(fn(h_all, *args))
        times.append(time.perf_counter() - t)
    return first, statistics.median(times) * 1e6


def main(argv=None):
    from rocket_tpu.nn import ssm

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--reps", type=int, default=20)
    args = parser.parse_args(argv)
    _, cell, config = run.load_cell(args.workload)
    devices = run.find_devices(int(cell["chips"]))
    di = config["mamba_expand"] * config["hidden_size"]
    n, slots = config["mamba_d_state"], cell["engine"]["max_slots"]
    chunk = cell["engine"]["prefill_chunk"]
    key = jax.random.key(0)

    def operands(s, t):
        ks = jax.random.split(key, 6)
        return (
            jax.random.normal(ks[0], (2, slots, n, di), jnp.float32),
            jax.nn.softplus(jax.random.normal(ks[1], (s, t, di), jnp.float32) - 3),
            jax.random.normal(ks[2], (s, t, di), jnp.float32),
            jax.random.normal(ks[3], (s, t, n), jnp.float32),
            jax.random.normal(ks[4], (s, t, n), jnp.float32),
            -jnp.exp(jax.random.normal(ks[5], (n, di), jnp.float32)),
        )

    cases = [
        ("chunk_all_rows", ssm.ssm_scan, operands(1, chunk), (jnp.asarray([3]),),
         jnp.asarray([chunk])),
        ("chunk_half_rows", ssm.ssm_scan, operands(1, chunk), (jnp.asarray([3]),),
         jnp.asarray([chunk // 2])),
        ("wave_all_slots", ssm.ssm_step, operands(slots, 1), (),
         jnp.ones((slots,), jnp.int32)),
    ]
    for name, fn, ops, where, valid in cases:
        fresh = jnp.zeros(valid.shape, bool)
        record = {"case": name, "device": devices[0].device_kind}
        outs = {}
        for how in ("kernel", "scan"):
            call = jax.jit(lambda *a, how=how: fn(
                *a, kernel=how == "kernel", interpret=False), donate_argnums=0)
            outs[how], record[f"{how}_us"] = timed(
                call, *ops, 1, *where, valid, fresh, reps=args.reps)
        record["max_abs_diff_y"] = float(jnp.abs(outs["kernel"][0] - outs["scan"][0]).max())
        record["max_abs_diff_h"] = float(jnp.abs(outs["kernel"][1] - outs["scan"][1]).max())
        print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
