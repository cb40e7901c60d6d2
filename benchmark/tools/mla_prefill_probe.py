"""The latent-attention chunk alone, on the chip, at a cell's own widths:
the Pallas kernel ``mla_prefill`` against the XLA loop it stands in for
(``LatentAttention._attend_chunk``), one layer, one slot.

    python -m benchmark.tools.mla_prefill_probe --workload dots.vlm1.inst-ep16.docqa [--reps 20] [--sweep]

For live contexts of 512, 2,048, 4,096 and 6,144 rows (the chunk is the
last ``prefill_chunk`` of them) it prints the microseconds a call takes of
the loop, of the chunk path as the program runs it (the table's gather +
the kernel) and of the kernel alone on rows gathered beforehand, each
beside the MXU's least time for the call's steps of 512 rows
(up-projection, scores and ``P.V`` of every head over a whole tile, at
the device's peak) and the largest difference from the loop's output.
``--sweep`` adds the kernel alone at other tilings (heads of a grid step
x key tile).

The timed calls are looped INSIDE one jitted program (``lax.fori_loop``,
the next call's ``positions`` and table a function of the last one's
output, so nothing is hoisted, the gather neither), at ``reps`` and at
``3 * reps`` calls: the slope is a call's device time with no host cost in
it (PERF.md section 7 i). One JSON
object per measurement. A one-off tool: the benchmark's own runs never run
this."""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import run
from benchmark.drivers import serve_latent_moe as driver


def per_call_us(call, ops, positions, reps):
    """``call(ops, positions) -> out``: device microseconds a call, and the
    last call's output. ``ops`` (a dict of arrays) is an argument of the
    jitted loop, not a constant of it."""
    def looped(n, ops, positions):
        def body(_, carry):
            pos, _ = carry
            # Never true; ties the next call, its table's gather included,
            # to this one's result.
            step = lambda out: (out[0, 0, :1] > 1e30).astype(jnp.int32)
            out = call(dict(ops, table=ops["table"] + step(carry[1])), pos)
            return pos + step(out), out
        return jax.lax.fori_loop(0, n, body, (positions, call(ops, positions)))[1]

    fn = jax.jit(looped)
    out = jax.block_until_ready(fn(1, ops, positions))     # compiles
    took = {}
    for n in (reps, 3 * reps):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(n, ops, positions))
        took[n] = time.perf_counter() - t
    return (took[3 * reps] - took[reps]) / (2 * reps) * 1e6, out


def main(argv=None):
    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.models.transformer import TransformerLM
    from rocket_tpu.ops.latent_prefill import mla_prefill

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--reps", type=int, default=20)
    parser.add_argument("--sweep", action="store_true")
    args = parser.parse_args(argv)
    _, cell, config = run.load_cell(args.workload)
    devices = run.find_devices(int(cell["chips"]))
    peak = run.load_json(run.HERE / "peaks.json")[devices[0].device_kind]["bf16_flops_per_s"]
    tc = driver.transformer_config(config)
    attn = TransformerLM(tc).blocks[0].attn
    h, nope, rope, v, rank = (attn.num_heads, attn.nope, attn.rope, attn.v_dim,
                              attn.kv_lora_rank)
    c, bl = cell["engine"]["prefill_chunk"], cell["engine"]["block_len"]
    mb = config["max_position_embeddings"] // bl
    dtype = jnp.dtype(tc.activation_dtype)
    ks = jax.random.split(jax.random.key(0), 4)
    layer = 1
    ops = {
        "w": (jax.random.normal(ks[0], (rank, h * (nope + v))) * rank ** -0.5).astype(dtype),
        "q_nope": jax.random.normal(ks[1], (1, c, h, nope)).astype(dtype),
        "q_rope": jax.random.normal(ks[2], (1, c, h, rope)).astype(dtype),
        # Two layers of one slot's table, the pages in a shuffled order.
        "pages": jax.random.normal(ks[3], (2, 1 + mb, bl, attn.config.pool_lanes)).astype(dtype),
        "table": jnp.asarray(np.random.default_rng(0).permutation(np.arange(1, 1 + mb))[None],
                             jnp.int32),
    }

    ops["rows"] = paged.paged_gather(ops["pages"], ops["table"], layer=layer)

    def chunk_path(ops, pos):
        return attn._attend_chunk({"kv_b": {"w": ops["w"]}}, ops["q_nope"], ops["q_rope"],
                                  ops["pages"], ops["table"], pos, layer)

    def loop_path(ops, pos):
        # The portable path is chosen by the backend: say it is the CPU.
        on_cpu, paged._on_cpu = paged._on_cpu, lambda: True
        try:
            return chunk_path(ops, pos)
        finally:
            paged._on_cpu = on_cpu

    def kernel_alone(**tiling):
        return lambda ops, pos: mla_prefill(
            ops["q_nope"], ops["q_rope"], ops["rows"],
            attn._up_weights({"kv_b": {"w": ops["w"]}}, dtype), pos, scale=attn.scale, **tiling)

    step_flops = 2 * 512 * h * (rank * (nope + v) + c * (nope + rope) + c * v)
    paths = [("loop", loop_path, max(2, args.reps // 4)), ("chunk_path", chunk_path, args.reps),
             ("kernel", kernel_alone(), args.reps)]
    if args.sweep:      # tilings that fit the kernel's scoped VMEM at these widths
        paths += [(f"kernel.h{heads}.kv{block_kv}",
                   kernel_alone(heads=heads, block_kv=block_kv), args.reps)
                  for heads, block_kv in ((4, 512), (8, 512), (2, 1024))]
    for live in (512, 2048, 4096, 6144):
        positions = jnp.asarray([live - c], jnp.int32)
        want = None
        for how, call, reps in paths:
            us, out = per_call_us(call, ops, positions, reps)
            out = np.asarray(out, np.float32)
            want = out if want is None else want
            print(json.dumps({
                "live_rows": live, "how": how, "us": us, "device": devices[0].device_kind,
                "mxu_floor_us": -(-live // 512) * step_flops / peak * 1e6,
                "max_abs_diff_from_loop": float(np.abs(out - want).max()),
                "out_std": float(want.std()),
            }), flush=True)


if __name__ == "__main__":
    main()
