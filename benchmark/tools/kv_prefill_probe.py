"""The K/V pool's long-table chunk attention alone, on the chip, at a
cell's own widths: the Pallas kernel ``kv_prefill`` against the XLA loop it
stands in for (``paged_attention._attend_chunk_live``), one layer, one
slot.

    python -m benchmark.tools.kv_prefill_probe --workload Laguna-XS.2-ep4.codeagent \
        [--reps 20] [--sweep]

For live contexts of 1,024, 2,048, 4,096, 8,192 and 15,360 rows (the chunk
is the last ``prefill_chunk`` of them) it prints the microseconds a call
takes of the loop and of the kernel, each beside the causal floor (the
scores and ``P.V`` that the causal rule requires, ``4 x Hq x D`` operations
a pair of query row and visible key, at the device's peak) and the largest
difference from the loop's output. ``--sweep`` adds the kernel at other
tilings (query heads of a grid step x key tile).

The timed calls are looped INSIDE one jitted program (``mla_prefill_probe.
per_call_us``: the next call's table a function of the last one's output,
so nothing is hoisted), at ``reps`` and at ``3 * reps`` calls: the slope is
a call's device time with no host cost in it. One JSON object per
measurement. A one-off tool: the benchmark's own runs never run this."""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import run
from benchmark.tools.mla_prefill_probe import per_call_us

LIVE_ROWS = (1024, 2048, 4096, 8192, 15360)


def attention_widths(config: dict) -> tuple[int, int, int]:
    """``(Hq, Hkv, D)`` of the configuration's layers that cache K/V: a
    full-attention layer's heads where the layers are of two kinds."""
    hq = config["num_attention_heads"]
    kinds = config.get("layer_types")
    if kinds and "num_attention_heads_per_layer" in config:
        hq = config["num_attention_heads_per_layer"][kinds.index("full_attention")]
    d = config.get("head_dim") or config["hidden_size"] // hq
    return hq, config["num_key_value_heads"], d


def causal_flops(hq: int, d: int, start: int, valid: int) -> int:
    """Scores and ``P.V`` of ``valid`` query rows from ``start`` on, each
    over the keys it sees."""
    return 4 * hq * d * (valid * start + valid * (valid + 1) // 2)


def main(argv=None):
    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.ops.kv_prefill import _heads_block, kv_prefill

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--reps", type=int, default=20)
    parser.add_argument("--sweep", action="store_true")
    args = parser.parse_args(argv)
    _, cell, config = run.load_cell(args.workload)
    devices = run.find_devices(int(cell["chips"]))
    peak = run.load_json(run.HERE / "peaks.json")[devices[0].device_kind]["bf16_flops_per_s"]
    hq, hkv, d = attention_widths(config)
    engine = cell["engine"]
    c, bl = engine["prefill_chunk"], engine["block_len"]
    mb = engine["max_model_len"] // bl
    dtype = jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 3)
    layer = 1
    # Two layers of one slot's table, the pages in a shuffled order.
    pool = (2, 1 + mb, bl, hkv * d)
    ops = {
        "q": jax.random.normal(ks[0], (1, c, hq, d)).astype(dtype),
        "k": jax.random.normal(ks[1], pool).astype(dtype),
        "v": jax.random.normal(ks[2], pool).astype(dtype),
        "table": jnp.asarray(np.random.default_rng(0).permutation(np.arange(1, 1 + mb))[None],
                             jnp.int32),
    }
    valid = jnp.asarray([c], jnp.int32)

    def loop(ops, pos):
        return paged._attend_chunk_live(ops["q"], ops["k"], ops["v"], ops["table"], pos,
                                        valid, layer)

    def kernel(**tiling):
        return lambda ops, pos: kv_prefill(ops["q"], ops["k"], ops["v"], ops["table"], pos,
                                           valid, layer, **tiling)

    paths = [("loop", loop, max(2, args.reps // 4)), ("kernel", kernel(), args.reps)]
    if args.sweep:
        hb = _heads_block(hq // hkv, d)
        tilings = {(hb, 512), (max(1, hb // 2), 1024), (1, 1024)} - {(hb, 1024)}
        paths += [(f"kernel.h{heads}.kv{block_kv}",
                   kernel(heads=heads, block_kv=block_kv), args.reps)
                  for heads, block_kv in sorted(tilings)
                  if (hq // hkv) % heads == 0]
    for live in LIVE_ROWS:
        positions = jnp.asarray([live - c], jnp.int32)
        want = None
        for how, call, reps in paths:
            us, out = per_call_us(call, ops, positions, reps)
            out = np.asarray(out, np.float32)
            want = out if want is None else want
            print(json.dumps({
                "workload": args.workload, "live_rows": live, "how": how, "us": us,
                "device": devices[0].device_kind,
                "causal_floor_us": causal_flops(hq, d, live - c, c) / peak * 1e6,
                "max_abs_diff_from_loop": float(np.abs(out - want).max()),
                "out_std": float(want.std()),
            }), flush=True)


if __name__ == "__main__":
    main()
