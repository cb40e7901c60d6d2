"""Block-sparse attention's page choice alone, on the chip, at a cell's own
widths: ``paged_attention.select_pages`` with its unit scores from the
Pallas kernel ``select_scores`` against the same choice with the XLA loop
over a group's heads it stands in for (``paged_attention._unit_scores``),
one layer.

    python -m benchmark.tools.select_scores_probe --workload MiniCPM-SALA-pp4.longdoc \
        [--reps 20] [--sweep]

Cases: a decode wave of every slot (``max_slots`` rows, about half of them
running, positions drawn from the cell's traffic), the same wave with every
slot running at the table's end, and one chunk of ``prefill_chunk`` rows at
a few positions. For each it prints the microseconds a choice takes by
each path (``us``) and those of its unit scores alone (``scores_us``), the
largest difference of the unit scores from the loop's, and the share of
(row, K/V head) page sets that are the loop's exactly. ``--sweep`` adds
the kernel's unit scores alone at other tilings (units a copy x query rows
a grid step).

The timed calls are looped INSIDE one jitted program (the next call's
positions a function of the last one's picks, so nothing is hoisted), at
``reps`` and at ``3 * reps`` calls: the slope is a call's device time with
no host cost in it. One JSON object per measurement. A one-off tool: the
benchmark's own runs never run this."""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import run


def per_call_us(call, ops, positions, reps):
    """``call(ops, positions) -> out`` (block ids or scores, never under
    -1): device microseconds a call, and the last call's output."""
    def looped(n, ops, positions):
        def body(_, carry):
            pos, out = carry
            # Never true; ties the next call to this one's result.
            pos = pos + (out.reshape(-1)[0] < -2).astype(jnp.int32)
            return pos, call(ops, pos)
        return jax.lax.fori_loop(0, n, body, (positions, call(ops, positions)))[1]

    fn = jax.jit(looped, static_argnums=0)
    out = jax.block_until_ready(fn(1, ops, positions))          # compiles
    took = {}
    for n in (reps, 3 * reps):
        jax.block_until_ready(fn(n, ops, positions))             # compiles
        t = time.perf_counter()
        out = jax.block_until_ready(fn(n, ops, positions))
        took[n] = time.perf_counter() - t
    return (took[3 * reps] - took[reps]) / (2 * reps) * 1e6, out


def cases(engine: dict, prompt: dict, seed: int = 0) -> list:
    """``(name, positions (S,), valid (S,), rows)`` of the probe."""
    rng = np.random.default_rng(seed)
    slots, top = engine["max_slots"], engine["max_model_len"] - 1
    drawn = np.exp(rng.normal(np.log(prompt["median"]), prompt["sigma"], slots))
    drawn = np.clip(drawn, prompt["min"], top).astype(np.int32)
    running = (rng.permutation(slots) < max(1, round(0.55 * slots))).astype(np.int32)
    chunk = engine["prefill_chunk"]
    out = [("wave", drawn, running, 1),
           ("wave_all_at_end", np.full(slots, top, np.int32), np.ones(slots, np.int32), 1)]
    for at in (8192 - chunk // 2, 24576, top + 1 - chunk):
        out.append((f"chunk_at_{at}", np.asarray([at], np.int32),
                    np.asarray([chunk], np.int32), chunk))
    return out


def main(argv=None):
    import rocket_tpu.ops.paged_attention as paged
    import rocket_tpu.ops.select_scores as kernel

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--reps", type=int, default=20)
    parser.add_argument("--sweep", action="store_true")
    args = parser.parse_args(argv)
    _, cell, config = run.load_cell(args.workload)
    devices = run.find_devices(int(cell["chips"]))
    engine = cell["engine"]
    prompt = run.load_json(run.HERE / "traffic" / f"{cell['traffic_file']}.json")["prompt"]
    cfg = paged.SparseAttentionConfig(**config["sparse_config"])
    hq, hkv, d = (config["num_attention_heads"], config["num_key_value_heads"],
                  config["head_dim"])
    units = cfg.units(engine["max_model_len"])
    layer = 1
    ks = jax.random.split(jax.random.key(0), 2)
    # Two sparse layers of every slot's compressed keys; logits of some 2.25
    # units' spread, as the cell's q/k norm weights give.
    kc = (1.5 * jax.random.normal(ks[0], (2, engine["max_slots"], units, hkv * d))
          ).astype(jnp.bfloat16)
    supported = kernel.select_scores_supported

    def choice(c, valid, slots):
        def call(ops, positions):
            pos = positions[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
            return paged.select_pages(ops["q"], ops["kc"], pos, h_kv=hkv, cfg=cfg,
                                      layer=layer, slots=slots, valid=valid)[0]
        return call

    def scores(c, valid, slots, how, **tiling):
        def call(ops, positions):
            ids = jnp.arange(len(positions), dtype=jnp.int32) if slots is None else slots
            if how == "kernel":
                return kernel.select_scores(ops["q"], ops["kc"], layer, ids, positions,
                                            valid, stride=cfg.kernel_stride,
                                            size=cfg.kernel_size, **tiling)
            pos = positions[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
            return paged._unit_scores(ops["q"], ops["kc"][layer, ids], pos, h_kv=hkv, cfg=cfg)
        return call

    for name, positions, valid, c in cases(engine, prompt):
        s = len(positions)
        q = (1.5 * jax.random.normal(ks[1], (s, c, hq, d))).astype(jnp.bfloat16)
        slots = None if c == 1 else jnp.arange(s, dtype=jnp.int32)
        ops = {"q": q, "kc": kc}
        pos = jnp.asarray(positions)
        valid = jnp.asarray(valid)
        found = {}
        for how in ("loop", "kernel"):
            kernel.select_scores_supported = supported if how == "kernel" else (
                lambda *shape: False)
            us, top = per_call_us(choice(c, valid, slots), ops, pos,
                                  args.reps if how == "kernel" else max(2, args.reps // 4))
            found[how] = np.sort(np.asarray(top), axis=-1)
            alone, _ = per_call_us(scores(c, valid, slots, how), ops, pos,
                                   args.reps if how == "kernel" else max(2, args.reps // 4))
            print(json.dumps({
                "workload": args.workload, "case": name, "how": how, "us": us,
                "scores_us": alone, "slots": s, "rows": c,
                "running": int(np.asarray(valid > 0).sum()),
                "device": devices[0].device_kind,
            }), flush=True)
        kernel.select_scores_supported = supported
        tilings = [(tile, rows) for tile in (256, 512, 1024)
                   for rows in ((1,) if c == 1 else (8, 16))] if args.sweep else []
        for tile, rows in tilings:
            us, _ = per_call_us(scores(c, valid, slots, "kernel", tile=tile, rows=rows),
                                ops, pos, args.reps)
            print(json.dumps({"workload": args.workload, "case": name, "how": "kernel",
                              "tile": tile, "rows": rows, "scores_us": us,
                              "device": devices[0].device_kind}), flush=True)
        full = (pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :])
        pick = np.asarray(valid)[:, None, None] > 0
        same = (found["loop"] == found["kernel"]).all(-1)            # (S, C, Hkv)
        sparse = np.asarray(full >= cfg.dense_len)[..., None] & pick
        rows = kc[layer, jnp.arange(s) if slots is None else slots]
        want = paged._unit_scores(q, rows, full, h_kv=hkv, cfg=cfg)
        got = kernel.select_scores(q, kc, layer, jnp.arange(s, dtype=jnp.int32), pos,
                                   valid, stride=cfg.kernel_stride, size=cfg.kernel_size)
        runs = np.asarray(valid) > 0
        print(json.dumps({
            "workload": args.workload, "case": name, "check": True,
            "max_abs_diff_from_loop": float(np.abs(np.asarray(got) - np.asarray(want))[runs].max()),
            "same_page_sets_share": float(same[np.broadcast_to(sparse, same.shape)].mean()),
        }), flush=True)


if __name__ == "__main__":
    main()
