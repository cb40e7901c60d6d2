"""The readings the limits of a ``serve_hybrid_ssm`` cell are set from, on
the chip at the cell's own size (``tools/control`` is GPT-2's,
``tools/control_latent_moe`` the latent family's):

    python -m benchmark.tools.control_hybrid_ssm --workload <cell> \
        --seeds 1,2 [--control-seeds 1] [--state-seeds 1] [--seconds 20]

For every seed the PROGRAM runs a short window and its numbers are printed
(the lower readings). For every seed in ``--control-seeds`` two CONTROLS
are read over the same sample, each the reference put in the program's
place in the nearest precision below one the configuration states:
``fp8`` — the operands of every matrix multiplication rounded to fp8
(below the bfloat16 of the weights and activations) — and ``bf16_state`` —
the recurrent state ``h`` carried in bfloat16 (below its float32). The
reference's other arithmetic stays float32, so ``bf16_state`` reads what
the state's precision ALONE moves; what it adds to a program whose every
activation is bfloat16 is the third control: for every seed in
``--state-seeds`` the PROGRAM runs once more with ``h`` rounded to
bfloat16 after every token (``program_bf16_state``: the one function that
writes the recurrence, ``rocket_tpu.nn.ssm._advance``, wrapped), judged
like a sound run against the float32 reference. Run the state seeds in a
process of their own: a program traced before the wrap keeps its float32
``h``. ``--probe-only`` serves no traffic: for every seed of ``--seeds``
or ``--state-seeds`` the engine is built and warmed, the state probe
(``drivers/serve_hybrid_ssm.py``) is served and compared, and ``state_gap``
is printed — half a minute a seed where a window takes three. One JSON
object per line."""

import argparse
import json

import jax
import jax.numpy as jnp

from benchmark import run
from benchmark.drivers import serve_hybrid_ssm as driver
from benchmark.reference import jamba_lm as ref
from benchmark.traffic import generator

CONTROLS = {
    "fp8": {"quant": ref.fp8},
    "bf16_state": {"state_dtype": jnp.bfloat16},
}


def emit(record):
    print(json.dumps(record), flush=True)


def low_state_program():
    """From here on the program carries ``h`` in bfloat16: every token's
    new state is rounded to it (and held in the float32 array)."""
    from rocket_tpu.nn import ssm

    advance = ssm._advance

    def rounded(h, *args):
        h, _ = advance(h, *args)
        h = h.astype(jnp.bfloat16).astype(jnp.float32)
        # y from the state as it is carried on.
        return h, jnp.sum(h * args[3], axis=-2, keepdims=True)

    ssm._advance = rounded


def probe_only(cell, config, seed, span):
    """One seed's probe alone, in the shape of a run's result."""
    before = {id(a) for a in jax.live_arrays()}
    engine = driver.build_engine(cell, config, seed)
    driver.warm_up(engine, config, cell["engine"]["prefill_chunk"])
    probe = driver.state_probe(engine, config, cell["compare"]["state_probe"], seed)
    del engine
    driver.free_since(before)
    gaps = driver.reference_numbers(config, seed, [], span=span, probe=probe)[1]
    return {"sample": [], "state_probe": probe, "state_gaps": gaps,
            "numbers": driver.summarise([], gaps)}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--state-seeds", default="")
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--probe-only", action="store_true")
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    state_seeds = [int(s) for s in args.state_seeds.split(",") if s]

    _, cell, config = run.load_cell(args.workload)
    run.configure_compile_cache()
    run.find_devices(int(cell["chips"]))
    span = int(generator.load_mix(cell["traffic_file"])["answer"]["max"])
    if state_seeds:
        if seeds or control_seeds:
            parser.error("--state-seeds run in a process of their own")
        low_state_program()
    side = "program_bf16_state" if state_seeds else "program"
    for seed in sorted(set(seeds) | set(control_seeds) | set(state_seeds)):
        if args.probe_only:
            result = probe_only(cell, config, seed, span)
        else:
            result = driver.run(cell, config, seed=seed, seconds=args.seconds)
        emit({"seed": seed, "side": side,
              "numbers": result["numbers"], "state_gaps": result["state_gaps"],
              **{k: result[k] for k in (
                  "compared_tokens", "compared_requests", "finished", "failed",
                  "end_to_end", "memory_peak_bytes") if k in result}})
        if seed in control_seeds:
            for name, low in CONTROLS.items():
                got, state_gaps = driver.reference_numbers(
                    config, seed, result["sample"], span=span,
                    probe=result["state_probe"], control=True, **low)
                emit({"seed": seed, "side": name,
                      "numbers": driver.summarise(got, state_gaps),
                      "state_gaps": state_gaps,
                      "per_request": [float(g.max()) for g in got]})


if __name__ == "__main__":
    main()
