"""The readings the limits of a ``serve_linear_moe`` cell are set from, on
the chip at the cell's own size (``tools/control_hybrid_ssm`` is the Mamba
family's, ``tools/control_latent_moe`` the latent family's):

    python -m benchmark.tools.control_linear_moe --workload <cell> \
        --seeds 1,2 [--control-seeds 1] [--controls fp8] [--seconds 20] [--probe-only]

For every seed the PROGRAM runs a short window and its numbers are printed
(the lower readings). For every seed in ``--control-seeds`` two CONTROLS
are read over the same sample, each the reference put in the program's
place in the nearest precision below one the configuration states:
``fp8`` — the operands of every matrix multiplication rounded to fp8
(below the bfloat16 of the weights and activations) — and ``bf16_state`` —
the matrix state ``S`` carried in bfloat16 (below its float32). The
reference's other arithmetic stays float32, so ``bf16_state`` reads what
the state's precision ALONE moves. ``--probe-only`` serves no traffic: for
every seed the engine is built and warmed, the state probe
(``drivers/serve_linear_moe.py``) is served and compared, and
``state_gap`` is printed, of the program and (``--control-seeds``) of both
controls — half a minute a seed where a window takes three. Every record
carries the harness's own judgement of its numbers against the cell's
``limits`` (``common.judge``, what decides a run's ``correct``): a
control's must read ``"correct": false``, and ``over`` names the limits it
passed. One JSON object per line."""

import argparse
import json

import jax
import jax.numpy as jnp

from benchmark import run
from benchmark.drivers import serve_linear_moe as driver
from benchmark.reference import qwen3_next_lm as ref
from benchmark.traffic import generator

CONTROLS = {
    "fp8": {"quant": ref.fp8},
    "bf16_state": {"state_dtype": jnp.bfloat16},
}


def emit(record):
    print(json.dumps(record), flush=True)


def judged(numbers: dict, limits: dict) -> dict:
    """``run.execute``'s judgement of the numbers that were read (a probe
    alone reads no token): ``correct`` and the limits passed."""
    read = {k: v for k, v in numbers.items() if v is not None}
    ok, checks = driver.common.judge(read, {k: limits[k] for k in read})
    return {"correct": ok, "over": [k for k, c in checks.items()
                                    if not c["value"] <= c["limit"]]}


def probe_only(cell, config, seed, span):
    """One seed's probe alone, in the shape of a run's result."""
    before = {id(a) for a in jax.live_arrays()}
    engine = driver.build_engine(cell, config, seed)
    driver.warm_up(engine, config, cell["engine"]["prefill_chunk"])
    probe = driver.state_probe(engine, config, cell["compare"]["state_probe"], seed)
    peak = driver.common.peak_bytes(jax.devices()[:1])
    del engine
    driver.free_since(before)
    gap = driver.reference_numbers(config, seed, [], span=span, probe=probe)[1]
    return {"sample": [], "state_probe": probe, "memory_peak_bytes": peak,
            "numbers": driver.summarise([], float(cell["compare"]["margin"]), gap)}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--controls", default=",".join(CONTROLS))
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--probe-only", action="store_true")
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    _, cell, config = run.load_cell(args.workload)
    run.configure_compile_cache()
    run.find_devices(int(cell["chips"]))
    span = int(generator.load_mix(cell["traffic_file"])["answer"]["max"])
    margin = float(cell["compare"]["margin"])
    for seed in sorted(set(seeds) | set(control_seeds)):
        if args.probe_only:
            result = probe_only(cell, config, seed, span)
        else:
            result = driver.run(cell, config, seed=seed, seconds=args.seconds)
        emit({"seed": seed, "side": "program", "numbers": result["numbers"],
              **judged(result["numbers"], cell["limits"]),
              **{k: result[k] for k in (
                  "compared_tokens", "compared_requests", "finished", "failed",
                  "end_to_end", "memory_peak_bytes") if k in result}})
        if seed in control_seeds:
            for name in args.controls.split(","):
                got, state_gap = driver.reference_numbers(
                    config, seed, result["sample"], span=span,
                    probe=result["state_probe"], control=True, **CONTROLS[name])
                numbers = driver.summarise(got, margin, state_gap)
                emit({"seed": seed, "side": name, "numbers": numbers,
                      **judged(numbers, cell["limits"]),
                      "per_request": [float(g.max()) for g, _ in got]})


if __name__ == "__main__":
    main()
