"""The readings the limits of a ``serve_latent_moe`` cell are set from, on
the chip at the cell's own size (``tools/control`` is GPT-2's):

    python -m benchmark.tools.control_latent_moe --workload <cell> \
        --seeds 1,2 [--control-seeds 1] [--seconds 20] [--margins 0,0.001,0.002,0.005]

For every seed the PROGRAM runs a short window and its numbers are printed
(the lower readings), with the gap and the share left out at each of
``--margins`` so that the margin can be chosen from one run. For every seed
in ``--control-seeds`` the CONTROL is read over the same sample: the
reference put in the program's place with the operands of every matrix
multiplication rounded to fp8 (the nearest precision below the
configuration's bfloat16). One JSON object per line."""

import argparse
import json

from benchmark import run
from benchmark.drivers import serve_latent_moe as driver
from benchmark.reference import deepseek_v3_lm as ref
from benchmark.traffic import generator


def emit(record):
    print(json.dumps(record), flush=True)


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--margins", default="")
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    margins = [float(m) for m in args.margins.split(",") if m]

    _, cell, config = run.load_cell(args.workload)
    run.configure_compile_cache()
    run.find_devices(int(cell["chips"]))
    span = int(generator.load_mix(cell["traffic_file"])["answer"]["max"])
    rule = cell["compare"]
    for seed in sorted(set(seeds) | set(control_seeds)):
        result = driver.run(cell, config, seed=seed, seconds=args.seconds)
        emit({"seed": seed, "side": "program", "numbers": result["numbers"],
              "compared_tokens": result["compared_tokens"],
              "compared_requests": result.get("compared_requests"),
              "finished": result["finished"], "failed": result["failed"],
              "end_to_end": result["end_to_end"],
              "memory_peak_bytes": result["memory_peak_bytes"]})
        for margin in margins:
            emit({"seed": seed, "side": "program", "margin": margin,
                  **driver.summarise(result["reference"], margin)})
        if seed in control_seeds:
            got = driver.reference_numbers(
                config, seed, result["sample"], span=span, quant=ref.fp8, control=True)
            emit({"seed": seed, "side": "fp8",
                  "numbers": driver.summarise(got, float(rule["margin"])),
                  "by_margin": {m: driver.summarise(got, m) for m in margins},
                  "per_request": [float(g.max()) for g, _ in got]})


if __name__ == "__main__":
    main()
