"""The readings the limits of `correct` are set from, on the chip at the
cell's own size, several seeds in one process:

    python -m benchmark.tools.control --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 20]

For every seed in ``--seeds`` the PROGRAM runs a short window and its numbers
are printed (the lower readings). For every seed in ``--control-seeds`` the
CONTROL is read: the reference put in the program's place with its matmul
operands rounded to fp8 (the nearest precision below the configurations'
bfloat16), and for a training cell the half-batch fault planted in the
reference. One JSON object per line; the benchmark's own runs never run this.
"""

import argparse
import json


from benchmark import run
from benchmark.drivers import serve, train
from benchmark.reference import gpt2 as ref
from benchmark.traffic import generator


def emit(record):
    print(json.dumps(record), flush=True)


def train_control(cell, config, seed):
    mix = generator.load_mix(cell["traffic_file"])
    steps = train.WARM_STEPS - 1
    rows = generator.corpus(mix, seed, config["vocab_size"], steps).reshape(
        steps, mix["batch"], mix["seq_len"]
    )
    kw = dict(block_rows=int(cell.get("reference_block_rows", 2)))
    reference = train.reference_steps(config, cell["optimizer"], seed, rows, **kw)
    for name, extra in (("fp8", {"quant": ref.fp8}),
                        ("half_batch", {"rows": mix["batch"] // 2})):
        planted = train.reference_steps(config, cell["optimizer"], seed, rows, **kw, **extra)
        numbers, worst = train.compare(planted, reference)
        emit({"seed": seed, "side": name, "numbers": numbers, "worst_leaf": worst})


def serve_both(cell, config, seed, seconds, control):
    result = serve.run(cell, config, seed=seed, seconds=seconds)
    record = {"seed": seed, "side": "program", "numbers": result["numbers"],
              "compared_tokens": result["compared_tokens"],
              "finished": result["finished"], "failed": result["failed"],
              "end_to_end": result["end_to_end"]}
    emit(record)
    if control:
        mix = generator.load_mix(cell["traffic_file"])
        gaps = serve.reference_gaps(
            config, seed, result["sample"], span=int(mix["answer"]["max"]),
            quant=ref.fp8, control=True,
        )
        emit({"seed": seed, "side": "fp8", "numbers": {"token_gap_max": max(gaps)},
              "per_request": gaps})


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=20.0)
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    _, cell, config = run.load_cell(args.workload)
    run.configure_compile_cache()
    run.find_devices(int(cell["chips"]))
    if cell["driver"] == "train":
        for seed in control_seeds:
            train_control(cell, config, seed)
        for seed in seeds:
            result = train.run(cell, config, seed=seed, seconds=args.seconds)
            emit({"seed": seed, "side": "program", "numbers": result["numbers"],
                  "worst_leaf": result["worst_leaf"],
                  "end_to_end": result["end_to_end"]})
    else:
        for seed in sorted(set(seeds) | set(control_seeds)):
            serve_both(cell, config, seed, args.seconds, seed in control_seeds)


if __name__ == "__main__":
    main()
