"""The readings the limits of a ``serve_window_moe`` cell are set from, on
the chip at the cell's own size (``tools/control_linear_moe`` is the
DeltaNet family's):

    python -m benchmark.tools.control_window_moe --workload <cell> \
        --seeds 1,2 [--control-seeds 1] [--controls fp8,window] [--seconds 20]

For every seed the PROGRAM runs a short window and its numbers are printed
(the lower readings). For every seed in ``--control-seeds`` two CONTROLS
are read over the same sample, each the reference put in the program's
place: ``fp8`` — the operands of every matrix multiplication rounded to
fp8, the nearest precision below the bfloat16 the configuration states —
and ``window`` — the sliding layers attending the whole context, as a
build that ignored the window would. Every record carries the harness's
own judgement of its numbers against the cell's ``limits``
(``common.judge``, what decides a run's ``correct``): a control's must
read ``"correct": false``, and ``over`` names the limits it passed. One
JSON object per line."""

import argparse
import json

from benchmark import run
from benchmark.drivers import serve_window_moe as driver
from benchmark.reference import laguna_lm as ref
from benchmark.traffic import generator

CONTROLS = {
    "fp8": {"quant": ref.fp8},
    "window": {"window": False},
}


def emit(record):
    print(json.dumps(record), flush=True)


def judged(numbers: dict, limits: dict) -> dict:
    """``run.execute``'s judgement of ``numbers``: ``correct`` and the
    limits passed."""
    ok, checks = driver.common.judge(numbers, limits)
    return {"correct": ok, "over": [k for k, c in checks.items()
                                    if c["value"] is None or not c["value"] <= c["limit"]]}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--controls", default=",".join(CONTROLS))
    parser.add_argument("--seconds", type=float, default=20.0)
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    _, cell, config = run.load_cell(args.workload)
    run.configure_compile_cache()
    run.find_devices(int(cell["chips"]))
    span = int(generator.load_mix(cell["traffic_file"])["answer"]["max"])
    margin = float(cell["compare"]["margin"])
    for seed in sorted(set(seeds) | set(control_seeds)):
        result = driver.run(cell, config, seed=seed, seconds=args.seconds)
        emit({"seed": seed, "side": "program", "numbers": result["numbers"],
              **judged(result["numbers"], cell["limits"]),
              **{k: result[k] for k in (
                  "compared_tokens", "compared_requests", "finished", "failed",
                  "end_to_end", "memory_peak_bytes") if k in result}})
        if seed in control_seeds:
            for name in args.controls.split(","):
                got = driver.reference_numbers(
                    config, seed, result["sample"], span=span, control=True,
                    **CONTROLS[name])
                numbers = driver.summarise(got, margin)
                emit({"seed": seed, "side": name, "numbers": numbers,
                      **judged(numbers, cell["limits"]),
                      "per_request": [float(g.max()) for g, _ in got]})


if __name__ == "__main__":
    main()
