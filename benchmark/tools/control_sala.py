"""The readings the limits of a ``serve_sala`` cell are set from, on the
chip at the cell's own size (``tools/control_linear_moe`` is the DeltaNet
family's):

    python -m benchmark.tools.control_sala --workload <cell> --seeds 1,2 \
        [--control-seeds 1] [--controls fp8,dense,decay_one] [--seconds 20] \
        [--dump DIR]

For every seed the PROGRAM runs a short window and its numbers are printed
(the lower readings). For every seed in ``--control-seeds`` three CONTROLS
are read over the same sample, each the reference put in the program's
place: ``fp8`` — the operands of every matrix multiplication rounded to
fp8, the nearest precision below the bfloat16 the configuration states;
``dense`` — the sparse layers attending every position past
``dense_len``, as a build that ignored the selection would; ``decay_one``
— every lightning head with ``lambda = 1``, as a build that ignored the
decay would. Every record carries the harness's own judgement of its
numbers against the cell's ``limits`` (``common.judge``, what decides a
run's ``correct``): a control's must read ``"correct": false``, and
``over`` names the limits it passed. ``--dump`` writes each side's
per-position gaps and selection margins to ``DIR/<seed>.<side>.npz``
(what a limit or the margin is chosen from). One JSON object per line."""

import argparse
import json
from pathlib import Path

import numpy as np

from benchmark import run
from benchmark.drivers import serve_sala as driver
from benchmark.reference import minicpm_sala_lm as ref
from benchmark.traffic import generator

CONTROLS = {
    "fp8": {"quant": ref.fp8},
    "dense": {"dense": True},
    "decay_one": {"decay_one": True},
}


def emit(record):
    print(json.dumps(record), flush=True)


def judged(numbers: dict, limits: dict) -> dict:
    """``run.execute``'s judgement of ``numbers``: ``correct`` and the
    limits passed."""
    ok, checks = driver.common.judge(numbers, limits)
    return {"correct": ok, "over": [k for k, c in checks.items()
                                    if c["value"] is None or not c["value"] <= c["limit"]]}


def dump(where, seed, side, per_request):
    if where:
        Path(where).mkdir(parents=True, exist_ok=True)
        np.savez(Path(where) / f"{seed}.{side}.npz",
                 gap=np.concatenate([g for g, _ in per_request] or [np.zeros(0)]),
                 margin=np.concatenate([m for _, m in per_request] or [np.zeros(0)]))


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--controls", default=",".join(CONTROLS))
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--dump", default="")
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
        dump(args.dump, seed, "program", result.get("reference", []))
        emit({"seed": seed, "side": "program", "numbers": result["numbers"],
              **judged(result["numbers"], cell["limits"]),
              "lengths": [[len(p), len(s)] for p, s in result["sample"]],
              **{k: result[k] for k in (
                  "compared_tokens", "compared_requests", "finished", "failed",
                  "end_to_end", "memory_peak_bytes") if k in result}})
        if seed in control_seeds:
            for name in args.controls.split(","):
                got, state_gap = driver.reference_numbers(
                    config, seed, result["sample"], span=span,
                    probe=result["state_probe"], control=True, **CONTROLS[name])
                dump(args.dump, seed, name, got)
                numbers = driver.summarise(got, margin, state_gap)
                emit({"seed": seed, "side": name, "numbers": numbers,
                      **judged(numbers, cell["limits"]),
                      "per_request": [float(g.max()) for g, _ in got]})


if __name__ == "__main__":
    main()
