"""One run of one cell:

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Finds a TPU with the chips the cell asks for or
fails (it never sets ``JAX_PLATFORMS`` and never falls back to the CPU),
keeps JAX's compile cache at ``$JAX_COMPILATION_CACHE_DIR`` or
``<checkout>/.jax_cache``, runs the cell's driver and prints, as the last
line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` on a traced run),
``end_to_end`` (every statistic of the host's clock, in a traced run too),
then ``checks``: every number compared beside its limit.

Everything that belongs to one configuration, cell or per-layer metric is a
file found by the name ``BENCHMARK.json`` gives; see ``benchmark/README.md``.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict]:
    """``(benchmark, cell, config)`` for a workload name: the entry of
    ``BENCHMARK.json``, the cell's own file and its configuration's.
    ``root`` holds ``BENCHMARK.json``; the cell's file lies in
    ``workloads/`` beside the directory of its configuration's file."""
    bench = load_json(root / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    entry = entries[0]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    home = (root / files[entry["config"]]).parent.parent
    cell = load_json(home / "workloads" / f"{name}.json")
    for key in ("config", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(
                f"benchmark: {name}: {key} is {cell[key]!r} in the cell's file "
                f"and {entry[key]!r} in BENCHMARK.json"
            )
    config = load_json(root / files[cell["config"]])
    return bench, cell, config


def metrics_of(bench: dict, kind: str, workload: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    that list it under ``workloads``, or list nothing."""
    return [
        m for m in bench[kind]
        if "workloads" not in m or workload in m["workloads"]
    ]


def read_per_layer(bench: dict, workload: str, ctx: dict) -> dict:
    """Each per-layer metric through the reader its own file names. A
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for metric in metrics_of(bench, "per_layer", workload):
        spec = load_json(HERE / "metrics" / f"{metric['name']}.json")
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def configure_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    # Small programs (the weights, the probes) are cached too, so that the
    # second run of a cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def find_devices(chips: int):
    """The TPU devices of this machine, or exit 2 with no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: no TPU (jax found {devices[0].platform!r}); "
              "nothing is measured on another backend", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) != chips:
        print(f"benchmark: the cell asks for {chips} chip(s), jax found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return devices


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            devices, root: Path = ROOT) -> dict:
    """Everything after the look for a chip: run the driver, read the
    metrics, decide ``correct``. Returns the result line as a dict."""
    from benchmark import trace as trace_lib
    from benchmark.drivers import common

    bench, cell, config = load_cell(workload, root)
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    trace_dir = str(root / ".bench_trace") if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    result = driver.run(
        cell, config, seed=seed, seconds=seconds, trace_dir=trace_dir,
        setup_clock=common.Clock(_PROCESS_START),
    )

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": result["memory_peak_bytes"],
    }
    line = {"correct": False, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}
    if trace:
        clock = common.Clock(_PROCESS_START)
        events = trace_lib.cut_to_span(
            trace_lib.load_xplane(trace_lib.newest_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        common.note(clock, "trace read")
        device["busy_s"] = trace_lib.busy_s(events)
        device["window_s"] = result["host"]["traced_s"]
        ctx = {
            "trace": events, "host": result["host"], "config": config,
            "cell": cell, "chips": len(devices), "cell_seconds": seconds,
            "peaks": load_json(HERE / "peaks.json").get(devices[0].device_kind),
            "device_kind": devices[0].device_kind,
        }
        line["metrics"] = read_per_layer(bench, workload, ctx)
        for text in trace_lib.summary_lines(events):
            print(text, file=sys.stderr)
        line["breakdown"] = {
            "device_ops": trace_lib.top_ops(events),
            "idle_gaps": trace_lib.idle_gaps(events),
        }
        common.note(clock, "per-layer metrics and breakdown read")
    else:
        for metric in metrics_of(bench, "end_to_end", workload):
            value = result["end_to_end"].get(metric["name"])
            if value is not None:
                line["metrics"][metric["name"]] = {
                    "value": float(value), "unit": metric["unit"],
                }
    # Every statistic the driver took on the host's clock, traced run or
    # not, beside the metrics the contract asks for (the driver of the
    # checks ignores this key; a person comparing statistics reads it).
    line["end_to_end"] = {
        k: float(v) for k, v in result["end_to_end"].items() if v is not None
    }
    ok, checks = common.judge(result.get("numbers", {}), cell["limits"])
    line["correct"] = bool(ok and result["failed"] == 0 and result["attempted"] > 0)
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    # In a directory that holds only BENCHMARK.json and the benchmark's own
    # files this fails, before anything is printed.
    import rocket_tpu  # noqa: F401

    _, cell, _ = load_cell(args.workload)
    configure_compile_cache()
    devices = find_devices(int(cell["chips"]))
    line = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                   devices=devices)
    for name, check in line["checks"].items():
        print(f"check {name}: value {check['value']} limit {check['limit']}",
              file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
