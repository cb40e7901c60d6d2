"""One traced run of a cell with its ``.xplane.pb`` KEPT, to show once, on the
chip, that the program's spans and the device's events lie on one clock:

    python -m benchmark.tools.span_check --workload gpt2-large.chat-busy --seed N

``run.py`` deletes the trace before its readers run, so this tool drives the
cell's driver itself and then reads the profiler's file. It prints, as JSON
lines on standard output (and to ``chiprun_out/span_check.<cell>.json``):

* ``nesting``: how many ``serve/*`` / ``train/*`` / ``data/*`` spans the
  file holds, and how many lie inside a ``bench/step`` or ``bench/module``
  span of the same host line (thread);
* ``harvest_vs_device``: for every ``serve/harvest_wait`` in the file, the
  distance from its end to the nearest end of a ``jit_decode_wave`` event
  on the device's ``XLA Modules`` line (the fetch returns when the wave it
  waited for is done);
* ``tick``: the median period of the driver's ticks beside
  ``tick_host_ms.p50 + harvest_wait_ms.p50``;
* ``tick_split``: the mean ``serve/tick`` of the traced stretch by child
  (``serve/admit``, ``serve/prefill_enqueue``, ``serve/harvest_wait``,
  ``serve/replay``, ``serve/grow``, ``serve/dispatch``) and its self time, in
  ms per tick: they add up to the mean tick, and all but the wait are where
  ``tick_host_ms`` goes;
* ``end_to_end``: the traced run's own end-to-end metrics, for the cost of
  tracing ON;
* every per-layer metric of the cell through its own reader;
* ``parse_trace``: whether ``rocket_tpu.obs.prof.parse_trace`` finds device
  slices and step windows in a perfetto capture of a few steps on this chip
  (the cell's own trace is an ``.xplane.pb`` only, which it cannot read).
"""

from __future__ import annotations

import argparse
import bisect
import importlib
import json
import os
import shutil
import statistics
import sys

from benchmark import run, trace
from benchmark.drivers import common

PROGRAM_PREFIXES = ("serve/", "train/", "data/")
BENCH_PARENTS = ("bench/step", "bench/module", "bench/data_wait")


def host_lines(data):
    """``{(plane, line): [(name, start_ns, end_ns, stats)]}`` of the host
    planes' spans of the program and the benchmark."""
    out = {}
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            events = [
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns), dict(e.stats))
                for e in line.events
                if e.name.startswith(PROGRAM_PREFIXES + ("bench/",))
            ]
            if events:
                out[(plane.name, line.name)] = events
    return out


def nesting(lines) -> dict:
    total = inside = 0
    names: dict = {}
    for events in lines.values():
        parents = [(s, e) for n, s, e, _ in events if n in BENCH_PARENTS]
        for name, s, e, _ in events:
            if not name.startswith(PROGRAM_PREFIXES):
                continue
            total += 1
            names[name] = names.get(name, 0) + 1
            inside += any(ps <= s and e <= pe for ps, pe in parents)
    return {"program_spans": total, "inside_a_bench_span": inside, "by_name": names,
            "host_lines_with_spans": len(lines)}


def harvest_vs_device(lines, data) -> dict | None:
    ends = sorted(
        int(e.start_ns + e.duration_ns)
        for plane in data.planes if trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines if line.name == trace.MODULES_LINE
        for e in line.events if e.name.startswith("jit_decode_wave(")
    )
    waits = [
        (e, stats.get("seq")) for events in lines.values()
        for name, _, e, stats in events if name == "serve/harvest_wait"
    ]
    if not ends or not waits:
        return None
    deltas = []
    for end, _ in waits:
        i = bisect.bisect_left(ends, end)
        near = min(ends[max(i - 1, 0):i + 1], key=lambda d: abs(d - end))
        deltas.append((end - near) / 1e6)  # ms; > 0: the fetch returned after the wave
    absolute = sorted(abs(d) for d in deltas)
    return {
        "harvest_waits": len(waits), "decode_waves": len(ends),
        "with_seq": sum(1 for _, seq in waits if seq is not None),
        "signed_ms_median": statistics.median(deltas),
        "abs_ms_median": statistics.median(absolute), "abs_ms_max": absolute[-1],
        "within_2ms": sum(1 for d in absolute if d <= 2.0),
    }


def tick_split(ctx) -> dict | None:
    from benchmark.readers import program_spans

    spans, stretch = program_spans.load(ctx)
    if spans is None:
        return None
    ticks = {s.id: s.end - s.start
             for s in program_spans.select(ctx, spans, stretch, ["serve/tick"], "stretch")}
    if not ticks:
        return None
    split = {"self": sum(ticks.values())}
    for s in spans:
        if s.parent in ticks:
            split[s.name] = split.get(s.name, 0.0) + s.end - s.start
            split["self"] -= s.end - s.start
    return {"ticks": len(ticks),
            "mean_ms_per_tick": {k: 1e3 * v / len(ticks) for k, v in sorted(split.items())}}


def parse_trace_probe(out_dir: str) -> dict:
    """A perfetto capture of five annotated matmul steps through the
    program's own ``TraceSession``, read back by its ``parse_trace``."""
    import jax
    import jax.numpy as jnp

    from rocket_tpu.obs import prof

    step = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(step(x))
    session = prof.TraceSession(out_dir)
    session.start()
    for i in range(5):
        with jax.profiler.StepTraceAnnotation("probe", step_num=i):
            x = step(x)
            jax.block_until_ready(x)
    path = session.stop()
    if path is None:
        return {"file": None}
    summary = prof.parse_trace(prof.load_trace_events(path), step_name="probe")
    return {
        "file": os.path.basename(path), "device_slices": summary.n_slices,
        "steps": len(summary.steps), "ops": len(summary.ops),
        "device_total_us": summary.device_total_us,
        "mean_step_device_busy_us": summary.mean("device_busy_us"),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=51.0)
    parser.add_argument("--out", default="chiprun_out")
    args = parser.parse_args(argv)

    import jax

    bench, cell, config = run.load_cell(args.workload)
    run.configure_compile_cache()
    devices = run.find_devices(int(cell["chips"]))
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    trace_dir = str(run.ROOT / ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    result = driver.run(cell, config, seed=args.seed, seconds=args.seconds,
                        trace_dir=trace_dir, setup_clock=common.Clock())
    path = trace.newest_xplane(trace_dir)
    data = jax.profiler.ProfileData.from_file(path)
    ctx = {
        "trace": trace.cut_to_span(trace.load_xplane(path)), "host": result["host"],
        "config": config,
        "cell": cell, "chips": len(devices), "cell_seconds": args.seconds,
        "peaks": run.load_json(run.HERE / "peaks.json").get(devices[0].device_kind),
        "device_kind": devices[0].device_kind,
    }
    lines = host_lines(data)
    report = {
        "workload": args.workload, "seed": args.seed,
        "xplane_bytes": os.path.getsize(path),
        # The end-to-end metrics of THIS traced run, to set beside an
        # untraced run of the same seed.
        "end_to_end": result["end_to_end"],
        "nesting": nesting(lines),
        "harvest_vs_device": harvest_vs_device(lines, data),
        "tick_split": tick_split(ctx),
        "metrics": {
            name: value["value"]
            for name, value in run.read_per_layer(bench, args.workload, ctx).items()
        },
    }
    ticks = result["host"].get("ticks")
    if ticks:
        inside = [t for t in ticks if t[0] >= args.seconds - result["host"]["traced_s"]
                  and t[0] < args.seconds]
        periods = [b[0] - a[0] for a, b in zip(inside, inside[1:])]
        report["tick"] = {
            "median_period_ms": statistics.median(periods) * 1e3,
            "mean_period_ms": statistics.fmean(periods) * 1e3,
            "ticks": len(inside),
        }
    shutil.rmtree(trace_dir, ignore_errors=True)
    report["parse_trace"] = parse_trace_probe(os.path.join(trace_dir, "prof"))
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"span_check.{args.workload}.json"), "w") as f:
        json.dump(report, f, indent=1)
    for key, value in report.items():
        print(json.dumps({key: value}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
