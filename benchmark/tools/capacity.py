"""The sweep for C, once: the whole supply of a cell queued at the start,
completed requests per second over the steady middle of the run.

    python -m benchmark.tools.capacity --workload gpt2-large.chat-busy --seed 1 [--supply-s 30]

Prints one JSON object; the cell's ``rate_per_s`` is 0.8 x its ``C``.
"""

import argparse
import json
import time

import numpy as np

from benchmark import run
from benchmark.drivers import common, serve
from benchmark.traffic import generator


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--supply-s", type=float, default=30.0)
    parser.add_argument("--supply-rate", type=float, default=6.0)
    args = parser.parse_args(argv)

    _, cell, config = run.load_cell(args.workload)
    run.configure_compile_cache()
    devices = run.find_devices(int(cell["chips"]))
    mix = generator.load_mix(cell["traffic_file"])
    arrivals = generator.requests(
        mix, args.seed, rate_per_s=args.supply_rate, seconds=args.supply_s,
        lead_in_s=0.0, vocab_size=config["vocab_size"],
    )
    engine = serve.build_engine(cell, config, args.seed)
    serve.warm_up(engine, config, cell["engine"]["prefill_chunk"])
    t0 = time.perf_counter()
    rids = [
        engine.submit(a.prompt, max_new_tokens=a.max_new_tokens, temperature=0.0)
        for a in arrivals
    ]
    done_at, ticks, tokens_at = {}, [], []
    while not engine.scheduler.idle:
        t = time.perf_counter()
        events = engine.step()
        now = time.perf_counter()
        ticks.append(now - t)
        for ev in events:
            tokens_at.append(now - t0)
            if ev.finished:
                done_at[ev.request.id] = now - t0
    times = np.sort(np.asarray([done_at[r] for r in rids]))
    n = len(times)
    lo, hi = int(0.2 * n), int(0.8 * n)
    span = times[hi] - times[lo]
    tokens_at = np.asarray(tokens_at)
    in_span = ((tokens_at >= times[lo]) & (tokens_at < times[hi])).sum()
    print(json.dumps({
        "workload": args.workload, "requests": n, "drained_in_s": float(times[-1]),
        "steady_from_s": float(times[lo]), "steady_to_s": float(times[hi]),
        "C_requests_per_s": float((hi - lo) / span),
        "tokens_per_s_in_steady": float(in_span / span),
        "tick_ms_p50": common.median(ticks) * 1e3,
        "tick_ms_p95": common.percentile(ticks, 95) * 1e3,
        "device": {"kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": common.peak_bytes(devices)},
    }))


if __name__ == "__main__":
    main()
