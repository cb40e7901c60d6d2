#!/usr/bin/env python
"""Serve smoke gate: the continuous-batching engine end to end on CPU.

Four legs (wired into scripts/check.sh and CI):

1. **In-process**: a 50-request synthetic workload on a tiny LM through
   :class:`rocket_tpu.serve.ServeEngine` must (a) complete every request,
   (b) compile the decode wave and the prefill chunk exactly ONCE — zero
   retraces across 50 admissions/evictions/refills, checked against the
   obs registry gauges, (c) produce greedy outputs token-identical to
   ``generate()`` for sampled spot-checks, and (d) leave a telemetry.json
   whose serve gauges + per-request spans tell the same story.
2. **Live export** (ISSUE 19): a serving session with the live plane
   armed must expose a mid-serve ``/metrics`` endpoint carrying the
   serve families, stream telemetry shards, detect a seeded ITL-p99 SLO
   violation online (``obs/slo/*`` counter), and gate ``python -m
   rocket_tpu.obs watch --slo`` offline (exit 1 seeded / 0 slack).
3. **Scanned waves** (ISSUE 11): the same model served with
   ``decode_waves_per_dispatch=4`` must produce greedy outputs
   BIT-IDENTICAL to the k=1 engine for an identical workload, with zero
   retraces, exactly ONE ``jax.device_get`` per dispatch of k waves
   (the dispatch amortization the k-wave ``lax.scan`` exists for), and a
   measured tokens-per-dispatch meaningfully above 1.
4. **CLI**: ``python -m rocket_tpu.serve`` as a subprocess (with a
   k-wave flag) must stream output, print the serve report, exit 0, and
   the ``report`` subcommand must render its telemetry.
5. **Timeline** (ISSUE 20): per-request tail forensics end to end — a
   starved pool preempts + resumes requests whose single timeline spans
   both residencies (eviction gap visible, phase durations summing to
   the measured wall time within 5%), the seeded ITL-p99 SLO violation
   names the window's tail exemplars in its flight anomaly, and
   ``python -m rocket_tpu.obs timeline`` renders the waterfalls from the
   persisted shards.

Exits non-zero on the first violated invariant.
"""

import json
import os
import subprocess
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def check(condition, message):
    if not condition:
        print(f"serve smoke FAILED: {message}", file=sys.stderr)
        sys.exit(1)


def engine_leg(out_dir: str) -> None:
    from rocket_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        generate,
    )
    from rocket_tpu.obs.telemetry import Telemetry
    from rocket_tpu.serve import ServeConfig, ServeEngine

    config = TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=2, num_heads=4,
        dropout=0.0,
    )
    model = TransformerLM(config)
    variables = jax.jit(model.init)(jax.random.key(0))

    telemetry = Telemetry(enabled=True, out_dir=out_dir)
    telemetry.start()
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                    max_model_len=48, num_blocks=17),  # starved -> evictions
        telemetry=telemetry,
    )
    rng = np.random.default_rng(0)
    jobs = []
    for _ in range(50):
        plen = int(rng.integers(1, 14))
        maxnew = int(rng.integers(1, 10))
        prompt = rng.integers(0, 64, size=plen).astype(np.int32)
        rid = engine.submit(prompt, max_new_tokens=maxnew, temperature=0.0)
        jobs.append((rid, prompt, maxnew))
    engine.drain()
    report = engine.report()
    check(report["requests"]["completed"] == 50,
          f"completed {report['requests']}")
    check(report["compiled"]["decode_traces"] == 1,
          f"decode retraced: {report['compiled']}")
    check(report["compiled"]["prefill_traces"] == 1,
          f"prefill retraced: {report['compiled']}")
    check(report["tokens_per_sec"] and report["tokens_per_sec"] > 0,
          f"tokens_per_sec {report['tokens_per_sec']}")
    check(report["time_to_first_token_s"]["count"] == 50, "ttft count")

    # Greedy spot-checks against generate() (every 10th request).
    for rid, prompt, maxnew in jobs[::10]:
        ref = np.asarray(
            generate(model, variables, prompt[None, :], maxnew, temperature=0)
        )[0, len(prompt):]
        got = np.asarray(engine.result(rid).tokens, np.int32)
        check((got == ref).all(), f"request {rid}: {got} != {ref}")

    telemetry.flush()
    telemetry.close(write=False)

    tel_path = os.path.join(out_dir, "telemetry.json")
    check(os.path.exists(tel_path), f"{tel_path} missing")
    with open(tel_path, encoding="utf-8") as f:
        doc = json.load(f)
    gauges = doc["metrics"]["gauges"]
    for name, want in [
        ("serve/decode_traces", 1), ("serve/prefill_traces", 1),
        ("serve/requests_completed", 50),
    ]:
        check(gauges.get(name) == want,
              f"telemetry gauge {name} = {gauges.get(name)}, want {want}")
    check(gauges.get("serve/tokens_generated", 0) > 0, "no tokens gauge")
    check(gauges.get("serve/kv_pool_bytes") == engine.engine.spec.pool_bytes,
          "kv_pool_bytes gauge")
    with open(os.path.join(out_dir, "spans.trace.json"), encoding="utf-8") as f:
        spans = json.load(f)["traceEvents"]
    n_req_spans = sum(
        1 for e in spans if str(e.get("name", "")).startswith("serve/request[")
    )
    check(n_req_spans == 50, f"{n_req_spans} request spans, want 50")
    print(f"serve smoke: engine leg OK "
          f"(preemptions={report['requests']['preemptions']}, "
          f"tok/s={report['tokens_per_sec']:.0f})")


def export_leg(out_dir: str) -> None:
    """Live plane over a serving session (ISSUE 19): /metrics scrapeable
    mid-serve with the serve families, shards streamed, and a seeded
    ITL-p99 SLO violation (objective 1 ps — any real inter-token gap
    violates) detected online and gating ``obs watch`` offline.

    The seeded spec, not default:serve, keeps the verdict deterministic:
    the committed serve objectives are TPU roofline ceilings a CPU toy
    run sits nowhere near."""
    import urllib.request

    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.obs.export import ExportConfig
    from rocket_tpu.obs.telemetry import Telemetry
    from rocket_tpu.serve import ServeConfig, ServeEngine

    violating = os.path.join(out_dir, "slo_itl_tight.json")
    passing = os.path.join(out_dir, "slo_itl_slack.json")
    os.makedirs(out_dir, exist_ok=True)
    for path, objective in ((violating, 1e-12), (passing, 3600.0)):
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"version": 1, "slos": [
                {"name": "seeded_itl_p99", "kind": "quantile",
                 "metric": "serve/itl_s", "quantile": 0.99,
                 "objective": objective},
            ]}, f)

    config = TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=2, num_heads=4,
        dropout=0.0,
    )
    model = TransformerLM(config)
    variables = jax.jit(model.init)(jax.random.key(0))
    telemetry = Telemetry(enabled=True, out_dir=out_dir)
    telemetry.start()
    telemetry.start_export(
        ExportConfig(enabled=True, interval_s=0.2, metrics_port=0,
                     slo_path=violating),
        default_dir=out_dir,
    )
    exporter = telemetry.exporter
    check(exporter is not None and exporter.server is not None,
          "export config did not mount the live plane")
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=4, block_len=8, prefill_chunk=8,
                    max_model_len=48),
        telemetry=telemetry,
    )
    rng = np.random.default_rng(3)
    for _ in range(10):
        prompt = rng.integers(0, 64, size=int(rng.integers(1, 10)))
        engine.submit(prompt.astype(np.int32), max_new_tokens=6,
                      temperature=0.0)
    engine.drain()
    # One deterministic tick (the thread also ticks at 0.2s cadence):
    # the seeded quantile SLO sees the serve/itl_s histogram and fires.
    record = exporter.tick()
    verdict, = [s for s in record["slo"] if s["name"] == "seeded_itl_p99"]
    check(verdict["violated"],
          f"seeded ITL SLO not violated online: {verdict}")
    counters = telemetry.registry.snapshot()["counters"]
    check(counters.get("obs/slo/seeded_itl_p99/violations", 0) >= 1,
          "online violation did not land the obs/slo/* edge counter")

    # Mid-serve scrape: the serve families a Prometheus server would
    # ingest, with cumulative buckets and the rank label.
    url = f"http://127.0.0.1:{exporter.server.port}/metrics"
    with urllib.request.urlopen(url, timeout=5) as resp:
        body = resp.read().decode()
    for family in ("rocket_tpu_serve_ttft_s_bucket",
                   "rocket_tpu_serve_itl_s_count",
                   "rocket_tpu_serve_slots_active",
                   "rocket_tpu_obs_slo_seeded_itl_p99_burn_rate"):
        check(family in body, f"{family} missing from the /metrics scrape")
    check('le="+Inf"' in body, "no +Inf closing bucket in the exposition")

    telemetry.close(write=False)
    shard_path = os.path.join(out_dir, "telemetry", "rank0.jsonl")
    check(os.path.exists(shard_path), f"{shard_path} not written")

    # Offline gates over the shards this session just streamed.
    watch = subprocess.run(
        [sys.executable, "-m", "rocket_tpu.obs", "watch", out_dir,
         "--slo", violating],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    check(watch.returncode == 1,
          f"obs watch on the seeded ITL violation exited {watch.returncode} "
          f"(want 1): {watch.stderr[-300:]}")
    check("VIOLATION seeded_itl_p99" in watch.stdout,
          "obs watch printed no VIOLATION line")
    watch = subprocess.run(
        [sys.executable, "-m", "rocket_tpu.obs", "watch", out_dir,
         "--slo", passing],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    check(watch.returncode == 0,
          f"obs watch on the slack spec exited {watch.returncode} (want 0)")
    print("serve smoke: export leg OK (mid-serve /metrics scrape, "
          "seeded ITL-p99 SLO fired online + gated offline)")


def scan_leg() -> None:
    """k-wave scanned dispatch: greedy parity with k=1, one device_get
    per k waves, zero retraces."""
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.serve import ServeConfig, ServeEngine

    config = TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=2, num_heads=4,
        dropout=0.0,
    )
    model = TransformerLM(config)
    variables = jax.jit(model.init)(jax.random.key(0))

    def run(k):
        engine = ServeEngine(
            model, variables["params"],
            ServeConfig(max_slots=4, block_len=8, prefill_chunk=8,
                        max_model_len=48, decode_waves_per_dispatch=k),
        )
        rng = np.random.default_rng(7)
        rids = []
        for _ in range(20):
            plen = int(rng.integers(1, 12))
            maxnew = int(rng.integers(3, 14))
            prompt = rng.integers(0, 64, size=plen).astype(np.int32)
            rids.append(engine.submit(prompt, max_new_tokens=maxnew,
                                      temperature=0.0))
        engine.drain()
        return engine, rids

    base, base_rids = run(1)
    scan, scan_rids = run(4)
    for b_rid, s_rid in zip(base_rids, scan_rids):
        b = base.result(b_rid).tokens
        s = scan.result(s_rid).tokens
        check(b == s, f"k=4 diverged from k=1 on request {s_rid}: {s} != {b}")

    report = scan.report()
    check(report["requests"]["completed"] == 20, "scan leg completion")
    check(report["compiled"]["decode_traces"] == 1,
          f"scan leg retraced: {report['compiled']}")
    eng = scan.engine
    check(eng.device_gets == eng.decode_dispatches,
          f"device_gets {eng.device_gets} != dispatches "
          f"{eng.decode_dispatches} — more than one host sync per k-wave "
          "dispatch")
    check(eng.decode_waves == 4 * eng.decode_dispatches,
          f"waves {eng.decode_waves} != 4 * dispatches "
          f"{eng.decode_dispatches}")
    tpd = report["dispatch"]["tokens_per_dispatch"]
    check(tpd and tpd > 1.5,
          f"tokens_per_dispatch {tpd} — the scan is not amortizing the "
          "dispatch")
    # Identical greedy workload => identical token count, ~4x fewer syncs.
    check(base.engine.device_gets > 2 * eng.device_gets,
          f"k=4 device_gets {eng.device_gets} not materially below k=1's "
          f"{base.engine.device_gets}")
    print(f"serve smoke: scan leg OK (tokens/dispatch={tpd}, "
          f"device_gets {base.engine.device_gets} -> {eng.device_gets})")


def cli_leg(out_dir: str) -> None:
    env = dict(os.environ)
    proc = subprocess.run(
        [sys.executable, "-m", "rocket_tpu.serve", "--requests", "12",
         "--max-new-tokens", "8", "--max-slots", "4", "--block-len", "8",
         "--prefill-chunk", "8", "--waves-per-dispatch", "2",
         "--show", "1", "--out-dir", out_dir],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    check(proc.returncode == 0,
          f"CLI exited {proc.returncode}:\n{proc.stdout}\n{proc.stderr}")
    check("--- request 0 ---" in proc.stdout, "no streamed output")
    check("serve_report" in proc.stdout, "no report on stdout")
    payload = json.loads(proc.stdout[proc.stdout.index("{"):])
    check(payload["serve_report"]["requests"]["completed"] == 12,
          "CLI report completion count")
    check(os.path.exists(os.path.join(out_dir, "telemetry.json")),
          "CLI telemetry.json missing")

    rep = subprocess.run(
        [sys.executable, "-m", "rocket_tpu.serve", "report", out_dir],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    check(rep.returncode == 0, f"report subcommand failed:\n{rep.stderr}")
    check("serve/decode_traces" in rep.stdout, "report missing trace gauge")
    print("serve smoke: CLI leg OK")


def timeline_leg(out_dir: str) -> None:
    """Per-request tail forensics (ISSUE 20): preempted+resumed
    waterfalls, the SLO-violation -> exemplar link, and the timeline
    CLI over the persisted shards."""
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.obs.export import ExportConfig, TelemetryExporter
    from rocket_tpu.obs.flight import FlightRecorder
    from rocket_tpu.obs.telemetry import Telemetry
    from rocket_tpu.serve import ServeConfig, ServeEngine

    os.makedirs(out_dir, exist_ok=True)
    violating = os.path.join(out_dir, "slo_itl_tight.json")
    with open(violating, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "slos": [
            {"name": "seeded_itl_p99", "kind": "quantile",
             "metric": "serve/itl_s", "quantile": 0.99,
             "objective": 1e-12},
        ]}, f)

    config = TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=2, num_heads=4,
        dropout=0.0,
    )
    model = TransformerLM(config)
    variables = jax.jit(model.init)(jax.random.key(0))
    telemetry = Telemetry(enabled=True, out_dir=out_dir)
    telemetry.start()
    telemetry.flight = FlightRecorder(telemetry=telemetry)
    engine = ServeEngine(
        model, variables["params"],
        # Starved pool (8 allocatable blocks, 4 slots): decode growth
        # exhausts it, so the youngest active request preempts and
        # resumes — the tail shape this leg exists to trace.
        ServeConfig(max_slots=4, block_len=4, prefill_chunk=4,
                    max_model_len=32, num_blocks=9),
        telemetry=telemetry,
    )
    # Warmup pays the two compiles, then the tracer window resets so the
    # measured waterfalls carry no compile time in their phases.
    for _ in range(2):
        engine.submit(np.asarray([1, 2], np.int32), max_new_tokens=2,
                      temperature=0.0)
    engine.drain()
    engine.tracer.flush(out_dir)

    rng = np.random.default_rng(3)
    rids = []
    for _ in range(8):
        prompt = rng.integers(0, 64, size=int(rng.integers(2, 7)))
        rids.append(engine.submit(prompt.astype(np.int32),
                                  max_new_tokens=int(rng.integers(10, 16)),
                                  temperature=0.0))
    engine.drain()
    preempted = [r for r in rids if engine.result(r).preemptions > 0]
    check(preempted, "starved pool produced no preemption to trace")

    # One synchronous exporter tick: flushes the measured window's
    # timelines + exemplars, then evaluates the seeded SLO against them.
    exporter = TelemetryExporter(
        telemetry, ExportConfig(enabled=True, slo_path=violating),
        identity={"rank": 0, "hostname": "smoke", "pid": os.getpid()},
        default_dir=out_dir,
    )
    record = exporter.tick()
    check(record["reqtrace"]["finished"] == 8,
          f"reqtrace window drained {record['reqtrace']} (want 8 finished)")
    verdict, = [s for s in record["slo"] if s["name"] == "seeded_itl_p99"]
    check(verdict["violated"], f"seeded ITL SLO not violated: {verdict}")
    exemplars = verdict.get("exemplars") or {}
    named = set(exemplars.get("itl_gap", [])) | set(exemplars.get("ttft", []))
    check(named, f"violation carries no exemplars: {verdict}")
    check(set(preempted) & named,
          f"preempted request(s) {preempted} not among the violation's "
          f"tail exemplars {exemplars}")
    anomaly = [a for a in telemetry.flight.anomalies()
               if a.get("kind") == "slo_violation"][-1]
    check(anomaly.get("exemplars") == exemplars,
          f"flight anomaly exemplars diverge: {anomaly}")
    telemetry.close(write=False)
    for name in ("reqtrace.jsonl", "exemplars.jsonl"):
        path = os.path.join(out_dir, "telemetry", name)
        check(os.path.exists(path), f"{path} not persisted")

    # The timeline CLI over the persisted shards: the preempted request's
    # waterfall shows the eviction gap, one timeline spanning BOTH
    # residencies, phases summing to the measured wall time within 5%.
    victim = preempted[0]
    cli = subprocess.run(
        [sys.executable, "-m", "rocket_tpu.obs", "timeline", out_dir,
         "--request", str(victim), "--format", "json"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    check(cli.returncode == 0,
          f"obs timeline --request exited {cli.returncode}: {cli.stderr}")
    rec, = json.loads(cli.stdout)["requests"]
    kinds = [e["ev"] for e in rec["events"]]
    check("evict" in kinds, f"no evict event on the waterfall: {kinds}")
    check(any(e.get("resumed") for e in rec["events"]
              if e["ev"] == "admit"),
          "no resumed re-admission on the preempted timeline")
    check(rec["phases"]["preempted_s"] > 0, f"no eviction gap: {rec['phases']}")
    phase_sum = sum(rec["phases"].values())
    check(abs(phase_sum - rec["total_s"]) <= 0.05 * rec["total_s"],
          f"phases {phase_sum} vs wall {rec['total_s']} beyond 5%")

    slowest = subprocess.run(
        [sys.executable, "-m", "rocket_tpu.obs", "timeline", out_dir,
         "--slowest", "3"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    check(slowest.returncode == 0,
          f"obs timeline --slowest exited {slowest.returncode}: "
          f"{slowest.stderr}")
    check("aggregate" in slowest.stdout, "no aggregate phase breakdown")
    print(f"serve smoke: timeline leg OK (preempted {preempted} traced, "
          f"exemplars {exemplars})")


def main() -> None:
    repo_runs = os.path.join(REPO, "runs")
    os.makedirs(repo_runs, exist_ok=True)
    import tempfile

    workdir = tempfile.mkdtemp(prefix="serve_smoke_", dir=repo_runs)
    engine_leg(os.path.join(workdir, "engine"))
    export_leg(os.path.join(workdir, "export"))
    scan_leg()
    cli_leg(os.path.join(workdir, "cli"))
    timeline_leg(os.path.join(workdir, "timeline"))
    print("serve smoke: all checks passed")


if __name__ == "__main__":
    main()
