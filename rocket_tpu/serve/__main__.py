"""``python -m rocket_tpu.serve`` — serve a checkpoint from the CLI.

Two subcommands:

* (default / ``run``) — build a model, load a checkpoint when given
  (the ``Checkpointer`` resume machinery + the resharding
  ``checkpoint_io`` reader, same as ``examples/generate.py``), then serve
  a synthetic workload (or prompts from stdin with ``--stdin``) through
  :class:`~rocket_tpu.serve.ServeEngine`: streamed output for the first
  few requests, the latency/throughput report, and a ``telemetry.json``
  with the serve gauges + per-request spans under ``--out-dir``.
* ``report <telemetry.json | run-dir>`` — render the serve section of a
  previously written telemetry file.

Examples::

    python -m rocket_tpu.serve --requests 20 --max-new-tokens 24
    python -m rocket_tpu.serve --config charlm --checkpoint checkpoints/char_lm --stdin
    python -m rocket_tpu.serve report runs/serve
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np


def _build_model(args):
    """(model, params, tokenizer) for the requested config."""
    import jax

    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM

    tokenizer = None
    if args.config == "tiny":
        config = TransformerConfig(
            vocab_size=128, max_seq_len=128, dim=64, num_layers=2,
            num_heads=4, dropout=0.0,
        )
    elif args.config == "charlm":
        from rocket_tpu.data.text import CharTokenizer, tiny_shakespeare

        tokenizer = CharTokenizer(tiny_shakespeare())
        config = TransformerConfig.char_lm(
            vocab_size=tokenizer.vocab_size, max_seq_len=256
        )
    else:
        raise SystemExit(f"unknown --config {args.config!r}")
    model = TransformerLM(config)
    params = None
    if args.checkpoint:
        params = _load_checkpoint_params(model, args.checkpoint)
    if params is None:
        if args.checkpoint:
            print(
                f"serve: no complete checkpoint under {args.checkpoint!r} — "
                "using random-init params", file=sys.stderr,
            )
        params = jax.jit(model.init)(jax.random.key(args.seed))["params"]
    return model, params, tokenizer


def _load_checkpoint_params(model, ckpt_dir: str):
    """Newest complete checkpoint's params via the Checkpointer's resume
    resolution + the resharding reader (works on checkpoints written by
    any process count / sharding)."""
    import jax

    from rocket_tpu.core.checkpoint import Checkpointer
    from rocket_tpu.runtime import checkpoint_io

    latest = Checkpointer(
        output_dir=ckpt_dir, resume_from="latest"
    )._resolve_resume_path("latest")
    if latest is None:
        return None
    template = {"params": jax.jit(model.init)(jax.random.key(0))["params"]}
    restored = checkpoint_io.load_pytree(
        os.path.join(latest, "model_0"), template
    )
    print(f"serve: loaded params from {latest}", file=sys.stderr)
    return restored["params"]


def _workload(args, model, tokenizer):
    """Yield (prompt, max_new_tokens) pairs: stdin lines or synthetic
    random prompts with mixed lengths."""
    if args.stdin:
        if tokenizer is None:
            raise SystemExit("--stdin needs a tokenized config (--config charlm)")
        for line in sys.stdin:
            line = line.rstrip("\n")
            if line:
                yield line, args.max_new_tokens
        return
    rng = np.random.default_rng(args.seed)
    vocab = model.config.vocab_size
    for _ in range(args.requests):
        plen = int(rng.integers(1, args.prompt_len + 1))
        yield (
            rng.integers(0, vocab, size=plen).astype(np.int32),
            int(rng.integers(1, args.max_new_tokens + 1)),
        )


def _run(args) -> int:
    from rocket_tpu.obs.telemetry import Telemetry
    from rocket_tpu.serve.api import ServeConfig, ServeEngine

    from rocket_tpu.obs.export import ExportConfig

    model, params, tokenizer = _build_model(args)
    telemetry = Telemetry(enabled=True, out_dir=args.out_dir)
    telemetry.start()
    # Live plane: --metrics-port mounts /metrics, --export streams JSONL
    # shards, --slo arms continuous burn-rate evaluation (default:serve
    # ships ITL/TTFT p99 objectives derived from the static roofline).
    telemetry.start_export(
        ExportConfig.from_env(
            enabled=args.export or None,
            interval_s=args.export_interval,
            metrics_port=args.metrics_port,
            slo_path=args.slo,
        ),
        default_dir=args.out_dir,
    )
    exporter = telemetry.exporter
    if exporter is not None and exporter.server is not None:
        print(
            f"serve: /metrics on http://{exporter.server.host}:"
            f"{exporter.server.port}", file=sys.stderr,
        )
    engine = ServeEngine(
        model, params,
        ServeConfig(
            max_slots=args.max_slots,
            block_len=args.block_len,
            num_blocks=args.num_blocks,
            max_model_len=args.max_model_len,
            prefill_chunk=args.prefill_chunk,
            decode_waves_per_dispatch=args.waves_per_dispatch,
            reqtrace=not args.no_reqtrace,
        ),
        tokenizer=tokenizer,
        telemetry=telemetry,
    )
    if args.trace_steps:
        # Windowed device-trace capture over engine ticks — the same
        # capture path training uses; render the file with
        # `python -m rocket_tpu.obs prof`.
        engine.capture_trace(
            args.trace_steps,
            args.trace_dir or os.path.join(args.out_dir, "traces"),
        )
    rids = [
        engine.submit(
            prompt,
            max_new_tokens=mnt,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            eos_token_id=args.eos_token_id,
        )
        for prompt, mnt in _workload(args, model, tokenizer)
    ]
    if not rids:
        raise SystemExit("serve: empty workload")

    # Stream the first --show requests live (the engine keeps every other
    # request moving underneath); then drain the rest.
    for rid in rids[: args.show]:
        print(f"--- request {rid} ---")
        for piece in engine.stream(rid):
            piece = piece if isinstance(piece, str) else f" {piece}"
            print(piece, end="", flush=True)
        print()
    engine.drain()
    trace_file = engine.finish_trace()
    if args.trace_steps:
        if trace_file:
            print(
                f"serve: device trace written to {trace_file} — render "
                "with `python -m rocket_tpu.obs prof`", file=sys.stderr,
            )
        else:
            print(
                "serve: --trace-steps window captured no trace (window "
                "past the last tick?)", file=sys.stderr,
            )

    if engine.tracer is not None:
        # Persist the final request-timeline window even when no live
        # exporter is attached to drain it — the run dir always renders
        # with `python -m rocket_tpu.obs timeline`.
        engine.tracer.flush(telemetry.resolve_out_dir(args.out_dir))
        print(
            "serve: request timelines under "
            f"{os.path.join(args.out_dir, 'telemetry')} — render with "
            "`python -m rocket_tpu.obs timeline "
            f"{args.out_dir} --slowest 3`", file=sys.stderr,
        )

    report = engine.report()
    print(json.dumps({"serve_report": report}, indent=1, sort_keys=True))
    out_dir = telemetry.flush()
    print(f"serve: telemetry written to {out_dir}", file=sys.stderr)
    telemetry.close(write=False)
    compiled = report["compiled"]
    if compiled["decode_traces"] != 1 or compiled["prefill_traces"] != 1:
        print(
            f"serve: RETRACE detected: {compiled} — the fixed-shape "
            "contract is broken", file=sys.stderr,
        )
        return 1
    if report["requests"]["completed"] != len(rids):
        print("serve: not all requests completed", file=sys.stderr)
        return 1
    return 0


def _report(args) -> int:
    path = args.path
    if os.path.isdir(path):
        path = os.path.join(path, "telemetry.json")
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    gauges = doc.get("metrics", {}).get("gauges", {})
    histograms = doc.get("metrics", {}).get("histograms", {})
    serve_gauges = {k: v for k, v in gauges.items() if k.startswith("serve/")}
    if not serve_gauges:
        print(f"{path}: no serve/* gauges — not a serve run?")
        return 1
    print(f"serve report — {path}")
    for name in sorted(serve_gauges):
        print(f"  {name:32s} {serve_gauges[name]:g}")
    for name in sorted(h for h in histograms if h.startswith("serve/")):
        h = histograms[name]
        mean = h.get("mean")
        print(
            f"  {name:32s} count={h.get('count')} "
            f"mean={mean if mean is None else round(mean, 6)} "
            f"max={h.get('max')}"
        )
    return 0


def _trace_window_arg(text: str) -> str:
    """Validate --trace-steps at PARSE time (exit 2, before the model
    builds) — a malformed window must not traceback after paying the
    checkpoint-load cost."""
    from rocket_tpu.obs.prof import parse_step_window

    try:
        parse_step_window(text)
    except ValueError as exc:
        raise argparse.ArgumentTypeError(str(exc))
    return text


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python -m rocket_tpu.serve")
    sub = parser.add_subparsers(dest="cmd")

    run = sub.add_parser("run", help="serve a workload (default)")
    for p in (parser, run):
        p.add_argument("--config", default="tiny", choices=["tiny", "charlm"])
        p.add_argument("--checkpoint", default=None,
                       help="checkpoint dir (Checkpointer layout); newest "
                       "complete step is loaded")
        p.add_argument("--requests", type=int, default=16)
        p.add_argument("--prompt-len", type=int, default=12,
                       help="max synthetic prompt length")
        p.add_argument("--max-new-tokens", type=int, default=16)
        p.add_argument("--temperature", type=float, default=0.0)
        p.add_argument("--top-k", type=int, default=None)
        p.add_argument("--top-p", type=float, default=None)
        p.add_argument("--eos-token-id", type=int, default=None)
        p.add_argument("--max-slots", type=int, default=4)
        p.add_argument("--block-len", type=int, default=16)
        p.add_argument("--num-blocks", type=int, default=None)
        p.add_argument("--max-model-len", type=int, default=None)
        p.add_argument("--prefill-chunk", type=int, default=16)
        p.add_argument("--waves-per-dispatch", type=int, default=1,
                       help="decode waves per device dispatch (k): one "
                       "compiled scan of k waves amortizes the host "
                       "dispatch over k tokens per slot")
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--show", type=int, default=2,
                       help="stream the first N requests to stdout")
        p.add_argument("--stdin", action="store_true",
                       help="read prompts from stdin (one per line)")
        p.add_argument("--trace-steps", default=None, metavar="A:B",
                       type=_trace_window_arg,
                       help="capture a windowed device trace over engine "
                       "ticks [A, B) through the obs.prof capture path "
                       "(render with `python -m rocket_tpu.obs prof`)")
        p.add_argument("--trace-dir", default=None,
                       help="trace output dir (default <out-dir>/traces)")
        p.add_argument("--out-dir", default=os.path.join("runs", "serve"))
        p.add_argument("--metrics-port", type=int, default=None,
                       help="mount a Prometheus /metrics endpoint on this "
                       "port (0 = ephemeral; env ROCKET_TPU_METRICS_PORT)")
        p.add_argument("--export", action="store_true",
                       help="stream registry snapshots as JSONL shards to "
                       "<out-dir>/telemetry/rank<k>.jsonl "
                       "(env ROCKET_TPU_EXPORT)")
        p.add_argument("--export-interval", type=float, default=None,
                       metavar="SECS", help="exporter tick cadence "
                       "(default 10)")
        p.add_argument("--slo", default=None, metavar="SPEC",
                       help="SLO spec file, or default:serve for the "
                       "committed ITL/TTFT objectives (env ROCKET_TPU_SLO)")
        p.add_argument("--no-reqtrace", action="store_true",
                       help="disable per-request timeline tracing "
                       "(rocket_tpu.obs.reqtrace; on by default — "
                       "host-side only, no effect on the compiled path)")

    rep = sub.add_parser("report", help="render a serve telemetry.json")
    rep.add_argument("path", help="telemetry.json or the run dir holding it")

    args = parser.parse_args(argv)
    if args.cmd == "report":
        return _report(args)
    return _run(args)


if __name__ == "__main__":
    sys.exit(main())
