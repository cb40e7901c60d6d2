"""The state-space recurrence's share of its roofline, in %, like
``kernel_roofline_served``: the least time the chip could take for the work
the traced stretch REQUIRED (the larger of operations over peak FLOP/s and
bytes over peak bytes/s, ``benchmark/counts_ssm.py``, ONE state layer's
work times the configuration's state layers) over the summed device time
of the events matching ``pattern``.

``per``  ``decode_tokens``: ``count(config, decoded tokens of the stretch)``
         (``host["traced_decode"]`` holds one entry a token);
         ``prefill_chunks``: ``count(config, [valid rows of each chunk])``
         from the program's ``serve/prefill_enqueue`` spans of the stretch.

No events matching ``pattern`` (a program without the named function, or a
lowering that leaves no event of that name), or nothing counted = nothing
to read."""

from benchmark import counts_ssm, trace
from benchmark.readers import program_spans


def chunk_rows(ctx):
    """The ``valid`` of every prefill chunk enqueued in the stretch, or
    None."""
    spans, stretch = program_spans.load(ctx)
    if spans is None:
        return None
    chunks = program_spans.select(
        ctx, spans, stretch, ["serve/prefill_enqueue"], "stretch")
    return [int(s.ids["valid"]) for s in chunks] or None


def read(ctx, *, pattern: str, count: str, per: str):
    durations = trace.op_durations_s(ctx["trace"], pattern)
    if not durations:
        return None
    config = ctx["config"]
    fn = getattr(counts_ssm, count)
    if per == "decode_tokens":
        tokens = len(ctx["host"].get("traced_decode") or [])
        if not tokens:
            return None
        work = fn(config, tokens)
    elif per == "prefill_chunks":
        rows = chunk_rows(ctx)
        if not rows:
            return None
        work = fn(config, rows)
    else:
        raise ValueError(f"ssm_roofline: unknown per={per!r}")
    if ctx["peaks"] is None:
        raise KeyError(f"no peak for device kind {ctx['device_kind']!r} in peaks.json")
    least = counts_ssm.state_layers(config) * max(
        work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / sum(durations) if least > 0 else None
