"""A chunk kernel's share of its roofline, in %, counted from what each
prefill chunk of the traced stretch served — its rows' POSITIONS, not only
their number (``served_roofline``'s ``prefill_chunks`` has the ``valid``
rows alone): the least time the chip could take (the larger of operations
over peak FLOP/s and bytes over peak bytes/s; ``benchmark/<module>.py``
counts ONE layer's work, ``count(config, [(start, valid), ...])``, and
``layers(config)`` the layers that do it) over the summed device time of
the events matching ``pattern``. The chunks are the program's
``serve/prefill_enqueue`` spans of the stretch, with their ``start`` and
``valid``.

No events matching ``pattern`` (a program without the kernel), no span, or
nothing counted = nothing to read."""

import importlib

from benchmark import trace
from benchmark.readers import program_spans


def read(ctx, *, pattern: str, module: str, count: str, layers: str):
    durations = trace.op_durations_s(ctx["trace"], pattern)
    if not durations:
        return None
    spans, stretch = program_spans.load(ctx)
    if spans is None:
        return None
    chunks = [(int(s.ids["start"]), int(s.ids["valid"])) for s in program_spans.select(
        ctx, spans, stretch, ["serve/prefill_enqueue"], "stretch")]
    if not chunks:
        return None
    counts = importlib.import_module(f"benchmark.{module}")
    config = ctx["config"]
    work = getattr(counts, count)(config, chunks)
    if ctx["peaks"] is None:
        raise KeyError(f"no peak for device kind {ctx['device_kind']!r} in peaks.json")
    least = getattr(counts, layers)(config) * max(
        work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / sum(durations) if least > 0 else None
