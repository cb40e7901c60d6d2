"""A kernel's share of its roofline, in %, for work counted from what the
traced stretch SERVED: the least time the chip could take for the work the
stretch required (the larger of operations over peak FLOP/s and bytes over
peak bytes/s) over the summed device time of the events matching
``pattern``. ``benchmark/<module>.py`` counts ONE layer's work (``count``)
and says how many of the configuration's layers do it (``layers``), so a
model whose layers are of more than one kind is counted by its own module
and read here (``kernel_roofline_served`` multiplies by every layer,
``ssm_roofline`` is bound to ``counts_ssm``).

``per``  ``decode_contexts``: ``count(config, live contexts of the
         stretch's decoded tokens)`` (``host["traced_decode"]``, one entry
         a token);
         ``decode_tokens``: ``count(config, how many tokens that is)``;
         ``prefill_chunks``: ``count(config, [valid rows of each chunk])``
         from the program's ``serve/prefill_enqueue`` spans of the stretch.

No events matching ``pattern`` (a program without the named function, as
every program before it was added), or nothing counted = nothing to
read."""

import importlib

from benchmark import trace
from benchmark.readers import ssm_roofline


def read(ctx, *, pattern: str, module: str, count: str, layers: str, per: str):
    durations = trace.op_durations_s(ctx["trace"], pattern)
    if not durations:
        return None
    counts = importlib.import_module(f"benchmark.{module}")
    if per in ("decode_contexts", "decode_tokens"):
        served = list(ctx["host"].get("traced_decode") or [])
        if per == "decode_tokens":
            served = len(served)
    elif per == "prefill_chunks":
        served = ssm_roofline.chunk_rows(ctx)
    else:
        raise ValueError(f"served_roofline: unknown per={per!r}")
    if not served:
        return None
    config = ctx["config"]
    work = getattr(counts, count)(config, served)
    if ctx["peaks"] is None:
        raise KeyError(f"no peak for device kind {ctx['device_kind']!r} in peaks.json")
    least = getattr(counts, layers)(config) * max(
        work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / sum(durations) if least > 0 else None
