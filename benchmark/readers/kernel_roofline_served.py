"""A kernel's share of its roofline, in %, like ``kernel_roofline`` — the
least time the chip could take for the work the traced window REQUIRED
(the larger of operations over peak FLOP/s and bytes over peak bytes/s)
over the summed device time of the kernel's events — for kernels whose
work is counted by a function of ``benchmark/<module>.py`` from what the
window served:

``per``  ``decode_tokens``: ``count(config, live contexts of the traced
         decode tokens)`` for one layer, times ``num_hidden_layers``;
         ``expert_calls``: ``count(config, [(kind, pairs), ...])`` over the
         program's ``moe/expert_pairs`` records of the stretch.

No events matching ``pattern``, or nothing counted = nothing to read."""

import importlib

from benchmark import trace
from benchmark.readers import expert_pairs


def read(ctx, *, pattern: str, module: str, count: str, per: str):
    durations = trace.op_durations_s(ctx["trace"], pattern)
    if not durations:
        return None
    fn = getattr(importlib.import_module(f"benchmark.{module}"), count)
    config = ctx["config"]
    if per == "decode_tokens":
        if not ctx["host"].get("traced_decode"):
            return None
        work = fn(config, ctx["host"]["traced_decode"])
        calls = config["num_hidden_layers"]
    elif per == "expert_calls":
        records = expert_pairs.calls(ctx)
        if not records:
            return None
        work = fn(config, [(kind, pairs) for kind, _, pairs in records])
        calls = 1
    else:
        raise ValueError(f"kernel_roofline_served: unknown per={per!r}")
    if ctx["peaks"] is None:
        raise KeyError(f"no peak for device kind {ctx['device_kind']!r} in peaks.json")
    least = calls * max(
        work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / sum(durations) if least > 0 else None
