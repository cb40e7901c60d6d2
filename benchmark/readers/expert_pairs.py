"""What the router sent to the experts held here, from the PROGRAM's
``moe/expert_pairs`` counter (``rocket_tpu.obs.spans``: one record per
program call — a decode wave or a prefill chunk — with ``kind``, ``tokens``
processed and ``pairs[layer][expert]``), over the traced stretch.

``stat``:

* ``per_token``: held pairs per token processed and routed layer (with 16
  of 256 experts held and 8 chosen a token, 0.5 if routing is even);
* ``max_over_mean``: per call and layer, the fullest held expert over the
  mean — the ``q``-th percentile over calls and layers that got a pair.

A program that records no such counter has nothing to read: ``None``.
"""

import json

from benchmark.drivers.common import percentile
from benchmark.readers import program_spans


def calls(ctx):
    """``[(kind, tokens, pairs[layer][expert]), ...]`` of the stretch, or
    None."""
    spans, stretch = program_spans.load(ctx)
    if spans is None:
        return None
    records = program_spans.select(ctx, spans, stretch, ["moe/expert_pairs"], "stretch")
    return [
        (s.ids["kind"], int(s.ids["tokens"]), json.loads(s.ids["pairs"]))
        for s in records
    ] or None


def read(ctx, *, stat: str, q: float = 95):
    records = calls(ctx)
    if not records:
        return None
    if stat == "per_token":
        routed = sum(tokens * len(pairs) for _, tokens, pairs in records)
        held = sum(sum(map(sum, pairs)) for _, _, pairs in records)
        return held / routed if routed else None
    if stat == "max_over_mean":
        ratios = [
            max(layer) * len(layer) / sum(layer)
            for _, _, pairs in records for layer in pairs if sum(layer) > 0
        ]
        return percentile(ratios, q) if ratios else None
    raise ValueError(f"expert_pairs: unknown stat={stat!r}")
