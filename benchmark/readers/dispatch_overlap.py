"""The share of the decode dispatches that were enqueued while an earlier
one was still unharvested, in %, from the PROGRAM's ``serve/dispatch`` spans
(``rocket_tpu.obs.spans``: one span per dispatch; its ``inflight`` id counts
the dispatches not yet fetched when this one was enqueued), over the traced
stretch. A dispatch with ``inflight`` >= 1 sat in the device's queue behind a
running wave: the host's fetch, replay, growing and jit call of that tick
were hidden under it. 0 is the first dispatch after an idle stretch and a
tick that drained before it evicted.

A program whose spans carry no ``inflight`` id (one dispatch in flight at a
time, harvested before the next) has nothing to read: ``None``.
"""

from benchmark.readers import program_spans


def read(ctx):
    spans, stretch = program_spans.load(ctx)
    if spans is None:
        return None
    made = program_spans.select(ctx, spans, stretch, ["serve/dispatch"], "stretch")
    behind = [s.ids["inflight"] for s in made if "inflight" in s.ids]
    if not behind:
        return None
    return 100.0 * sum(1 for n in behind if int(n) >= 1) / len(behind)
