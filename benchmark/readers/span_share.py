"""The share of the traced stretch that the PROGRAM's garbage-collection
spans cover, in %: the union of the spans of ``name`` that start inside the
stretch (``program_spans``), cut to it, over its seconds. The metrics give
``*/gc``: a collection holds every thread, whichever span it struck. The program
records a collection that starts while spans are on as ``<path>/gc``, named
for the span it interrupted (``serve/gc`` inside a serve tick), so the
interpreter was held for the whole of each.

0 is a reading: no collection in the stretch. A program that installs no
collection listener in this process (``rocket_tpu.obs.spans._on_gc`` among
``gc.callbacks``; before the listener came there is none) records no such
span whatever happens, and has nothing to read: ``None``.
"""

import gc

from benchmark import trace
from benchmark.readers import program_spans


def listening() -> bool:
    try:
        from rocket_tpu.obs import spans
    except ImportError:
        return False
    hook = getattr(spans, "_on_gc", None)
    return hook is not None and hook in gc.callbacks


def read(ctx, *, name):
    spans, stretch = program_spans.load(ctx)
    if spans is None or not listening():
        return None
    lo, hi = stretch
    held = program_spans.select(ctx, spans, stretch, [name], "stretch")
    covered = trace.total(trace.clip(trace.union((s.start, s.end) for s in held), lo, hi))
    return 100.0 * covered / (hi - lo)
