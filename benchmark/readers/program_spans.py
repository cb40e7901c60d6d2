"""A statistic over the spans the PROGRAM recorded in this process
(``rocket_tpu.obs.spans``): serve tick phases, the legs of a request's first
token, train wave phases, compile events. They are durations on the host's
clock between host instants of enqueue and fetch, never device completions.

The spans are read from ``rocket_tpu.obs.spans.recorded()`` (``ctx["spans"]``
in the tests): the profiler's file is gone by the time the readers run. A
program without that recorder, as before this reader came, has nothing to
read: ``None``.

``name``     a span name or a list of them; ``*`` matches as in a file name.
``q``        a percentile (0..100), or ``"total"``: the length of the UNION of
             the spans' intervals (compile events nest, so their plain sum
             counts the same seconds twice).
``minus``    names of child spans (``parent`` = the span's ``id``) whose
             durations are taken off each span: its self time.
``per_parent`` add up the spans that share a parent first (``data/next`` and
             ``data/h2d`` of one wave).
``window``   ``stretch``: spans that start inside the traced stretch, from
             the start of the first ``serve/tick`` or ``train/wave`` recorded
             to the end of the last (the program's spans are on only while
             the profiler runs). The stretch ends at the close of the
             measured window, which :func:`window_open` places on the
             recorder's clock; ``submitted``: ``req/*`` legs of requests
             whose ``req/queue`` starts in the window; ``in_window``: spans
             that END in it (``compile/*`` and ``req/*`` are recorded
             whether or not the profiler runs); ``before_window``: spans
             that end before it opens, i.e. during set-up.
``scale``    seconds times this (1e3 = ms).
"""

from collections import namedtuple
from fnmatch import fnmatchcase

from benchmark import trace
from benchmark.drivers.common import percentile

#: The order of ``rocket_tpu.obs.spans.SpanEvent``.
Span = namedtuple("Span", "name start end parent ids tid id cat")
STRETCH = ("serve/tick", "train/wave")


def load(ctx):
    """``(spans, (lo, hi))``: the recorded spans and the traced stretch on
    the recorder's clock (``host["stretch"]`` where the driver names it,
    else from the first ``serve/tick`` or ``train/wave`` recorded to the
    last), or ``(None, None)`` where either is missing."""
    rows = ctx.get("spans")
    if rows is None:
        try:
            from rocket_tpu.obs.spans import recorded
        except ImportError:
            return None, None
        rows = recorded()
    spans = [Span(*row) for row in rows]
    stretch = ctx.get("host", {}).get("stretch")
    if stretch:
        # The serving drivers keep the profiler (and so the spans) on until
        # their loop has drained; the stretch they name ends at the close.
        return spans, (float(stretch[0]), float(stretch[1]))
    marks = [s for s in spans if s.name in STRETCH]
    if not marks:
        return None, None
    return spans, (min(s.start for s in marks), max(s.end for s in marks))


def window_open(ctx, stretch):
    """Where the measured window opened, on the recorder's clock: the train
    driver's first wave (``host["waves"]`` holds raw ``perf_counter``
    instants), else the end of the stretch less the cell's seconds (the
    serve driver's clock counts from the window's start)."""
    waves = ctx.get("host", {}).get("waves")
    if waves:
        return float(waves[0][0])
    return stretch[1] - float(ctx["cell_seconds"])


def select(ctx, spans, stretch, names, window):
    lo, hi = stretch
    named = [s for s in spans if any(fnmatchcase(s.name, n) for n in names)]
    if window == "stretch":
        return [s for s in named if lo <= s.start <= hi]
    opened = window_open(ctx, stretch)
    if window == "in_window":
        return [s for s in named if opened <= s.end <= hi]
    if window == "before_window":
        return [s for s in named if s.end < opened]
    if window == "submitted":
        rids = {
            s.ids.get("rid") for s in spans
            if s.name == "req/queue" and opened <= s.start < hi
        }
        return [s for s in named if s.ids.get("rid") in rids]
    raise ValueError(f"program_spans: unknown window={window!r}")


def read(ctx, *, name, q, minus=(), per_parent=False, window="stretch",
         scale=1.0):
    spans, stretch = load(ctx)
    if spans is None:
        return None
    names = [name] if isinstance(name, str) else list(name)
    chosen = select(ctx, spans, stretch, names, window)
    if not chosen:
        return None
    if q == "total":
        return scale * trace.total(trace.union((s.start, s.end) for s in chosen))
    seconds = {s.id: s.end - s.start for s in chosen}
    if minus:
        for child in spans:
            if child.parent in seconds and child.name in minus:
                seconds[child.parent] -= child.end - child.start
    if per_parent:
        sums: dict = {}
        for s in chosen:
            sums[s.parent] = sums.get(s.parent, 0.0) + seconds[s.id]
        seconds = sums
    return scale * percentile(list(seconds.values()), float(q))
