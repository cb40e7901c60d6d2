"""The CPU time the PROGRAM's spans of a name spent on their thread, from the
``cpu_s`` id every span carries while spans are on (``rocket_tpu.obs.spans``:
``time.thread_time()`` read beside the span's two ``perf_counter`` instants),
over the spans that start inside the traced stretch (``program_spans``).

``name``   a span name; ``*`` matches as in a file name.
``stat``   ``"mean"``: Σ ``cpu_s`` ÷ the spans, times ``scale``; ``"share"``:
           100 × Σ ``cpu_s`` ÷ Σ (end − start), in %. Near 100 the host
           computed for the whole span; far under it the thread waited
           inside it (a lock, the runtime, the device's queue) or was
           descheduled.
``scale``  seconds times this (1e3 = ms), for the mean.

Sums, not a percentile, are the statistics: the chip's host keeps a
thread's CPU time in steps of its clock tick (``cpu_s`` of a 2.7 ms span
reads 0 or a whole step, and a percentile 0), while a sum over the stretch's
hundreds of spans counts the steps that fell inside them: an estimate whose
error is about one over the square root of their count (so a share of a
span that computes throughout may read a few per cent over 100).

A program whose spans carry no ``cpu_s`` (before the id came) has nothing
to read: ``None``.
"""

from benchmark.readers import program_spans


def read(ctx, *, name, stat, scale=1.0):
    spans, stretch = program_spans.load(ctx)
    if spans is None:
        return None
    timed = [s for s in program_spans.select(ctx, spans, stretch, [name], "stretch")
             if "cpu_s" in s.ids]
    if not timed:
        return None
    cpu = [float(s.ids["cpu_s"]) for s in timed]
    if stat == "share":
        wall = sum(s.end - s.start for s in timed)
        return 100.0 * sum(cpu) / wall if wall > 0 else None
    if stat == "mean":
        return scale * sum(cpu) / len(cpu)
    raise ValueError(f"span_cpu: unknown stat={stat!r}")
