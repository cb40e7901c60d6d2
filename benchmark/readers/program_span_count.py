"""How many spans of a name the PROGRAM recorded that end inside the measured
window, all of it and not only its traced stretch (see ``program_spans``,
``window_open``): with ``compile/backend``, the programs compiled or loaded
between the window's opening and the last wave or tick, each once however
many events it raised. Compile events are recorded whether or not the
profiler runs, so a recompilation anywhere in the window is counted. Nought
is a reading; no recorder or no stretch to place the window by is not."""

from benchmark.readers import program_spans


def read(ctx, *, name):
    spans, stretch = program_spans.load(ctx)
    if spans is None:
        return None
    return float(len(program_spans.select(ctx, spans, stretch, [name], "in_window")))
