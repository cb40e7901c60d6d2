"""Share of the traced window, in %, in which no operation ran on the
device: 1 - (union of the op intervals) / window."""

from benchmark import trace


def read(ctx):
    window_s = ctx["host"].get("traced_s")
    if not window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx["trace"]) / window_s)
