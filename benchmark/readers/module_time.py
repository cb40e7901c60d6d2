"""Device duration of a jitted program: a percentile, in milliseconds, of
the ``XLA Modules`` events whose name matches ``pattern``."""

from benchmark import trace
from benchmark.drivers.common import percentile


def read(ctx, *, pattern: str, q: float = 50):
    durations = trace.module_durations_s(ctx["trace"], pattern)
    if not durations:
        return None
    return percentile(durations, q) * 1e3
