"""Host time per training wave OUTSIDE the jitted call and the benchmark's
own lagged sync, in milliseconds: the wave's period minus the Module's call
and minus the sync wait — what ``Looper``, ``Dataset`` and the capsules
between them cost the host (clocks in the benchmark's own capsules)."""

from benchmark.drivers.common import percentile


def read(ctx, *, q: float = 50):
    waves = ctx["host"].get("waves")
    if not waves or len(waves) < 2:
        return None
    outside = [
        (nxt[0] - w[0]) - (w[2] - w[1]) - w[4]
        for w, nxt in zip(waves, waves[1:])
    ]
    return percentile(outside, q) * 1e3
