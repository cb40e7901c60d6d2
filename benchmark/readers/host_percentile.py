"""A percentile of a list the driver recorded on the host clock
(``host[key]``): generator lateness, queue wait."""

from benchmark.drivers.common import percentile


def read(ctx, *, key: str, q: float):
    values = ctx["host"].get(key)
    if not values:
        return None
    return percentile(values, q)
