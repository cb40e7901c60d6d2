"""The whole step's share of the chip's peak, in %: the operations the
traced window's tokens REQUIRE (``host['traced_flops']``, from
``benchmark/counts.py``; recomputed work not counted) over seconds, the
chips and the peak of ``device_kind``. ``over="window"``: the traced
window's seconds, idle time included (what the offered load makes of the
chip). ``over="busy"``: the seconds in which an operation ran on the device
(``trace.busy_s``): the share of the peak WHILE the device works, which a
faster kernel or a kernel taken off the path raises at the same load. A
device that is not in ``peaks.json`` is an error, not a default."""

from benchmark import trace


def read(ctx, *, over: str = "window"):
    host = ctx["host"]
    if not host.get("traced_s") or host.get("traced_flops") is None:
        return None
    if over == "window":
        seconds = host["traced_s"]
    elif over == "busy":
        seconds = trace.busy_s(ctx["trace"])
    else:
        raise ValueError(f"mfu: unknown over={over!r}")
    if ctx["peaks"] is None:
        raise KeyError(f"no peak for device kind {ctx['device_kind']!r} in peaks.json")
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * host["traced_flops"] / seconds / peak
