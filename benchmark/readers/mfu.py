"""The whole step's share of the chip's peak, in %: the operations the
traced window's tokens REQUIRE (``host['traced_flops']``, from
``benchmark/counts.py``; recomputed work not counted) over the window's
seconds, the chips and the peak of ``device_kind``. A device that is not in
``peaks.json`` is an error, not a default."""


def read(ctx):
    host = ctx["host"]
    if not host.get("traced_s") or host.get("traced_flops") is None:
        return None
    if ctx["peaks"] is None:
        raise KeyError(f"no peak for device kind {ctx['device_kind']!r} in peaks.json")
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * host["traced_flops"] / host["traced_s"] / peak
