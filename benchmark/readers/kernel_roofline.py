"""A kernel's share of its roofline, in %: the least time the chip could
take for the work the traced window REQUIRED of it — the larger of
operations over peak FLOP/s and bytes over peak bytes/s, from
``benchmark/counts.py`` and the traffic, never from the kernel's grid —
over the summed device time of the kernel's events.

``per`` says how the window's work is counted: ``train_step`` = one call of
``count(config, batch, seq_len)`` per layer per traced step;
``decode_tokens`` = ``count(config, live contexts of the traced decode
tokens)`` per layer. No events matching ``pattern`` = nothing to read."""

from benchmark import counts, trace


def read(ctx, *, pattern: str, count: str, per: str):
    durations = trace.op_durations_s(ctx["trace"], pattern)
    if not durations:
        return None
    host, config = ctx["host"], ctx["config"]
    fn = getattr(counts, count)
    if per == "train_step":
        if not host.get("traced_steps"):
            return None
        work = fn(config, host["batch"], host["seq_len"])
        calls = host["traced_steps"] * config["n_layer"]
    elif per == "decode_tokens":
        if not host.get("traced_decode"):
            return None
        work = fn(config, host["traced_decode"])
        calls = config["n_layer"]
    else:
        raise ValueError(f"kernel_roofline: unknown per={per!r}")
    if ctx["peaks"] is None:
        raise KeyError(f"no peak for device kind {ctx['device_kind']!r} in peaks.json")
    least = calls * max(
        work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / sum(durations)
