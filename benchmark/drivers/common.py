"""What both drivers share: the model built from a configuration file, the
weights handed to the program, the profiler window and the comparison of
numbers with their limits."""

from __future__ import annotations

import contextlib
import statistics
import sys
import time

import jax

from benchmark.reference import gpt2 as ref



def transformer_config(config: dict):
    """The program's ``TransformerConfig`` for a GPT-2 configuration file
    (no preset of the program is used: the sizes are the file's)."""
    from rocket_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["n_positions"],
        dim=config["n_embd"],
        num_layers=config["n_layer"],
        num_heads=config["n_head"],
        dropout=float(config["resid_pdrop"]),
        tied_embeddings=bool(config["tie_word_embeddings"]),
        activation_dtype="bfloat16",
        loss_chunk=128,
    )


def program_params(config: dict, key, dtype):
    """The benchmark's weights in the program's layout. Traceable; give
    the key as an ARGUMENT of the jitted call, so that one compiled
    program serves every seed."""
    return ref.unstack(ref.make_params(key, config, dtype), config["n_layer"])


def seeded_model(config: dict, seed: int, dtype):
    """A ``TransformerLM`` whose ``init`` returns the benchmark's weights,
    made on the device in one jitted call that takes the seed's key as its
    ARGUMENT: one compiled program serves every seed. ``Module`` first tries
    ``jax.jit(model.init)(its own key)``; a seed baked into that trace would
    compile anew for every seed (9 s of set-up on the chip), so under a
    trace this ``init`` declines and ``Module`` takes its documented
    fall-back, the eager call, which lands here. The program's key is not
    used: the reference must be able to make the same weights."""
    from rocket_tpu.models.transformer import TransformerLM

    make = jax.jit(lambda key: program_params(config, key, dtype))

    class SeededLM(TransformerLM):
        def init(self, key):
            if isinstance(key, jax.core.Tracer):
                raise TypeError("SeededLM.init jits its own weights; call it eagerly")
            return {"params": make(ref.seed_key(seed)), "state": {}}

    return SeededLM(transformer_config(config))


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where the backend
    reports none, as the CPU does)."""
    stats = [d.memory_stats() or {} for d in devices]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100), linear between order statistics."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values) -> float:
    return float(statistics.median(values))


#: A request counts toward ``tpot_p90_ms`` with at least this many gaps.
TPOT_MIN_GAPS = 8


def token_gaps(token_times, lo: float, hi: float) -> list[list[float]]:
    """Per request (a list of its tokens' delivery times, seconds), the
    gaps in ms between successive tokens whose LATER token is delivered in
    ``[lo, hi)``. Requests with no such gap are left out."""
    per_request = [
        [(b - a) * 1e3 for a, b in zip(times, times[1:]) if lo <= b < hi]
        for times in token_times
    ]
    return [gaps for gaps in per_request if gaps]


def gap_metrics(token_times, seconds: float) -> dict:
    """The statistics of the gap between tokens, over the window
    ``[0, seconds)``. ``tpot_p90_ms``: per request the MEAN of its gaps
    (time per output token), over requests with ``TPOT_MIN_GAPS`` gaps or
    more, then the 90th percentile over those requests; ``tpot_mean_ms``
    the mean over the same requests; ``itl_p95_ms`` the 95th percentile of
    the single gaps of every request. A statistic with nothing under it is
    None."""
    per_request = token_gaps(token_times, 0.0, seconds)
    single = [g for gaps in per_request for g in gaps]
    means = [sum(g) / len(g) for g in per_request if len(g) >= TPOT_MIN_GAPS]
    return {
        "tpot_p90_ms": percentile(means, 90) if means else None,
        "tpot_mean_ms": sum(means) / len(means) if means else None,
        "itl_p95_ms": percentile(single, 95) if single else None,
        "tpot_requests": len(means),
        "gaps": len(single),
    }


def pick_sample(finished: list, every: int, seed: int = 0) -> list:
    """The requests the reference runs over: every ``every``-th of
    ``finished`` (``(prompt, served tokens)`` in submit order), starting at
    ``seed % every``, and always the longest (prompt + answer)."""
    every = max(1, int(every))
    chosen = set(range(int(seed) % every, len(finished), every))
    if finished:
        chosen.add(max(range(len(finished)),
                       key=lambda i: len(finished[i][0]) + len(finished[i][1])))
    return [finished[i] for i in sorted(chosen)]


def span(name: str, on: bool):
    """A host span in the profiler's own trace, or nothing."""
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def note(clock, what: str) -> None:
    """One line on standard error: where set-up's seconds go."""
    print(f"[{clock():8.2f}s] {what}", file=sys.stderr, flush=True)


class Clock:
    """Seconds since ``start`` on the host's monotonic clock."""

    def __init__(self, start: float | None = None):
        self.start = time.perf_counter() if start is None else start

    def __call__(self) -> float:
        return time.perf_counter() - self.start


def worst_leaf_gap(program: dict, reference: dict, skip=()) -> tuple[float, str]:
    """The largest ``|program - reference|`` over the leaves, each measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Returns ``(gap, leaf name)``."""
    names = [n for n in reference if n not in skip]
    med = statistics.median(float(reference[n]) for n in names)
    worst, at = 0.0, ""
    for n in names:
        r = float(reference[n])
        gap = abs(float(program[n]) - r) / max(r, med)
        if gap >= worst:
            worst, at = gap, n
    return worst, at


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``numbers`` (name -> value) against ``limits`` (name -> upper
    limit). A number without a limit, a limit without a number, or a value
    that is not finite and within its limit makes the run incorrect."""
    checks, ok = {}, set(numbers) == set(limits)
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if value is None or limit is None or not (value == value and value <= limit):
            ok = False
    return ok, checks
