"""The serving driver: an open loop over ``ServeEngine.submit()`` and
``ServeEngine.step()``, one process, one thread.

Each turn of the loop submits every request now due, then takes one step.
A lead-in of the same traffic runs before the clock starts and belongs to
set-up; the measured requests are those DUE inside the window, each timed
from when it was due. After the window closes the loop goes on, with no
new arrivals, until every measured request has its first token (a minute
at most): a token that comes late is late, not missing.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import counts
from benchmark.drivers import common
from benchmark.reference import gpt2 as ref
from benchmark.traffic import generator

#: How long past the close a first token is waited for.
GRACE_S = 60.0


def reference_gaps(config: dict, seed: int, sample: list, *, span: int,
                   quant=None, control: bool = False) -> list[float]:
    """For each ``(prompt, served tokens)``: the widest gap by which a
    served token's reference logit lies below the reference's best, over
    the answer's positions (at most ``span`` of them). ``control=True``
    reads instead the gap of the token that ``quant``'s lower precision
    puts first at each position."""
    n_head = config["n_head"]
    pad = config["n_positions"]  # every sequence padded to it: one compile
    params = jax.jit(
        lambda key: ref.make_params(key, config, jnp.bfloat16)
    )(ref.seed_key(seed))

    @jax.jit
    def answer_logits(params, tokens, start):
        # Hidden states of the whole sequence, logits of ``span`` positions.
        def head(x, q):
            x = jax.lax.dynamic_slice_in_dim(x, start, span, axis=0)
            table = params["wte"]["table"].astype(jnp.float32)
            return jnp.einsum("td,vd->tv", q(x), q(table), precision=ref.HIGHEST)

        out = head(ref.hidden(params, tokens[None], n_head)[0], lambda a: a)
        if not control:
            return out, jnp.argmax(out, axis=-1)
        low = head(ref.hidden(params, tokens[None], n_head, quant)[0], quant)
        return out, jnp.argmax(low, axis=-1)

    gaps = []
    for prompt, served in sample:
        p, a = len(prompt), len(served)
        padded = np.zeros((pad,), np.int32)
        padded[:p + a] = np.concatenate([prompt, served])
        # Position p-1+j predicts answer token j.
        start = min(p - 1, pad - span)
        out, picked = jax.device_get(
            answer_logits(params, jnp.asarray(padded), start)
        )
        off = p - 1 - start
        out = out[off:off + a]
        tokens = picked[off:off + a] if control else served
        gaps.append(float(np.max(out.max(axis=-1) - out[np.arange(a), tokens])))
    return gaps


def build_engine(cell: dict, config: dict, seed: int):
    from rocket_tpu.models.transformer import TransformerLM
    from rocket_tpu.serve import ServeConfig, ServeEngine

    model = TransformerLM(common.transformer_config(config))
    params = jax.jit(
        lambda key: common.program_params(config, key, jnp.bfloat16)
    )(ref.seed_key(seed))
    e = cell["engine"]
    engine = ServeEngine(model, params, ServeConfig(
        max_slots=e["max_slots"], block_len=e["block_len"],
        prefill_chunk=e["prefill_chunk"],
        decode_waves_per_dispatch=e["decode_waves_per_dispatch"],
    ))
    return engine


def warm_up(engine, config: dict, chunk: int) -> None:
    """Both programs once: a prompt of two chunks, two tokens."""
    prompt = np.arange(chunk + 3, dtype=np.int32) % config["vocab_size"]
    engine.submit(prompt, max_new_tokens=2, temperature=0.0)
    engine.drain()


class Loop:
    """The open loop and its records."""

    def __init__(self, engine, arrivals, *, seconds: float, temperature: float,
                 tracing: bool = False):
        self.engine = engine
        self.arrivals = arrivals
        self.seconds = seconds
        self.temperature = temperature
        self.tracing = tracing
        self.next = 0
        self.by_rid: dict = {}       # rid -> index into arrivals
        self.submit_at: dict = {}    # index -> time submitted
        self.token_at: dict = {}     # index -> [times of its tokens]
        self.refused: list = []
        self.ticks: list = []        # (start, end, active slots) per step

    def turn(self, clock) -> None:
        now = clock()
        with common.span("bench/submit", self.tracing):
            while self.next < len(self.arrivals) and self.arrivals[self.next].due_s <= now:
                a = self.arrivals[self.next]
                try:
                    rid = self.engine.submit(
                        a.prompt, max_new_tokens=a.max_new_tokens,
                        temperature=self.temperature,
                    )
                    self.by_rid[rid] = self.next
                    self.submit_at[self.next] = clock()
                    self.token_at[self.next] = []
                except ValueError:
                    self.refused.append(self.next)
                self.next += 1
        t_step = clock()
        with common.span("bench/step", self.tracing):
            events = self.engine.step()
        t = clock()
        self.ticks.append((t_step, t, self.engine.scheduler.active_slots))
        for ev in events:
            self.token_at[self.by_rid[ev.request.id]].append(t)

    def measured(self) -> list[int]:
        return [i for i, a in enumerate(self.arrivals) if 0.0 <= a.due_s < self.seconds]

    def waiting_for_first_token(self) -> bool:
        return any(
            i in self.token_at and not self.token_at[i] for i in self.measured()
        )

    def run(self, clock) -> None:
        """From the first lead-in arrival until the window has closed,
        every arrival is submitted and every measured request has its
        first token."""
        while True:
            now = clock()
            left = self.next < len(self.arrivals)
            if now >= self.seconds and not left:
                if not self.waiting_for_first_token() or now >= self.seconds + GRACE_S:
                    return
            due = left and self.arrivals[self.next].due_s <= now
            if self.engine.scheduler.idle and not due:
                time.sleep(0.001)
                continue
            self.turn(clock)


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace_dir=None,
        setup_clock=None) -> dict:
    """One run of a serving cell. ``trace_dir`` set = a traced run."""
    setup_clock = setup_clock or common.Clock()
    mix = generator.load_mix(cell["traffic_file"])
    tracing = trace_dir is not None
    trace_seconds = min(float(cell.get("trace_seconds", 10)), seconds)
    lead_in_s = float(cell["lead_in_s"])
    arrivals = generator.requests(
        mix, seed, rate_per_s=float(cell["rate_per_s"]), seconds=seconds,
        lead_in_s=lead_in_s, vocab_size=config["vocab_size"],
    )
    common.note(setup_clock, f"imports done, {len(arrivals)} arrivals made")
    engine = build_engine(cell, config, seed)
    devices = jax.devices()[:1]
    common.note(setup_clock, "engine built")
    warm_up(engine, config, cell["engine"]["prefill_chunk"])
    common.note(setup_clock, "both programs warm, lead-in starts")

    loop = Loop(engine, arrivals, seconds=seconds,
                temperature=float(mix["temperature"]), tracing=tracing)
    # The clock's zero is the start of the window; the lead-in runs at
    # negative times and is part of set-up.
    t_zero = time.perf_counter() + lead_in_s
    clock = common.Clock(t_zero)
    trace = {}
    if tracing:
        # The traced stretch is the END of the window: ``bench/stretch`` is
        # a span from its start to the close of the window, which the
        # readers' trace is cut to. The profiler itself is stopped only
        # once the loop has drained: stopping it takes a second in docqa
        # and 85 s after a loaded chat stretch (a million device events),
        # and inside the loop that blocks the engine. The Python tracer is
        # off: no reader looks at Python calls, and they cost the host
        # some 0.7 ms of every traced tick.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        original_turn = loop.turn

        def turn(clock_):
            if "t0" not in trace and clock_() >= seconds - trace_seconds:
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                trace["span"] = jax.profiler.TraceAnnotation("bench/stretch")
                trace["span"].__enter__()
                trace["t0"] = clock_()
            if "t0" in trace and "t1" not in trace and clock_() >= seconds:
                trace["t1"] = clock_()
                trace["span"].__exit__(None, None, None)
            original_turn(clock_)

        loop.turn = turn
    try:
        loop.run(clock)
    finally:
        if "t0" in trace:
            if "t1" not in trace:
                trace["t1"] = clock()
                trace["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
    setup_s = t_zero - setup_clock.start
    common.note(setup_clock, "window closed, first tokens all in")

    measured = loop.measured()
    ttft, failed = [], 0
    for i in measured:
        times = loop.token_at.get(i)
        if not times:
            failed += 1
            ttft.append(float("inf"))
        else:
            ttft.append((times[0] - arrivals[i].due_s) * 1e3)
    delivered = sum(
        1 for times in loop.token_at.values() for t in times if 0.0 <= t < seconds
    )
    gap = common.gap_metrics(loop.token_at.values(), seconds)
    late = [
        (loop.submit_at[i] - arrivals[i].due_s) * 1e3
        for i in measured if i in loop.submit_at
    ]
    result = {
        "attempted": len(measured),
        "failed": failed,
        "end_to_end": {
            "serve_tokens_per_s": delivered / seconds,
            "ttft_p90_ms": common.percentile(ttft, 90) if ttft else None,
            "tpot_p90_ms": gap["tpot_p90_ms"],
            "tpot_mean_ms": gap["tpot_mean_ms"],
            "itl_p95_ms": gap["itl_p95_ms"],
            "setup_s": setup_s,
        },
        "memory_peak_bytes": common.peak_bytes(devices),
        "host": {
            "gen_late_ms": late,
            "ticks": loop.ticks,
            "max_slots": cell["engine"]["max_slots"],
        },
    }
    finite = [t for t in ttft if t != float("inf")]
    if finite:
        common.note(setup_clock, "ttft_ms p50 %.1f p75 %.1f p90 %.1f mean %.1f max %.1f n %d" % (
            common.percentile(finite, 50), common.percentile(finite, 75),
            common.percentile(finite, 90), sum(finite) / len(finite), max(finite),
            len(finite)))
    common.note(setup_clock, "gap_ms tpot_p90 %s tpot_mean %s over %d requests; itl_p95 %s over %d gaps" % (
        gap["tpot_p90_ms"], gap["tpot_mean_ms"], gap["tpot_requests"],
        gap["itl_p95_ms"], gap["gaps"]))
    tracer = engine.tracer
    if tracer is not None:
        phases = [
            ph for rid, i in loop.by_rid.items()
            if 0.0 <= arrivals[i].due_s < seconds
            for ph in [tracer.phases(rid)] if ph is not None
        ]
        result["host"]["queue_ms"] = [ph["queue_s"] * 1e3 for ph in phases]
    if tracing and "t1" in trace:
        result["host"]["traced_s"] = trace["t1"] - trace["t0"]
        # The same stretch on the clock of the program's span recorder.
        result["host"]["stretch"] = (t_zero + trace["t0"], t_zero + trace["t1"])
        result["host"]["itl_gap_ms"] = [
            g for gaps in common.token_gaps(loop.token_at.values(), trace["t0"], trace["t1"])
            for g in gaps
        ]
        result["host"]["traced_flops"] = _flops_between(
            config, loop, arrivals, cell["engine"]["prefill_chunk"], trace["t0"], trace["t1"]
        )
        result["host"]["traced_decode"] = _decode_contexts(loop, arrivals, trace["t0"], trace["t1"])

    finished = [
        (arrivals[i].prompt, np.asarray(engine.result(rid).tokens, np.int32))
        for rid, i in loop.by_rid.items()
        if engine.result(rid).finished
    ]
    # The float32 reference runs over a sample fixed by the cell's
    # ``compare`` rule: the whole of a loaded window would take it longer
    # than the window itself.
    result["finished"] = len(finished)
    result["sample"] = sample = common.pick_sample(finished, cell["compare"]["every"], seed)
    # Free the program's state before the reference takes the chip.
    del engine, loop, tracer
    t = time.perf_counter()
    gaps_ref = reference_gaps(config, seed, sample, span=int(mix["answer"]["max"]))
    result["numbers"] = {"token_gap_max": max(gaps_ref) if gaps_ref else None}
    result["compared_tokens"] = int(sum(len(served) for _, served in sample))
    common.note(
        setup_clock,
        f"reference done in {time.perf_counter() - t:.1f}s over "
        f"{result['compared_tokens']} served tokens of {len(sample)} requests",
    )
    return result


def _flops_between(config, loop, arrivals, chunk, t0, t1) -> float:
    """Operations of every token processed in ``[t0, t1)``: a decode token
    is dated by its delivery; a request's prefill (all prompt positions
    but the last, which the first decode wave processes) by its first
    token."""
    positions = []
    for i, times in loop.token_at.items():
        p = len(arrivals[i].prompt)
        if times and t0 <= times[0] < t1:
            positions.extend(range(p - 1))
        positions.extend(p - 1 + j for j, t in enumerate(times) if t0 <= t < t1)
    return counts.serve_flops(config, positions)


def _decode_contexts(loop, arrivals, t0, t1) -> list:
    """Live context (positions attended) of every decode token delivered
    in ``[t0, t1)``."""
    return [
        len(arrivals[i].prompt) + j
        for i, times in loop.token_at.items()
        for j, t in enumerate(times) if t0 <= t < t1
    ]
