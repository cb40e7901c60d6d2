"""The training driver: ``Launcher([Looper([Dataset, Module([Loss,
Optimizer, Scheduler])])])`` over a token stream from the seed.

One ``Launcher.launch()`` does everything the program does: its first
steps are the warm-up (the first wave compiles or loads the step) and the
steps the reference follows; the SAME compiled step and state then run the
window. The benchmark's own capsules sit around ``Dataset`` and ``Module``
and only read clocks, open profiler spans and read the step's metrics.
"""

from __future__ import annotations

import collections
import statistics
import time

import jax
import jax.numpy as jnp

from benchmark import counts
from benchmark.drivers import common
from benchmark.reference import gpt2 as ref
from benchmark.traffic import generator

#: Steps before the clock starts: three the reference follows and one
#: more, so that the copy of the three-step change is off the device
#: queue before the window opens.
WARM_STEPS = 4
#: Steps the host may run ahead of the device inside the window.
LAG = 2


def _adam_mu(opt_state):
    """The first-moment tree in an optax optimizer state."""
    found = [
        s for s in jax.tree.leaves(opt_state, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu")
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def reference_steps(config: dict, opt: dict, seed: int, tokens, *,
                    block_rows: int, quant=None, rows=None) -> dict:
    """The plain reference over the batches ``tokens`` (steps, B, T): each step's
    loss, the per-leaf norms of the first gradient and of the parameters'
    change after the last step. ``quant``/``rows`` plant the control and
    the half-batch fault."""
    n_head, n_layer, n_embd = config["n_head"], config["n_layer"], config["n_embd"]
    make = jax.jit(lambda key: ref.make_params(key, config, jnp.float32))
    params = make(ref.seed_key(seed))
    state = ref.adamw_init(params)
    losses, grad_norms = [], None
    for i in range(len(tokens)):
        loss, grads = ref.loss_and_grads(
            params, jnp.asarray(tokens[i]), n_head, block_rows=block_rows,
            quant=quant, rows=rows,
        )
        losses.append(float(loss))
        if i == 0:
            grad_norms = jax.device_get(
                jax.jit(lambda g: ref.per_layer_norms(g, n_layer, n_embd))(grads)
            )
        lr = ref.warmup_cosine(
            i, peak=opt["lr"], warmup_steps=opt["warmup_steps"],
            decay_steps=opt["decay_steps"],
        )
        params, state = ref.adamw_update(
            params, grads, state, i, lr, b1=opt["b1"], b2=opt["b2"],
            eps=opt["eps"], weight_decay=opt["weight_decay"],
        )
        del grads
    delta = jax.jit(lambda p, p0: ref.per_layer_norms(
        jax.tree.map(jnp.subtract, p, p0), n_layer, n_embd
    ))(params, make(ref.seed_key(seed)))
    return {
        "losses": losses,
        "grad_norms": {k: float(v) for k, v in grad_norms.items()},
        "delta_norms": {k: float(v) for k, v in jax.device_get(delta).items()},
    }


def compare(program: dict, reference: dict) -> dict:
    """The numbers `correct` is decided by, program against reference."""
    numbers = {
        f"loss{i + 1}_rel": abs(p - r) / abs(r)
        for i, (p, r) in enumerate(zip(program["losses"], reference["losses"]))
    }
    numbers["grad1_leaf_gap"], at = common.worst_leaf_gap(
        program["grad_norms"], reference["grad_norms"]
    )
    worst = {"grad1_leaf_gap": at}
    # Leaves whose first gradient is nought to rounding move under Adam by
    # round-off alone: left out by a rule on the reference's gradient.
    med = statistics.median(reference["grad_norms"].values())
    skip = {n for n, g in reference["grad_norms"].items() if g < 1e-3 * med}
    numbers["delta3_leaf_gap"], at = common.worst_leaf_gap(
        program["delta_norms"], reference["delta_norms"], skip=skip
    )
    worst["delta3_leaf_gap"] = at
    return numbers, worst


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace_dir=None,
        setup_clock=None) -> dict:
    """One run of a training cell. ``trace_dir`` set = a traced run."""
    import rocket_tpu as rt
    from rocket_tpu import optim
    from rocket_tpu.data.text import TokenDataset
    from rocket_tpu.models.transformer import next_token_loss

    setup_clock = setup_clock or common.Clock()
    mix = generator.load_mix(cell["traffic_file"])
    opt = cell["optimizer"]
    batch, seq = mix["batch"], mix["seq_len"]
    vocab = config["vocab_size"]
    tracing = trace_dir is not None
    trace_seconds = min(float(cell.get("trace_seconds", 10)), seconds)
    n_batches = int(seconds * mix["steps_per_second_ceiling"]) + WARM_STEPS + LAG + 1
    tokens = generator.corpus(mix, seed, vocab, n_batches)
    data = TokenDataset(tokens, seq_len=seq)

    common.note(setup_clock, f"imports done, corpus of {n_batches} batches made")
    runtime = rt.Runtime(seed=int(seed) % (2**31 - 1))
    devices = list(runtime.mesh.devices.flat)
    model = common.seeded_model(config, seed, jnp.float32)
    module = rt.Module(
        model,
        capsules=[
            rt.Loss(next_token_loss()),
            rt.Optimizer(optim.adamw(
                b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                weight_decay=opt["weight_decay"],
            )),
            rt.Scheduler(optim.warmup_cosine_lr(
                opt["lr"], warmup_steps=opt["warmup_steps"],
                decay_steps=opt["decay_steps"],
            )),
        ],
        compute_dtype=jnp.bfloat16,
    )

    norms = jax.jit(lambda tree: ref.tree_norms(tree, config["n_embd"]))
    delta_norms = jax.jit(lambda p, key: ref.tree_norms(jax.tree.map(
        jnp.subtract, p, common.program_params(config, key, jnp.float32)
    ), config["n_embd"]))
    seen: dict = {"step": 0, "losses": [], "waves": [], "pending": collections.deque()}

    class Edge(rt.Capsule):
        """Reads the clock and closes/opens the host spans between the
        program's capsules; ``after`` is the span that starts here."""

        def __init__(self, key, after=None, priority=1000):
            super().__init__(priority=priority)
            self.key, self.after = key, after

        def launch(self, attrs=None):
            open_span = seen.pop("span", None)
            if open_span is not None:
                open_span.__exit__(None, None, None)
            seen[self.key] = time.perf_counter()
            if self.after is not None and seen.get("tracing"):
                seen["span"] = jax.profiler.TraceAnnotation(self.after)
                seen["span"].__enter__()

    class Probe(rt.Capsule):
        """After the Module: the warm-up's readings, the window's clock."""

        def __init__(self):
            super().__init__(priority=50)

        def launch(self, attrs=None):
            seen["step"] += 1
            i = seen["step"]
            loss = attrs.step_metrics.loss
            if i <= WARM_STEPS - 1:
                seen["losses"].append(loss)
            if i <= WARM_STEPS:
                common.note(setup_clock, f"step {i} dispatched")
            if i == 1:
                # The first gradient as the optimizer got it: after one
                # step Adam's first moment is (1 - b1) * g.
                seen["mu_norms"] = norms(_adam_mu(module.state["opt_state"]))
            if i == WARM_STEPS - 1:
                seen["delta_norms"] = delta_norms(
                    module.state["params"], ref.seed_key(seed)
                )
            if i < WARM_STEPS:
                return
            if i == WARM_STEPS:
                jax.block_until_ready((loss, seen["mu_norms"], seen["delta_norms"]))
                seen["setup_s"] = setup_clock()
                seen["t0"] = time.perf_counter()
                common.note(setup_clock, "warm-up synced, window opens")
                return
            now = time.perf_counter()
            seen["pending"].append(loss)
            if len(seen["pending"]) > LAG:
                jax.block_until_ready(seen["pending"].popleft())
            after = time.perf_counter()
            # (wave start, data ready, module returned, probe start, lagged sync s)
            seen["waves"].append(
                (seen["t_wave"], seen["t_data"], seen["t_module"], now, after - now)
            )
            elapsed = after - seen["t0"]
            if tracing and "trace_t0" not in seen and elapsed >= seconds - trace_seconds:
                jax.block_until_ready(loss)
                jax.profiler.start_trace(trace_dir)
                seen["tracing"] = True
                seen["trace_t0"] = time.perf_counter()
                seen["trace_step0"] = i
            if elapsed >= seconds:
                jax.block_until_ready(loss)
                seen["t_end"] = time.perf_counter()
                seen["steps"] = i - WARM_STEPS
                attrs.looper.terminate = True

    common.note(setup_clock, "runtime and capsules built")
    try:
        rt.Launcher(
            [rt.Looper(
                [Edge("t_wave", after="bench/data_wait"),
                 rt.Dataset(data, batch_size=batch, shuffle=False, drop_last=True),
                 Edge("t_data", after="bench/module"),
                 module,
                 Edge("t_module", priority=60),
                 Probe()],
                tag="train", progress=False,
            )],
            num_epochs=1, runtime=runtime,
        ).launch()
    finally:
        open_span = seen.pop("span", None)
        if open_span is not None:
            open_span.__exit__(None, None, None)
        if seen.get("tracing"):
            jax.profiler.stop_trace()

    if "t_end" not in seen:
        # The corpus ran out before the time did (the step got more than
        # twice as fast as when the corpus was sized): the window is what ran.
        if "t0" not in seen:
            raise RuntimeError("the run ended inside its warm-up")
        seen["t_end"] = time.perf_counter()
        seen["steps"] = seen["step"] - WARM_STEPS
    common.note(setup_clock, "window closed, program torn down")
    window_s = seen["t_end"] - seen["t0"]
    tokens_per_step = batch * seq
    result = {
        "attempted": seen["steps"],
        "failed": 0,
        "end_to_end": {
            "train_tokens_per_s": seen["steps"] * tokens_per_step / window_s,
            "setup_s": seen["setup_s"],
        },
        "memory_peak_bytes": common.peak_bytes(devices),
        "host": {"waves": seen["waves"], "batch": batch, "seq_len": seq},
    }
    if tracing:
        traced_steps = seen["step"] - seen["trace_step0"]
        result["host"].update(
            traced_s=seen["t_end"] - seen["trace_t0"],
            traced_steps=traced_steps,
            traced_flops=traced_steps * tokens_per_step
            * counts.train_flops_per_token(config, seq),
        )

    program = {
        "losses": [float(x) for x in jax.device_get(seen["losses"])],
        "grad_norms": {
            k: float(v) / (1.0 - opt["b1"])
            for k, v in jax.device_get(seen["mu_norms"]).items()
        },
        "delta_norms": {k: float(v) for k, v in jax.device_get(seen["delta_norms"]).items()},
    }
    # The program's state is gone (the Launcher's destroy pass cleared it);
    # drop what this frame still holds before the reference takes the chip.
    del module, model, runtime, data
    seen.clear()
    t = time.perf_counter()
    rows = tokens[: (WARM_STEPS - 1) * tokens_per_step].reshape(
        WARM_STEPS - 1, batch, seq
    )
    reference = reference_steps(
        config, opt, seed, rows,
        block_rows=int(cell.get("reference_block_rows", 2)),
    )
    result["numbers"], result["worst_leaf"] = compare(program, reference)
    common.note(setup_clock, f"reference done in {time.perf_counter() - t:.1f}s")
    return result
