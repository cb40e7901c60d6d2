"""chip_smoke.py — the quickest proof that rocket-tpu still starts on the chip.

Drives the two paths users depend on through their normal entry points, at
the full published width of GPT-2 124M (12 layers, d=768, 12 heads, vocab
50257, T=1024, bf16 compute), with random weights and a corpus made from
``--seed``:

* **train** — ``Runtime`` + ``Launcher([Looper([Dataset, Module([Loss,
  Optimizer, Scheduler])])])`` as ``examples/gpt2.py`` builds it, B=8, AdamW,
  for a handful of steps. Checks: the flash pallas kernel is what ran, every
  loss is finite, the loss falls, and the first step's loss agrees with a
  plain XLA-attention forward of the same params and batch.
* **serve** — ``ServeEngine`` on the same model, a few greedy requests of
  different prompt lengths. Checks: every request finishes, the decode
  program holds the fused paged-decode pallas kernel, and each request's
  tokens equal the non-paged greedy path (``generate``).
* ``--chips 4`` (never given by the driver) runs ONLY the same train steps
  on a four-device mesh — ``{"data": 4}`` and ``{"data": 2, "model": 2}``
  with ``gpt2_tp_rules`` — and the one-device reference they are compared
  with. One process drives all four chips.

One JSON object per phase goes to stdout; the LAST stdout line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Any failed check, or any exception, exits non-zero with ``"ok": false``.
Without a TPU the script fails at once and prints no result: it never sets
``JAX_PLATFORMS`` and never falls back to the CPU. The times it prints are
observations on one run, not a benchmark.

The phase functions take the model config as an argument so that
``tests/test_chip_smoke.py`` can rehearse them at a tiny width on the CPU
mesh; the device check in :func:`main` stays strict.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# In a directory that holds nothing else of the repo these fail, before
# anything is printed.
import rocket_tpu as rt
from rocket_tpu import optim, tune
from rocket_tpu.core.module import _split_batch
from rocket_tpu.data.device_cache import materialize_marker
from rocket_tpu.data.text import CharTokenizer, TokenDataset, synthetic_corpus
from rocket_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    _decode_params,
    generate,
    next_token_loss,
)
from rocket_tpu.nn.attention import resolve_impl
from rocket_tpu.ops.paged_attention import paged_decode_supported
from rocket_tpu.parallel.sharding import gpt2_tp_rules
from rocket_tpu.serve import ServeConfig, ServeEngine
from rocket_tpu.serve.engine import (
    DECODE_DONATE,
    abstract_wave_inputs,
    build_decode_wave,
)

#: |first-step loss - XLA-attention reference loss| the train phase admits
#: (nats; the loss is ~10.9 at init, so this is 0.5% — bf16 attention
#: accumulates in a different order in the flash kernel).
LOSS_TOL = 5e-2
#: |loss(mesh) - loss(one device)| per step the --chips 4 phase admits over
#: its first steps (bf16 reductions split differently across devices).
MESH_LOSS_TOL = 5e-2
#: max |paged logits - dense logits| admitted where a near-tie flipped a
#: greedy token. Logits leave the head in bf16: one ulp is 0.0156 for
#: |logit| in [2, 4), so this is three of them.
LOGIT_TOL = 5e-2


def device_line(ok: bool, devices) -> str:
    """The contract's last stdout line."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    })


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def phase_ok(record: dict) -> bool:
    """A phase passes when no check is False (None = does not apply, e.g.
    a kernel check in the CPU rehearsal)."""
    return all(v is not False for v in record["checks"].values())


def _peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None  # the CPU backend reports none
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def _spread(tree):
    """How a state subtree sits on the mesh: over how many devices its
    leaves live, how many leaves are split (a shard smaller than the
    array), over how many devices the split leaves spread, and the largest
    share of a split leaf that one device holds."""
    leaves = jax.tree.leaves(tree)
    split = split_devices = 0
    worst = 0.0
    for leaf in leaves:
        shards = leaf.addressable_shards
        if shards[0].data.size < leaf.size:
            split += 1
            split_devices = max(split_devices, len({s.device for s in shards}))
            worst = max(worst, shards[0].data.size / leaf.size)
    return {
        "leaves": len(leaves),
        "on_devices": min(
            len({s.device for s in leaf.addressable_shards}) for leaf in leaves
        ),
        "split_leaves": split,
        "split_over_devices": split_devices,
        "max_share_on_one_device": round(worst, 4),
    }


def train_phase(config, *, batch: int, steps: int, seed: int,
                mesh_shape=None, devices=None, param_sharding=None,
                require_kernels: bool = True, name: str = "train") -> dict:
    """``steps`` optimizer steps of ``TransformerLM(config)`` through the
    capsule tree; returns the phase record (observations + ``checks``)."""
    seq = config.max_seq_len
    runtime = rt.Runtime(mesh_shape=mesh_shape, devices=devices, seed=seed)
    mesh_devices = list(runtime.mesh.devices.flat)

    # Corpus from the seed: synthetic text, char-tokenized (the ids stay
    # far below the vocab — a learnable stream, so ten steps show a fall).
    need = batch * steps * seq
    text = synthetic_corpus(num_chars=need + need // 8 + 64, seed=seed)
    tokens = CharTokenizer(text).encode(text)[:need] % config.vocab_size
    if len(tokens) != need:
        raise RuntimeError(f"corpus came out short: {len(tokens)} < {need}")
    data = TokenDataset(tokens.astype(np.int32), seq_len=seq)

    model = TransformerLM(config)
    objective = next_token_loss()
    reference = TransformerLM(dataclasses.replace(config, attention_impl="xla"))

    def reference_loss(params, batch_):
        out, _ = reference.apply(
            {"params": params, "state": {}}, dict(batch_), mode="train"
        )
        return objective(out).astype(jnp.float32)

    module = rt.Module(
        model,
        capsules=[
            rt.Loss(objective),
            rt.Optimizer(optim.adamw(weight_decay=0.1)),
            rt.Scheduler(optim.warmup_cosine_lr(
                6e-4, warmup_steps=max(1, steps // 50), decay_steps=steps,
            )),
        ],
        param_sharding=param_sharding,
        compute_dtype=jnp.bfloat16,
    )
    seen: dict = {"losses": [], "marks": []}

    class Before(rt.Capsule):
        """Runs between Dataset and Module. On the first wave — while the
        initial params are still live (the step donates them) — it prices
        the same params and batch through plain XLA attention."""

        def launch(self, attrs=None):
            if "ref_loss" not in seen:
                rows = materialize_marker(_split_batch(attrs.batch)[0])
                seen["ref_loss"] = float(jax.device_get(
                    jax.jit(reference_loss)(module.state["params"], rows)
                ))
                seen["t0"] = time.perf_counter()

    class After(rt.Capsule):
        """Runs after the Module: one real sync per step, then the clock."""

        def __init__(self):
            super().__init__(priority=50)

        def launch(self, attrs=None):
            loss = jax.block_until_ready(attrs.step_metrics.loss)
            seen["marks"].append(time.perf_counter())
            seen["losses"].append(float(jax.device_get(loss)))
            if len(seen["marks"]) == 1:
                # The program the step just ran, as text: same jitted
                # function, same arguments (a persistent-cache hit).
                seen["hlo"] = module._train_step.lower(
                    module.state, _split_batch(attrs.batch)[0]
                ).compile().as_text()
                # The device is idle here; restart the clock so step 2's
                # time does not carry this compile.
                seen["first_step_s"] = seen["marks"][0] - seen["t0"]
                seen["marks"][0] = time.perf_counter()
            if len(seen["marks"]) == steps:
                # block_until_ready is the sync this script trusts: if it
                # returned early, this fetch of the step counter would
                # have to wait out the rest of the step.
                t = time.perf_counter()
                jax.device_get(module.state["step"])
                seen["fetch_after_sync_s"] = time.perf_counter() - t
                seen["params"] = _spread(module.state["params"])
                seen["moments"] = _spread(module.state["opt_state"])

    rt.Launcher(
        [rt.Looper(
            [rt.Dataset(data, batch_size=batch, shuffle=True, drop_last=True),
             Before(), module, After()],
            tag="train", progress=False,
        )],
        num_epochs=1, runtime=runtime,
    ).launch()

    attn = model.blocks[0].attn
    impl = resolve_impl(
        config.attention_impl, seq, attn.head_dim, b=batch,
        h=attn.num_heads, h_kv=attn.num_kv_heads, mesh=runtime.mesh,
    )
    losses, marks = seen["losses"], seen["marks"]
    step_s = [b - a for a, b in zip(marks, marks[1:])]
    median_s = statistics.median(step_s)
    n_dev = len(mesh_devices)
    pallas_calls = seen["hlo"].count("tpu_custom_call")
    return {
        "phase": name,
        "mesh": dict(runtime.mesh.shape),
        "model": {"dim": config.dim, "layers": config.num_layers,
                  "heads": config.num_heads, "vocab": config.vocab_size,
                  "seq": seq, "batch": batch},
        "attention_impl": impl,
        "pallas_calls_in_step": pallas_calls,
        "losses": [round(x, 4) for x in losses],
        "reference_loss_xla": round(seen["ref_loss"], 4),
        "first_loss_abs_diff": round(abs(losses[0] - seen["ref_loss"]), 5),
        # First wave: trace + lower + compile (or cache load) + one step.
        "first_step_s": round(seen["first_step_s"], 3),
        "step_s": [round(x, 5) for x in step_s],
        "median_step_s": median_s,
        "steps_per_s": 1.0 / median_s,
        "tokens_per_s_per_chip": batch * seq / median_s / n_dev,
        "fetch_after_sync_s": round(seen["fetch_after_sync_s"], 6),
        "peak_bytes_in_use": _peak_bytes(mesh_devices),
        "params": seen["params"],
        "moments": seen["moments"],
        "checks": {
            "steps_ran": len(losses) == steps,
            "flash_impl": (impl == "flash") if require_kernels else None,
            "pallas_in_step": (pallas_calls > 0) if require_kernels else None,
            "losses_finite": bool(np.all(np.isfinite(losses))),
            "loss_fell": losses[-1] < losses[0],
            "first_loss_matches_xla":
                abs(losses[0] - seen["ref_loss"]) <= LOSS_TOL,
        },
    }


def _paged_logits(model, params, tokens, block_len: int):
    """Logits for the token after ``tokens`` through the paged path: the
    prefix prefilled in one chunk, then the last token as a C=1 decode
    wave against a one-slot pool."""
    n = len(tokens)
    # The engine's own sizing math, for one slot of exactly n positions.
    spec, mb, _, _ = ServeConfig(
        max_slots=1, block_len=block_len, max_model_len=n
    ).resolve(model.config)
    k_pages, v_pages = spec.init_pages()
    table = jnp.asarray(1 + np.arange(mb, dtype=np.int32)[None])
    toks = jnp.asarray(tokens, jnp.int32)[None]
    at = lambda v: jnp.asarray([v], jnp.int32)  # noqa: E731
    step = jax.jit(model.decode_step_paged)
    if n > 1:
        _, k_pages, v_pages = step(
            params, toks[:, :n - 1], k_pages, v_pages, table, at(0), at(n - 1)
        )
    logits, _, _ = step(
        params, toks[:, n - 1:], k_pages, v_pages, table, at(n - 1), at(1)
    )
    return np.asarray(logits[0], np.float32)


def _dense_logits(model, params, tokens):
    """The same logits through the non-paged path (``decode_step`` over
    ``apply_cached``, what ``generate`` runs)."""
    dtype = jnp.dtype(model.config.activation_dtype or jnp.float32)
    caches = model.init_cache(1, len(tokens), dtype)
    logits, _ = jax.jit(model.decode_step)(
        params, jnp.asarray(tokens, jnp.int32)[None], caches, 0
    )
    return np.asarray(logits[0], np.float32)


def divergence_gap(model, params, prefix, block_len: int) -> float:
    """max |paged - dense| over the logits both paths compute after the
    common ``prefix`` — what decides a request whose greedy tokens split."""
    params = _decode_params(params, model.config.activation_dtype)
    return float(np.max(np.abs(
        _paged_logits(model, params, prefix, block_len)
        - _dense_logits(model, params, prefix)
    )))


def serve_phase(config, *, seed: int, max_slots: int, block_len: int,
                prefill_chunk: int, prompt_lens, max_new_tokens: int,
                require_kernels: bool = True) -> dict:
    """A few greedy requests through ``ServeEngine`` against the dense
    ``generate`` path; returns the phase record."""
    model = TransformerLM(config)
    params = jax.jit(model.init)(jax.random.key(seed))["params"]
    serve_config = ServeConfig(
        max_slots=max_slots, block_len=block_len, prefill_chunk=prefill_chunk,
    )
    t0 = time.perf_counter()
    engine = ServeEngine(model, params, serve_config)
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, config.vocab_size, size=n).astype(np.int32)
        for n in prompt_lens
    ]
    rids = [
        engine.submit(p, max_new_tokens=max_new_tokens, temperature=0.0)
        for p in prompts
    ]
    engine.drain()
    serve_s = time.perf_counter() - t0
    report = engine.report()
    spec = engine.engine.spec

    # The C=1 decode program, as text: same builder, same signature as the
    # engine's jit (what serve_audit compiles too).
    decode_args, _ = abstract_wave_inputs(
        model, spec, max_slots=max_slots,
        max_blocks_per_seq=engine.engine.max_blocks_per_seq,
        prefill_chunk=prefill_chunk,
    )
    pallas_calls = jax.jit(
        build_decode_wave(model, waves=engine.engine.waves_per_dispatch),
        donate_argnums=DECODE_DONATE,
    ).lower(*decode_args).compile().as_text().count("tpu_custom_call")

    requests = []
    variables = {"params": params}
    for rid, prompt in zip(rids, prompts):
        got = np.asarray(engine.result(rid).tokens, np.int32)
        want = np.asarray(generate(
            model, variables, prompt[None], max_new_tokens, temperature=0.0,
        ))[0, len(prompt):]
        record = {
            "prompt_len": int(len(prompt)),
            "finished": bool(engine.result(rid).finished),
            "new_tokens": int(len(got)),
        }
        if np.array_equal(got, want):
            record.update(decided_by="tokens", ok=True)
        else:
            # A random-init model has near-ties, and two kernels that
            # reduce in a different order may flip one in bf16: compare
            # the two paths' logits after the common prefix instead.
            m = min(len(got), len(want))
            differ = np.flatnonzero(got[:m] != want[:m])
            n = int(differ[0]) if differ.size else m
            gap = divergence_gap(
                model, params, np.concatenate([prompt, got[:n]]), block_len
            )
            record.update(
                decided_by="logits", first_diff_at=n,
                logit_max_abs_diff=gap,
                ok=len(got) == len(want) and gap <= LOGIT_TOL,
            )
        requests.append(record)

    return {
        "phase": "serve",
        "pool": {"slots": max_slots, "num_blocks": spec.num_blocks,
                 "block_len": spec.block_len, "dtype": spec.dtype,
                 "bytes": spec.pool_bytes},
        "prefill_chunk": prefill_chunk,
        "pallas_calls_in_decode": pallas_calls,
        "requests": requests,
        "compiled": report["compiled"],
        # Observations on one run (compiles included in the first two).
        "serve_wall_s": round(serve_s, 3),
        "ttft_s": report["time_to_first_token_s"],
        "inter_token_s": report["inter_token_latency_s"],
        "peak_bytes_in_use": _peak_bytes(jax.devices()[:1]),
        "checks": {
            "all_finished": all(
                r["finished"] and r["new_tokens"] == max_new_tokens
                for r in requests
            ),
            "kernel_supported": paged_decode_supported(
                spec.block_len, spec.head_dim, np.dtype(spec.dtype).itemsize,
                lanes=spec.lanes[0],
            ) if require_kernels else None,
            "pallas_in_decode":
                (pallas_calls > 0) if require_kernels else None,
            "one_decode_trace": report["compiled"]["decode_traces"] == 1,
            "matches_dense_path": all(r["ok"] for r in requests),
        },
    }


def mesh_phases(config, *, batch: int, steps: int, seed: int,
                require_kernels: bool = True) -> list:
    """The --chips 4 phase: the same train steps on ``{"data": 4}`` and on
    ``{"data": 2, "model": 2}`` with ``gpt2_tp_rules``, each compared with
    the same global batch and seed on a mesh of ONE device of the four."""
    devices = jax.devices()
    common = dict(batch=batch, steps=steps, seed=seed)
    # One device of four resolves "auto" attention to XLA: an independent
    # reference, so its kernel checks do not apply.
    ref = train_phase(
        config, mesh_shape={"data": 1}, devices=devices[:1],
        name="train_ref_1dev", require_kernels=False, **common,
    )
    dp = train_phase(
        config, mesh_shape={"data": 4}, devices=devices[:4],
        name="train_dp4", require_kernels=require_kernels, **common,
    )
    tp = train_phase(
        config, mesh_shape={"data": 2, "model": 2}, devices=devices[:4],
        param_sharding=gpt2_tp_rules(), name="train_dp2_tp2",
        require_kernels=require_kernels, **common,
    )
    for record in (dp, tp):
        diffs = [abs(a - b) for a, b in zip(record["losses"], ref["losses"])]
        record["max_loss_diff_vs_1dev"] = round(max(diffs), 5)
        record["checks"]["losses_match_1dev"] = max(diffs) <= MESH_LOSS_TOL
    # Data parallel: every device holds the state (replicated), nothing
    # is split. Tensor parallel: the rule-matched params AND their Adam
    # moments are split over more than one device, and no device holds a
    # whole copy of a split leaf.
    dp["checks"]["state_on_every_device"] = (
        dp["params"]["on_devices"] == 4 and dp["moments"]["on_devices"] == 4
    )
    tp["checks"]["params_spread"] = (
        tp["params"]["split_leaves"] > 0
        and tp["params"]["split_over_devices"] > 1
        and tp["params"]["max_share_on_one_device"] < 1.0
    )
    tp["checks"]["moments_spread"] = (
        tp["moments"]["split_leaves"] >= 2 * tp["params"]["split_leaves"]
        and tp["moments"]["split_over_devices"] > 1
    )
    return [ref, dp, tp]


def tune_tables_note() -> dict:
    """Which tuned-kernel tables the run could read (git-committed files
    under rocket_tpu/tune/configs/): with no entries every kernel runs
    its hand-picked default."""
    return {
        "phase": "inputs",
        "tune_table_entries": {
            kernel: len((table or {}).get("entries", ()))
            for kernel, table in sorted(tune.load_tables().items())
        },
        "checks": {},
    }


def _phases(chips: int, seed: int):
    """The phase records of one run, in order, as they finish."""
    config = TransformerConfig.gpt2_124m()
    config.dropout = 0.0
    yield tune_tables_note()
    if chips == 4:
        yield from mesh_phases(config, batch=8, steps=6, seed=seed)
    else:
        yield train_phase(config, batch=8, steps=10, seed=seed)
        yield serve_phase(
            config, seed=seed, max_slots=16, block_len=16, prefill_chunk=128,
            prompt_lens=(7, 96, 150, 224), max_new_tokens=32,
        )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform!r}); "
              "this script never runs a model on the CPU", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    ok = False
    try:
        verdicts = []
        for record in _phases(args.chips, args.seed):
            emit(record)
            verdicts.append(phase_ok(record))
        ok = all(verdicts)
    finally:
        # An exception still propagates (traceback, non-zero exit); the
        # last stdout line says "ok": false either way.
        print(device_line(ok, devices), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
