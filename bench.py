"""Benchmark suite: every BASELINE.json north-star config, one JSON line.

Configs (driver contract: stdout carries exactly ONE JSON line; progress
goes to stderr):

* ``gpt2``       — GPT-2 124M, B=8, T=1024, bf16, flash attention, AdamW
                   (BASELINE.json configs[4], single chip). THE headline
                   metric: tok/sec/chip + MFU.
* ``gpt2_350m``  — GPT-2 medium (d=1024, ~354M params): the wider matmuls
                   fill the MXU better — the framework's best-MFU config.
* ``llama``      — Llama-family 124M-class (RoPE + RMSNorm + SwiGLU +
                   GQA-4): the second model family's throughput.
* ``charlm``     — TinyShakespeare char-transformer, B=128, T=256
                   (configs[2]): tok/sec/chip + MFU.
* ``resnet18``   — CIFAR-10 ResNet-18, B=256 (configs[1]): samples/sec/chip.
* ``resnet50``   — ImageNet-shape ResNet-50, B=128 (configs[3], single
                   chip — the per-chip batch is the measured throughput
                   knee, see bench_resnet50; the DDP scaling half needs
                   real multi-chip hardware): samples/sec/chip + MFU.
* ``mlp``        — MNIST MLP, B=1024 (configs[0], round-1 continuity):
                   samples/sec/chip vs the torch-CPU measurement.

Every config drives the FULL capsule stack (Launcher/Looper/Dataset/Module)
— framework overhead is part of the number. Timing syncs with
``jax.block_until_ready`` at each window boundary (dispatch is
asynchronous: a clock read without it times the enqueue). The measured
steps are split into 3 windows; ``value``/``mfu`` are the ALL-WINDOW MEAN
and ``best_value``/``best_mfu`` carry the fastest window.

Every result names the device it ran on (``platform``, ``kind``,
``count``). A config that raises, or that the time budget skipped, or a
detail probe that raises, makes the run exit non-zero — the JSON line is
still printed first. MFU needs the device kind in the peak table
(``rocket_tpu/utils/perf.py``): an unknown kind is an error, not a
silently missing field.
"""

import argparse
import json
import os
import sys
import time

import jax
import numpy as np
import optax

import rocket_tpu as rt
from rocket_tpu import optim
from rocket_tpu.data.datasets import ArrayDataset
from rocket_tpu.data.text import TokenDataset, synthetic_corpus, CharTokenizer
from rocket_tpu.models.mlp import MLP
from rocket_tpu.models.resnet import resnet18
from rocket_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    next_token_loss,
)

TORCH_CPU_MLP_BASELINE = 35768.0      # samples/sec, torch on a CPU host (BASELINE.md)


def peak_flops():
    """bf16 peak for the local device kind. A kind that is not in the
    table is an error: MFU against no peak, or the wrong one, is not a
    measurement."""
    from rocket_tpu.utils.perf import peak_flops as _peak

    peak = _peak()
    if peak is None:
        raise RuntimeError(
            f"bench: device kind {jax.devices()[0].device_kind!r} is not in "
            "rocket_tpu.utils.perf.DEVICE_SPECS — MFU cannot be computed"
        )
    return peak


def device_record() -> dict:
    """The device a result was measured on, as JAX reports it."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def cross_entropy(b):
    return optax.softmax_cross_entropy_with_integer_labels(
        b["logits"], b["label"]
    ).mean()


def _class_dataset(shape, batch, warmup, steps, num_classes=10):
    rng = np.random.default_rng(0)
    total = batch * (warmup + steps)
    return ArrayDataset(
        rng.normal(size=(total, *shape)).astype(np.float32),
        rng.integers(0, num_classes, size=total).astype(np.int32),
    )


class Timer(rt.Capsule):
    """Measures steady-state step time with true device syncs.

    Starts the clock after ``warmup`` steps (past compile), syncing on the
    module's device step counter. The measured steps are split into
    ``windows`` sub-windows with a sync only at each boundary — steps
    inside a window still pipeline — and the caller reads the mean over
    all windows and the fastest one.
    """

    def __init__(self, module, warmup: int, steps: int, windows: int = 3):
        super().__init__(priority=50)  # after all work capsules
        if warmup < 1:
            # The opening mark fires at measured == 0, i.e. on the warmup-th
            # launch; warmup=0 would silently drop the first window.
            raise ValueError("Timer needs warmup >= 1")
        self._module = module
        self._warmup = warmup
        self.window_steps = max(1, steps // max(1, windows))
        self.count = 0
        self._marks = []

    def _sync_mark(self):
        # The step counter is an output of the last dispatched step, so
        # it is ready only when that step has retired. On a directly
        # attached chip block_until_ready waits for exactly that:
        # chip_smoke.py fetches the counter right after such a wait and
        # the fetch takes <1 ms where a step takes ~60 ms (PR 21 chip
        # run) — the wait had not returned early.
        jax.block_until_ready(self._last_step)
        self._marks.append(time.perf_counter())

    def launch(self, attrs=None):
        self.count += 1
        # Keep a handle on the live device step counter: the launcher's
        # destroy pass clears the module before stop() runs.
        self._last_step = self._module.state["step"]
        if self.count == 1:
            self.n_params = sum(
                int(l.size) for l in jax.tree.leaves(self._module.state["params"])
            )
            # Expert-FFN params (leaves under an 'experts' subtree): MoE
            # FLOPs count only the top-k ACTIVE experts per token.
            self.n_expert_params = sum(
                int(leaf.size)
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                    self._module.state["params"]
                )[0]
                if any(
                    getattr(p, "key", getattr(p, "name", None)) == "experts"
                    for p in path
                )
            )
        measured = self.count - self._warmup
        if measured >= 0 and measured % self.window_steps == 0:
            self._sync_mark()

    def stop(self) -> float:
        """Total measured wall time (all complete windows)."""
        return self._marks[-1] - self._marks[0]

    def best_step_time(self) -> float:
        """Seconds/step in the fastest complete window. Marks land only on
        complete window boundaries, so every span here covers exactly
        ``window_steps`` steps."""
        spans = [
            (b - a) / self.window_steps
            for a, b in zip(self._marks, self._marks[1:])
        ]
        return min(spans)

    def mean_step_time(self) -> float:
        """Seconds/step averaged over ALL complete windows — comparable to
        single-window measurements (the round-1 baselines)."""
        return self.stop() / (self.window_steps * (len(self._marks) - 1))


def _train(capsules, runtime, timer):
    launcher = rt.Launcher(
        [rt.Looper(capsules + [timer], tag="train", progress=False)],
        num_epochs=1,
        runtime=runtime,
    )
    launcher.launch()


def bench_mlp(warmup=10, steps=60, batch=1024):
    n_dev = len(jax.devices())
    runtime = rt.Runtime(seed=0)
    data = _class_dataset((784,), batch, warmup, steps)
    model = MLP(in_features=784, num_classes=10, hidden=(512, 256))
    module = rt.Module(
        model,
        capsules=[rt.Loss(cross_entropy), rt.Optimizer(optim.sgd(), learning_rate=0.01)],
    )
    timer = Timer(module, warmup, steps)
    _train([rt.Dataset(data, batch_size=batch), module], runtime, timer)
    best_per_chip = batch / timer.best_step_time() / n_dev
    # vs_baseline rides the full-window MEAN — the torch-CPU baseline was
    # measured as a mean, so the ratio must not absorb the best-window pick.
    per_chip = batch / timer.mean_step_time() / n_dev
    return {
        "metric": "mnist_mlp_samples_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "samples/sec/chip",
        "best_value": round(best_per_chip, 1),
        "vs_baseline": round(per_chip / TORCH_CPU_MLP_BASELINE, 3),
    }


def _bench_cnn(model, shape, batch, warmup, steps, metric, gmacs_fwd,
               num_classes):
    """Shared CNN bench body. ``gmacs_fwd``: forward G-MACs per sample
    (1 MAC = 2 FLOPs, matching peak_flops' FMA hardware peak); training
    counts ~3x forward."""
    import jax.numpy as jnp

    n_dev = len(jax.devices())
    # 4 GB cache budget: the ImageNet-shape dataset for a 30-step window
    # split is ~1.3 GB — v5e HBM holds it with room to spare, and keeping
    # the device-resident path is what makes this a compute benchmark
    # (streaming would measure the host-to-device copy instead).
    runtime = rt.Runtime(seed=0, device_cache_bytes=4 << 30)
    data = _class_dataset(shape, batch, warmup, steps, num_classes=num_classes)
    module = rt.Module(
        model,
        capsules=[
            rt.Loss(cross_entropy),
            rt.Optimizer(optim.momentum(beta=0.9), learning_rate=0.1),
        ],
        compute_dtype=jnp.bfloat16,
    )
    timer = Timer(module, warmup, steps)
    _train(
        [
            rt.Dataset(
                data, batch_size=batch, drop_last=True,
                # The model computes bf16; storing the cache at compute
                # precision halves the per-step gather traffic (f32 cache
                # gather measured 4.1 ms/step vs 2.4 bf16 at B=128
                # ImageNet shapes — docs/performance.md).
                cache_dtype=jnp.bfloat16,
            ),
            module,
        ],
        runtime, timer,
    )
    best_per_chip = batch / timer.best_step_time() / n_dev
    per_chip = batch / timer.mean_step_time() / n_dev
    out = {
        "metric": metric,
        "value": round(per_chip, 1),
        "unit": "samples/sec/chip",
        "best_value": round(best_per_chip, 1),
    }
    peak = peak_flops()
    flops_per_sample = 3 * 2 * gmacs_fwd * 1e9
    out["mfu"] = round(per_chip * flops_per_sample / peak, 4)
    out["best_mfu"] = round(best_per_chip * flops_per_sample / peak, 4)
    return out


#: ResNet-18 bench batch — shared with the sched-audit calibration leg
#: so the predicted and the measured step stay the same program.
RESNET18_BATCH = 256


def bench_resnet18(warmup=5, steps=30, batch=RESNET18_BATCH):
    # CIFAR-stem ResNet-18 @32x32: ~0.557 G-MACs forward per sample.
    return _bench_cnn(
        resnet18(num_classes=10, stem="cifar"), (32, 32, 3), batch,
        warmup, steps, "cifar_resnet18_samples_per_sec_per_chip",
        gmacs_fwd=0.557, num_classes=10,
    )


def bench_resnet50(warmup=4, steps=30, batch=128):
    from rocket_tpu.models.resnet import resnet50

    # ResNet-50 @224x224: ~4.1 G-MACs forward per sample. B=128/chip is the
    # measured throughput knee (B=64: 24% MFU bare-loop, B=128: 27%,
    # B=192: 24%); BASELINE configs[3] pins the model, not the per-chip
    # batch.
    return _bench_cnn(
        resnet50(num_classes=1000), (224, 224, 3), batch,
        warmup, steps, "imagenet_resnet50_samples_per_sec_per_chip",
        gmacs_fwd=4.1, num_classes=1000,
    )


def _bench_lm(config, batch, warmup, steps, name, lr=3e-4):
    import jax.numpy as jnp

    n_dev = len(jax.devices())
    runtime = rt.Runtime(seed=0)
    seq = config.max_seq_len
    rng = np.random.default_rng(0)
    tokens = rng.integers(
        0, config.vocab_size, size=seq * (batch * (warmup + steps) + 1)
    ).astype(np.int32)
    data = TokenDataset(tokens, seq_len=seq)
    model = TransformerLM(config)
    module = rt.Module(
        model,
        capsules=[
            rt.Loss(next_token_loss()),
            rt.Optimizer(optim.adamw(), learning_rate=lr),
        ],
        compute_dtype=jnp.bfloat16,
    )
    timer = Timer(module, warmup, steps)
    moe_dropped = {}

    class MoESpy(rt.Capsule):
        """Keeps a handle on the last step's capacity-overflow fraction (a
        device scalar from step_metrics; fetched ONCE after the run —
        never mid-loop)."""

        def __init__(self):
            super().__init__(priority=40)  # after the Timer

        def launch(self, attrs=None):
            if attrs is not None and attrs.step_metrics is not None:
                v = attrs.step_metrics.moe_frac_dropped
                if v is not None:
                    moe_dropped["value"] = v

    extra_capsules = [MoESpy()] if config.num_experts > 0 else []
    _train(
        [rt.Dataset(data, batch_size=batch, drop_last=True), module]
        + extra_capsules,
        runtime, timer,
    )
    best_tok_per_chip = batch * seq / timer.best_step_time() / n_dev
    tok_per_chip = batch * seq / timer.mean_step_time() / n_dev
    # MoE: only the k routed experts' params do FLOPs per token (the
    # dispatch/combine einsum overhead is NOT counted — conservative MFU).
    active_params = timer.n_params
    if config.num_experts > 0 and timer.n_expert_params:
        active_params -= timer.n_expert_params * (
            1 - config.expert_top_k / config.num_experts
        )
    flops_per_tok = 6 * active_params + 12 * config.num_layers * seq * config.dim
    out = {
        "metric": f"{name}_tok_per_sec_per_chip",
        "value": round(tok_per_chip, 1),
        "unit": "tok/sec/chip",
        "best_value": round(best_tok_per_chip, 1),
    }
    peak = peak_flops()
    # "mfu" follows "value" (all-window mean); "best_mfu" tracks the
    # fastest window.
    out["mfu"] = round(tok_per_chip * flops_per_tok / peak, 4)
    out["best_mfu"] = round(best_tok_per_chip * flops_per_tok / peak, 4)
    if "value" in moe_dropped:
        # Capacity waste tracked round-over-round (round-4 verdict ask #3);
        # identically 0 under the dropless dispatch.
        out["frac_dropped"] = round(float(np.asarray(moe_dropped["value"])), 4)
    return out


#: charlm bench batch — shared with the sched-audit calibration leg.
CHARLM_BATCH = 128


def charlm_config():
    """The charlm bench model config, built ONCE — the sched-audit
    calibration leg predicts exactly the config this bench measures."""
    tok = CharTokenizer(synthetic_corpus(10_000))
    config = TransformerConfig.char_lm(
        vocab_size=tok.vocab_size, max_seq_len=256
    )
    config.dropout = 0.0
    return config


def bench_charlm(warmup=5, steps=40):
    return _bench_lm(charlm_config(), batch=CHARLM_BATCH, warmup=warmup,
                     steps=steps, name="charlm")


def bench_gpt2(warmup=5, steps=30):
    config = TransformerConfig.gpt2_124m()
    config.dropout = 0.0
    return _bench_lm(config, batch=8, warmup=warmup, steps=steps,
                     name="gpt2_124m")


def bench_gpt2_350m(warmup=4, steps=15):
    config = TransformerConfig.gpt2_350m()
    config.dropout = 0.0
    return _bench_lm(config, batch=8, warmup=warmup, steps=steps, name="gpt2_350m")


def bench_llama(warmup=4, steps=15):
    # Second model family: RoPE + RMSNorm + SwiGLU + GQA (124M-class dims).
    config = TransformerConfig.llama_style()
    return _bench_lm(config, batch=8, warmup=warmup, steps=steps, name="llama_style")


def bench_longctx(warmup=3, steps=12):
    """Long-context single-chip: Llama-style 124M-class at T=4096 (B=2 —
    same tokens/step as the T=1024 config). Exercises the flash kernel's
    long-sequence regime (nk=8 kv blocks, f32 dq partials); the
    sequence-PARALLEL path (ring attention over a 'seq' axis) is
    validated by dryrun_multichip — one physical chip here."""
    config = TransformerConfig.llama_style(max_seq_len=4096)
    return _bench_lm(config, batch=2, warmup=warmup, steps=steps,
                     name="llama_t4096")


def bench_moe(warmup=4, steps=15):
    """Single-chip MoE LM (GPT-2-small dims, 4 experts, top-2): routed-FFN
    throughput + MFU over ACTIVE params (round-3 verdict ask #4 — MoE was
    correctness-proven but perf-unmeasured)."""
    config = TransformerConfig.gpt2_124m()
    config.dropout = 0.0
    config.num_experts = 4
    config.expert_top_k = 2
    config.expert_capacity_factor = 1.25
    return _bench_lm(config, batch=8, warmup=warmup, steps=steps, name="moe_gpt2_e4")


def bench_pipeline(warmup=3, steps=12):
    """GPipe schedule sanity wall-clock on a VIRTUAL 4-stage CPU mesh (one
    physical chip here — this measures that the compiled M+P-1-tick
    schedule executes and stays within a sane multiple of the unpipelined
    scan on the SAME virtual mesh; it is NOT chip performance)."""
    import subprocess

    code = r"""
import json, time, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, %r)
import jax.numpy as jnp
import numpy as np
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM, next_token_loss
from rocket_tpu.runtime.context import Runtime
from rocket_tpu.parallel.sharding import pipeline_rules
import rocket_tpu as rt
from rocket_tpu import optim
from rocket_tpu.data.text import TokenDataset

runtime = Runtime(mesh_shape={"pipe": 4}, devices=jax.devices()[:4], seed=0)
config = TransformerConfig(
    vocab_size=256, max_seq_len=128, dim=128, num_layers=4, num_heads=4,
    dropout=0.0, scan_layers=True, pipeline_axis="pipe",
    pipeline_microbatches=4,
)
rng = np.random.default_rng(0)
warmup, steps = %d, %d
data = TokenDataset(rng.integers(0, 256, size=128 * (warmup + steps + 1) * 8).astype(np.int32), seq_len=128)
module = rt.Module(
    TransformerLM(config),
    capsules=[rt.Loss(next_token_loss()), rt.Optimizer(optim.adamw(), learning_rate=1e-3)],
    param_sharding=pipeline_rules(),
)
marks = []
class Timer(rt.Capsule):
    def __init__(self):
        super().__init__(priority=50)
        self.count = 0
    def launch(self, attrs=None):
        self.count += 1
        if self.count >= warmup:
            float(np.asarray(attrs.step_metrics.loss))
            marks.append(time.perf_counter())
rt.Launcher(
    [rt.Looper([rt.Dataset(data, batch_size=8, drop_last=True), module, Timer()],
               tag="train", progress=False)],
    num_epochs=1, runtime=runtime,
).launch()
dt = (marks[-1] - marks[0]) / (len(marks) - 1)
print(json.dumps({"steps_per_sec": 1.0 / dt}))
"""
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count=8", ""
        ).strip() + " --xla_force_host_platform_device_count=4"
    ).strip()
    proc = subprocess.run(
        [sys.executable, "-c", code % (repo, warmup, steps)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"pipeline sanity subprocess failed: {proc.stderr[-500:]}"
        )
    sps = json.loads(proc.stdout.strip().splitlines()[-1])["steps_per_sec"]
    return {
        "metric": "pipeline_gpipe_virtual4_steps_per_sec",
        "value": round(sps, 3),
        "unit": "steps/sec (virtual 4-stage CPU mesh sanity, not chip perf)",
    }


BENCHES = {
    "gpt2": bench_gpt2,
    "gpt2_350m": bench_gpt2_350m,
    "llama": bench_llama,
    "moe": bench_moe,
    "charlm": bench_charlm,
    "resnet18": bench_resnet18,
    "resnet50": bench_resnet50,
    "mlp": bench_mlp,
    "pipeline": bench_pipeline,
    # Last on purpose: the longest compile; the soft time budget should
    # cut this one before the others.
    "longctx": bench_longctx,
}


#: Headline metric name per config (for the record of a config that raised).
METRIC_NAMES = {
    "gpt2": "gpt2_124m_tok_per_sec_per_chip",
    "gpt2_350m": "gpt2_350m_tok_per_sec_per_chip",
    "llama": "llama_style_tok_per_sec_per_chip",
    "longctx": "llama_t4096_tok_per_sec_per_chip",
    "moe": "moe_gpt2_e4_tok_per_sec_per_chip",
    "charlm": "charlm_tok_per_sec_per_chip",
    "resnet18": "cifar_resnet18_samples_per_sec_per_chip",
    "resnet50": "imagenet_resnet50_samples_per_sec_per_chip",
    "mlp": "mnist_mlp_samples_per_sec_per_chip",
    "pipeline": "pipeline_gpipe_virtual4_steps_per_sec",
}

#: Hard cap on the emitted stdout line: a reader that keeps only the tail
#: of the output must still get the whole line. The headline is emitted
#: compact and SELF-CONTAINED; everything else goes to
#: ``BENCH_DETAIL.json`` (written at run time, git-ignored).
MAX_LINE_BYTES = 1500

DETAIL_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json"
)

VALUE_POLICY = (
    "value/mfu=all-window mean; best_value/best_mfu=best of 3 windows; "
    "vs_baseline uses means"
)


def _pick_headline(results):
    ok = {n: r for n, r in results.items() if "error" not in r}
    return ok.get("gpt2") or next(iter(ok.values()), None) \
        or next(iter(results.values()))


#: Budget-file directory the static SPMD auditor maintains
#: (``python -m rocket_tpu.analysis shard --update-budgets``).
BUDGETS_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "tests", "fixtures", "budgets",
)


def _budget_summary(budgets_dir, keys_attr, source):
    """Shared reader for the committed audit-budget records: per-target
    gated keys (named by ``keys_attr`` on the budgets module, resolved
    inside the guard) plus a worst-case (max) headline per key. None
    when no budgets are committed; never raises — BENCH emission must
    survive a missing or corrupt record. (The audits themselves run in
    CI — re-running them here would duplicate the gate, not the
    measurement.)"""
    try:
        from rocket_tpu.analysis import budgets as budgets_mod
        keys = getattr(budgets_mod, keys_attr)
        load_budget = budgets_mod.load_budget
        names = sorted(
            os.path.splitext(f)[0] for f in os.listdir(budgets_dir)
            if f.endswith(".json")
        )
        targets = {}
        for name in names:
            record = load_budget(budgets_dir, name)
            if record is None:
                continue
            targets[name] = {key: record.get(key) for key in keys}
        if not targets:
            return None
        summary = {"targets": targets, "source": source}
        for key in keys:
            summary[key] = max(t[key] or 0 for t in targets.values())
        return summary
    except Exception:  # noqa: BLE001 — emission must never die on this
        return None


def shard_audit_summary(budgets_dir=BUDGETS_DIR):
    """The audited per-device HBM estimate and per-step collective-bytes
    totals for the repo's canonical sharded configs, from the records
    the SPMD self-gate verifies every CI run."""
    return _budget_summary(
        budgets_dir, "GATED_KEYS", "tests/fixtures/budgets"
    )


#: Numerics-budget directory the precision auditor maintains
#: (``python -m rocket_tpu.analysis prec --update-budgets``).
PREC_BUDGETS_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "tests", "fixtures", "budgets", "prec",
)


def prec_audit_summary(budgets_dir=PREC_BUDGETS_DIR):
    """The audited mixed-precision numbers (fp32-bytes fraction of the
    traced step's values, widen/narrow cast counts — worst across
    targets) from the records the precision self-gate verifies every CI
    run."""
    return _budget_summary(
        budgets_dir, "PREC_GATED_KEYS", "tests/fixtures/budgets/prec"
    )


#: Schedule-budget directory the roofline auditor maintains
#: (``python -m rocket_tpu.analysis sched --update-budgets``).
SCHED_BUDGETS_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "tests", "fixtures", "budgets", "sched",
)

#: Configs the sched calibration leg re-predicts: name -> builder() ->
#: (step_fn, variables, batch, donate, units_per_step). The builders
#: derive the model config and batch from the SAME definitions the
#: bench functions measure (charlm_config/CHARLM_BATCH,
#: RESNET18_BATCH), so a bench-config edit cannot silently desync the
#: calibration. Only configs whose measured record exists in this run's
#: results are predicted (each costs one AOT compile).
def _calib_charlm():
    from rocket_tpu.analysis.shard_audit import _lm_parts

    config = charlm_config()
    step_fn, variables, batch, _rules, donate = _lm_parts(
        None, config=config, batch_size=CHARLM_BATCH
    )
    return step_fn, variables, batch, donate, \
        CHARLM_BATCH * config.max_seq_len  # tokens/step


def _calib_resnet18():
    from rocket_tpu.analysis.sched_audit import _resnet_parts

    step_fn, variables, batch, _rules, donate = _resnet_parts(
        batch_size=RESNET18_BATCH
    )
    return step_fn, variables, batch, donate, RESNET18_BATCH  # samples


_SCHED_CALIBRATION = {
    "charlm": _calib_charlm,
    "resnet18": _calib_resnet18,
}


def sched_audit_summary(results=None, budgets_dir=SCHED_BUDGETS_DIR):
    """Predicted step-time attribution + predicted-vs-measured
    calibration for BENCH_DETAIL.json.

    Two halves, both best-effort (None/partial on any failure — emission
    must never die on the audits):

    * the committed schedule-budget records (the numbers the sched
      self-gate verifies every CI run): per-target predicted step time,
      exposed-communication time, overlap fraction and the
      compute/memory/comm attribution;
    * a calibration leg re-predicting the step time of measured bench
      configs (``_SCHED_CALIBRATION``) with the same roofline model, so
      the model/reality drift is itself a tracked number.
      ``calibration_error`` is (predicted - measured) / measured;
      ``device_matched`` is False when the bench device's kind is not in
      the peak table (the prediction then prices the reference kind and
      the error mostly measures that mismatch — e.g. the CPU-only CI
      container). Known structural drift: LM configs run the pallas
      flash kernels on hardware while the fake-mesh compile takes the
      XLA attention path, so conv configs calibrate much tighter.
    """
    out = {}
    try:
        from rocket_tpu.analysis import budgets as budgets_mod

        names = sorted(
            os.path.splitext(f)[0] for f in os.listdir(budgets_dir)
            if f.endswith(".json")
        )
        targets = {}
        worst_step = worst_exposed = 0.0
        for name in names:
            record = budgets_mod.load_budget(budgets_dir, name)
            if record is None:
                continue
            targets[name] = {
                key: record.get(key)
                for key in ("predicted_step_time_us", "exposed_comm_us",
                            "overlap_fraction", "predicted_mfu",
                            "fractions", "bound")
            }
            worst_step = max(worst_step,
                             record.get("predicted_step_time_us") or 0)
            worst_exposed = max(worst_exposed,
                                record.get("exposed_comm_us") or 0)
        if targets:
            out = {
                "targets": targets,
                "predicted_step_time_us": worst_step,
                "exposed_comm_us": worst_exposed,
                "source": "tests/fixtures/budgets/sched",
            }
    except Exception:  # noqa: BLE001 — emission must never die on this
        pass
    try:
        calibration = _sched_calibration(results or {})
        if calibration:
            out["calibration"] = calibration
    except Exception as exc:  # noqa: BLE001
        log(f"bench: sched calibration failed: {exc!r}")
    return out or None


def _sched_calibration(results):
    from rocket_tpu.analysis.sched_audit import (
        DEFAULT_DEVICE_KIND,
        audit_schedule,
    )
    from rocket_tpu.utils.perf import device_spec

    kind = jax.devices()[0].device_kind
    spec = device_spec(kind)
    priced_kind = spec.kind if spec is not None else DEFAULT_DEVICE_KIND
    entries = {}
    for name, build in _SCHED_CALIBRATION.items():
        record = results.get(name) or {}
        value = record.get("value")
        if not value or "error" in record:
            continue
        step_fn, variables, batch, donate, units_per_step = build()
        report = audit_schedule(
            step_fn, variables, batch, mesh_shape={"data": 1},
            device_kind=priced_kind, donate_argnums=donate,
            label=f"calib:{name}",
        )
        predicted_us = report.record.get("predicted_step_time_us")
        if not predicted_us:
            continue
        # value is per-chip; bench configs above are single-chip runs,
        # so units/step / value is the measured step time.
        measured_us = units_per_step / value * 1e6
        entries[name] = {
            "predicted_step_time_us": predicted_us,
            "measured_step_time_us": round(measured_us, 3),
            "calibration_error": round(
                (predicted_us - measured_us) / measured_us, 4
            ),
            "predicted_mfu": report.record.get("predicted_mfu"),
            "overlap_fraction": report.record.get("overlap_fraction"),
            "priced_for": priced_kind,
            "device_matched": spec is not None,
        }
    return entries


#: Calibration-budget directory the measured-vs-predicted auditor
#: maintains (``python -m rocket_tpu.analysis calib --update-budgets``).
CALIB_BUDGETS_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "tests", "fixtures", "budgets", "calib",
)


def calib_summary(budgets_dir=CALIB_BUDGETS_DIR, live=True):
    """Measured-vs-predicted calibration record for BENCH_DETAIL.json
    (``rocket_tpu.analysis.calib`` / ``rocket_tpu.obs.prof``).

    Two halves, both best-effort:

    * the committed calibration budgets (the numbers the calib gate
      verifies every CI run): per-target absolute calibration error +
      unjoined measured fraction;
    * a ``live`` capture->parse->reconcile leg re-running the
      gpt2_sentinel target on THIS machine — a device trace of the real
      compiled step, bucketed per HLO op and joined against the priced
      DAG, so the record carries the calibration error measured on this
      run's hardware (the first real-TPU bench run turns
      ``device_matched`` True and the error becomes a model-quality
      number instead of a device-mismatch one).
    """
    out = _budget_summary(
        budgets_dir, "CALIB_GATED_KEYS", "tests/fixtures/budgets/calib"
    ) or {}
    if live:
        try:
            from rocket_tpu.analysis.calib import (
                CALIB_TARGETS,
                run_calib_target,
            )

            report = run_calib_target(CALIB_TARGETS["gpt2_sentinel"])
            if report.record:
                keys = (
                    "n_steps", "measured_step_us", "predicted_step_us",
                    "calib_error", "abs_calib_error", "join_coverage",
                    "measured_exposed_comm_us",
                    "predicted_exposed_comm_us", "measured_mfu",
                    "predicted_mfu", "device_kind_measured", "priced_for",
                    "device_matched",
                )
                out["live"] = {"gpt2_sentinel": {
                    k: report.record.get(k) for k in keys
                }}
        except Exception as exc:  # noqa: BLE001 — emission must survive
            log(f"bench: calib live capture failed: {exc!r}")
    return out or None


#: Tuned-kernel config tables the offline autotuner maintains
#: (``python -m rocket_tpu.tune --update-table``).
TUNE_CONFIGS_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "rocket_tpu", "tune", "configs",
)


def tune_summary(configs_dir=TUNE_CONFIGS_DIR):
    """Tuned-vs-default kernel config record for BENCH_DETAIL.json.

    Per tunable kernel: the checked-in table's entries — each carries
    its (device kind, shape bucket, dtype) key and the tuner-measured
    ``speedup``/``tuned_us``/``default_us`` — so tuned-vs-default
    speedup is tracked per kernel per device kind round-over-round,
    plus ``structural_wins`` (ISSUE 14): every entry whose winning
    config pins a STRUCTURAL variant (``impl``/``schedule``/
    ``epilogue``) away from the reference implementation, with the
    variant name and the measured speedup vs ``impl=reference`` — the
    generate-and-verify search's soft-spot scoreboard, carried across
    probe-less runs like the rest of the record. An empty table
    (n_entries 0) means the search found no win for that kernel yet and
    every call runs the hand-picked default. ``device_kind`` is THIS
    run's device, so the record says whether the measured throughput
    above could have hit the table at all. Best effort: None on any
    failure — emission must never die on tuning."""
    try:
        from rocket_tpu import tune

        summary = tune.tables_summary(configs_dir)
        if summary is None:
            return None
        summary["device_kind"] = jax.devices()[0].device_kind
        summary["table_device_kinds"] = sorted({
            entry.get("device_kind")
            for kernel in summary["kernels"].values()
            for entry in kernel["entries"]
            if entry.get("device_kind")
        })
        return summary
    except Exception as exc:  # noqa: BLE001 — best-effort, like the audits
        log(f"bench: tune_summary failed: {exc!r}")
        return None


def _reset_tune_provenance():
    """Best-effort: clear the tune lookup log before a config runs."""
    try:
        from rocket_tpu import tune

        tune.reset_lookup_log()
    except Exception:  # noqa: BLE001
        pass


def _tune_provenance():
    """The deduplicated kernel-config lookups the config just traced
    (table hit vs default fallback + the resolved entry key), or None."""
    try:
        from rocket_tpu import tune

        return tune.lookup_log_summary() or None
    except Exception:  # noqa: BLE001
        return None


#: Serving-budget directory the serve auditor maintains
#: (``python -m rocket_tpu.analysis serve --update-budgets``).
#: Peak-HBM budget directory the memory auditor maintains
#: (``python -m rocket_tpu.analysis mem --update-budgets``).
MEM_BUDGETS_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "tests", "fixtures", "budgets", "mem",
)


def mem_audit_summary(budgets_dir=MEM_BUDGETS_DIR):
    """The audited per-device peak-HBM prediction and saved-activation
    bytes for the repo's canonical train/eval configs, from the records
    the memory self-gate verifies every CI run."""
    return _budget_summary(
        budgets_dir, "MEM_GATED_KEYS", "tests/fixtures/budgets/mem"
    )


#: Determinism-budget directory the repro auditor maintains
#: (``python -m rocket_tpu.analysis repro --update-budgets``).
REPRO_BUDGETS_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "tests", "fixtures", "budgets", "repro",
)

#: Crash-consistency coverage-budget directory the fault auditor
#: maintains (``python -m rocket_tpu.analysis fault --update-budgets``).
FAULT_BUDGETS_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "tests", "fixtures", "budgets", "fault",
)


def fault_audit_summary(budgets_dir=FAULT_BUDGETS_DIR):
    """The audited crash-consistency coverage record — crash points
    enumerated across the three checkpoint save paths, supervisor
    states explored by the model check, signal handlers checked — from
    the records the fault self-gate verifies every CI run. Coverage
    fingerprints are identities (any drift fails CI), so this reads
    the records directly rather than riding :func:`_budget_summary`'s
    numeric-max headline."""
    try:
        from rocket_tpu.analysis import budgets as budgets_mod
        keys = budgets_mod.FAULT_GATED_KEYS
        names = sorted(
            os.path.splitext(f)[0] for f in os.listdir(budgets_dir)
            if f.endswith(".json")
        )
        targets = {}
        for name in names:
            record = budgets_mod.load_budget(budgets_dir, name)
            if record is None:
                continue
            targets[name] = {
                key: record.get(key) for key in keys
                if record.get(key) is not None
            }
        if not targets:
            return None
        return {
            "targets": targets,
            "source": "tests/fixtures/budgets/fault",
            "crash_points": max(
                t.get("crash_points") or 0 for t in targets.values()
            ),
            "states_explored": max(
                t.get("states_explored") or 0 for t in targets.values()
            ),
            "handlers_checked": max(
                t.get("handlers_checked") or 0 for t in targets.values()
            ),
        }
    except Exception:  # noqa: BLE001 — emission must never die on this
        return None


def repro_audit_summary(budgets_dir=REPRO_BUDGETS_DIR):
    """The audited determinism record per canonical target — the
    program fingerprint (identity-gated: CI fails on ANY
    drift) plus the RNG-discipline counters — from the records the
    repro self-gate verifies every CI run. Fingerprints are identities,
    not magnitudes, so this cannot ride :func:`_budget_summary` (its
    per-key numeric max would choke on the strings); the headline is
    the worst random-consumer count and the fingerprinted-target tally."""
    try:
        from rocket_tpu.analysis import budgets as budgets_mod
        keys = budgets_mod.REPRO_GATED_KEYS
        names = sorted(
            os.path.splitext(f)[0] for f in os.listdir(budgets_dir)
            if f.endswith(".json")
        )
        targets = {}
        for name in names:
            record = budgets_mod.load_budget(budgets_dir, name)
            if record is None:
                continue
            targets[name] = {key: record.get(key) for key in keys}
        if not targets:
            return None
        return {
            "targets": targets,
            "source": "tests/fixtures/budgets/repro",
            "random_consumers": max(
                t.get("random_consumers") or 0 for t in targets.values()
            ),
            "fingerprinted_targets": sum(
                1 for t in targets.values() if t.get("program_fingerprint")
            ),
        }
    except Exception:  # noqa: BLE001 — emission must never die on this
        return None


SERVE_BUDGETS_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "tests", "fixtures", "budgets", "serve",
)


def serve_audit_summary(serve=None, budgets_dir=SERVE_BUDGETS_DIR):
    """Predicted serving latency/HBM + predicted-vs-measured calibration
    for BENCH_DETAIL.json.

    Two halves, both best-effort (None/partial on any failure):

    * the committed serving-budget records (the numbers the serve
      self-gate verifies every CI run): per-target predicted ITL/TTFT,
      the analytic floor, the overfetch ratio and the engine HBM
      footprint;
    * a calibration leg re-predicting the ``charlm`` audit target —
      configured byte-identically to :func:`serve_summary`'s engine —
      priced for THIS run's device kind, against the serve record this
      run just measured. ``itl_calibration_error`` is
      (predicted - measured_p50) / measured_p50, same convention as
      sched_audit's calibration; ``device_matched`` False means the
      bench device's kind is absent from the peak table and the error
      mostly measures that mismatch (e.g. the CPU-only CI container).
    """
    out = {}
    try:
        from rocket_tpu.analysis import budgets as budgets_mod

        names = sorted(
            os.path.splitext(f)[0] for f in os.listdir(budgets_dir)
            if f.endswith(".json")
        )
        targets = {}
        worst_itl = worst_ttft = worst_hbm = 0.0
        for name in names:
            record = budgets_mod.load_budget(budgets_dir, name)
            if record is None:
                continue
            targets[name] = {
                key: record.get(key)
                for key in ("predicted_itl_us", "predicted_ttft_us",
                            "itl_floor_us", "overfetch_ratio",
                            "hbm_total_bytes", "host_bytes_per_wave",
                            "host_bytes_per_dispatch",
                            "byte_model", "waves_per_dispatch",
                            "device_kind")
            }
            worst_itl = max(worst_itl, record.get("predicted_itl_us") or 0)
            worst_ttft = max(worst_ttft,
                             record.get("predicted_ttft_us") or 0)
            worst_hbm = max(worst_hbm, record.get("hbm_total_bytes") or 0)
        if targets:
            out = {
                "targets": targets,
                "predicted_itl_us": worst_itl,
                "predicted_ttft_us": worst_ttft,
                "hbm_total_bytes": int(worst_hbm),
                "source": "tests/fixtures/budgets/serve",
            }
    except Exception:  # noqa: BLE001 — emission must never die on this
        pass
    try:
        calibration = _serve_calibration(serve)
        if calibration:
            out["calibration"] = calibration
    except Exception as exc:  # noqa: BLE001
        log(f"bench: serve calibration failed: {exc!r}")
    return out or None


def _serve_calibration(serve):
    """Re-predict the measured serve engine's ITL/TTFT with the static
    roofline, priced for this run's device kind."""
    if not serve:
        return None
    measured_itl_ms = (serve.get("itl_ms") or {}).get("p50")
    measured_ttft_ms = (serve.get("ttft_ms") or {}).get("p50")
    if not measured_itl_ms:
        return None
    from rocket_tpu.analysis.sched_audit import DEFAULT_DEVICE_KIND
    from rocket_tpu.analysis.serve_audit import (
        SERVE_TARGETS,
        audit_serving,
    )
    from rocket_tpu.utils.perf import device_spec

    kind = jax.devices()[0].device_kind
    spec = device_spec(kind)
    priced_kind = spec.kind if spec is not None else DEFAULT_DEVICE_KIND
    target = SERVE_TARGETS["charlm"]
    model, serve_cfg = target.build()
    report = audit_serving(
        model, serve_cfg, device_kind=priced_kind,
        ref_prompt_len=target.ref_prompt_len, label="calib:serve",
    )
    predicted_itl = report.record.get("predicted_itl_us")
    if not predicted_itl:
        return None
    measured_itl_us = measured_itl_ms * 1e3
    entry = {
        "predicted_itl_us": predicted_itl,
        "measured_itl_us": round(measured_itl_us, 3),
        "itl_calibration_error": round(
            (predicted_itl - measured_itl_us) / measured_itl_us, 4
        ),
        "priced_for": priced_kind,
        "device_matched": spec is not None,
    }
    predicted_ttft = report.record.get("predicted_ttft_us")
    if predicted_ttft and measured_ttft_ms:
        measured_ttft_us = measured_ttft_ms * 1e3
        entry["predicted_ttft_us"] = predicted_ttft
        entry["measured_ttft_us"] = round(measured_ttft_us, 3)
        entry["ttft_calibration_error"] = round(
            (predicted_ttft - measured_ttft_us) / measured_ttft_us, 4
        )
    return entry


#: Where a telemetry-enabled bench run's record lands: bench trees carry
#: no Tracker, so Runtime.end_training falls back to
#: <project_dir>/runs/telemetry with project_dir "." — i.e. relative to
#: the CWD bench ran from, not to this file. The repo-rooted path is the
#: second candidate for the usual run-from-repo-root case.
TELEMETRY_CANDIDATES = (
    os.path.join("runs", "telemetry", "telemetry.json"),
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "runs", "telemetry", "telemetry.json"),
)

#: Freshness fence: only a telemetry.json written by THIS process run may
#: enter BENCH_DETAIL.json — a leftover record from an earlier
#: telemetry-enabled run must not masquerade as this run's goodput.
_PROCESS_START = time.time()


def telemetry_summary(path=None):
    """Goodput + key run metrics from this run's telemetry record
    (``ROCKET_TPU_TELEMETRY=1 python bench.py ...``; successive configs
    overwrite, so this records the final config's phases). None when
    telemetry was off, the record predates this process (stale file from
    an earlier run), or it is unreadable — emission must never die on
    observability."""
    try:
        if path is None:
            path = next(
                (p for p in TELEMETRY_CANDIDATES
                 if os.path.exists(p)
                 and os.path.getmtime(p) >= _PROCESS_START),
                None,
            )
            if path is None:
                return None
        with open(path) as f:
            record = json.load(f)
        goodput = record["goodput"]
        metrics = record.get("metrics", {})
        out = {
            "goodput_fraction": goodput.get("goodput_fraction"),
            "total_wall_s": goodput.get("total_wall_s"),
            "fractions": goodput.get("fractions"),
            "source": os.path.relpath(path, os.path.dirname(DETAIL_PATH)),
        }
        gauges = metrics.get("gauges", {})
        for key in ("perf/steps_per_sec", "perf/mfu",
                    "hbm/peak_bytes_in_use_max"):
            if key in gauges:
                out[key] = gauges[key]
        stalls = record.get("watchdog", {}).get("stalls")
        if stalls:
            out["watchdog_stalls"] = stalls
        return out
    except Exception:  # noqa: BLE001 — best-effort, like the audit summaries
        return None


def health_summary(warmup=10, steps=60, batch=1024):
    """Sentinel overhead + anomaly accounting for BENCH_DETAIL.json.

    The MLP config is timed twice — health sentinels OFF, then ON with
    the gated ``skip_step`` action (the most expensive sentinel path:
    per-branch finite checks, norms, the on-device EMA and the lax.cond
    update gate, plus the lagged explicit host fetch). ``overhead_frac``
    is the steps/sec cost of turning sentinels on, best-of-3-windows on
    both sides. Telemetry stays OFF in both probes so the probe cannot
    masquerade as the main run's telemetry record. Runs on the device: a
    failure raises (``main`` records it and exits non-zero)."""
    sps = {}
    stats = None
    for mode in (False, True):
        runtime = rt.Runtime(
            seed=0, health=mode, anomaly_action="skip_step",
            telemetry=False,
        )
        data = _class_dataset((784,), batch, warmup, steps)
        model = MLP(in_features=784, num_classes=10, hidden=(512, 256))
        module = rt.Module(
            model,
            capsules=[rt.Loss(cross_entropy),
                      rt.Optimizer(optim.sgd(), learning_rate=0.01)],
        )
        timer = Timer(module, warmup, steps)
        _train([rt.Dataset(data, batch_size=batch), module], runtime, timer)
        sps[mode] = 1.0 / timer.best_step_time()
        if mode:
            stats = runtime.health.summary()
    overhead = (sps[False] - sps[True]) / sps[False]
    return {
        "steps_per_sec_baseline": round(sps[False], 2),
        "steps_per_sec_with_sentinels": round(sps[True], 2),
        "overhead_frac": round(overhead, 4),
        "action": stats["action"],
        "anomalies": stats["anomalies"],
        "skipped_steps": stats["skipped_steps"],
        "config": "mlp",
    }


#: Targets the overlap on/off probe re-audits (the TP/FSDP train
#: targets plus the TP eval step — the paths the overlapped collectives
#: rewire).
OVERLAP_PROBE_TARGETS = ("tp_1x8", "tp_2x4", "fsdp_1x8", "tp_2x4_eval")


def overlap_summary(targets=OVERLAP_PROBE_TARGETS):
    """Overlap-on/off diff of audited collective bytes + simulated
    exposed-communication time per TP/FSDP target, for
    BENCH_DETAIL.json.

    Rebuilds each audit target twice — ``ROCKET_TPU_OVERLAP=1`` (the
    ring/bulk collective-matmul + bucketed-grad paths) and ``=0`` (the
    plain GSPMD program) — and re-runs the SPMD byte audit and the
    schedule simulation on the fake mesh. Static, CPU-only: the perf
    trajectory records the communication win even on accelerator-free
    runs. Best effort (None on any failure)."""
    try:
        from rocket_tpu.analysis import sched_audit as sched_mod
        from rocket_tpu.analysis import shard_audit as shard_mod

        out = {}
        for name in targets:
            legs = {}
            for leg, env_val in (("overlap", "1"), ("baseline", "0")):
                prior = os.environ.get("ROCKET_TPU_OVERLAP")
                os.environ["ROCKET_TPU_OVERLAP"] = env_val
                try:
                    shard_rep = shard_mod.run_target(
                        shard_mod.BUILTIN_TARGETS[name]
                    )
                    sched_rep = sched_mod.run_sched_target(
                        sched_mod.SCHED_TARGETS[name]
                    )
                finally:
                    if prior is None:
                        os.environ.pop("ROCKET_TPU_OVERLAP", None)
                    else:
                        os.environ["ROCKET_TPU_OVERLAP"] = prior
                srec, crec = shard_rep.record, sched_rep.record
                legs[leg] = {
                    "collective_bytes_per_step": srec.get(
                        "collective_bytes_per_step"
                    ),
                    "n_collectives": crec.get("n_collectives"),
                    "comm_total_us": crec.get("comm_total_us"),
                    "exposed_comm_us": crec.get("exposed_comm_us"),
                    "predicted_step_time_us": crec.get(
                        "predicted_step_time_us"
                    ),
                }
            on, off = legs["overlap"], legs["baseline"]
            rec = dict(legs)
            if on["collective_bytes_per_step"] and \
                    off["collective_bytes_per_step"]:
                rec["bytes_ratio"] = round(
                    off["collective_bytes_per_step"]
                    / on["collective_bytes_per_step"], 3
                )
            if on["exposed_comm_us"] is not None and \
                    off["exposed_comm_us"]:
                rec["exposed_comm_drop_frac"] = round(
                    1.0 - on["exposed_comm_us"] / off["exposed_comm_us"], 4
                )
            out[name] = rec
        return {
            "targets": out,
            "device_kind": sched_mod.DEFAULT_DEVICE_KIND,
            "wire_dtype": os.environ.get(
                "ROCKET_TPU_OVERLAP_WIRE", "bfloat16"
            ),
        }
    except Exception:  # noqa: BLE001 — emission must never die on this
        return None


def serve_summary(requests=64, warmup_requests=8):
    """Steady-state serving throughput + latency percentiles for
    BENCH_DETAIL.json (``rocket_tpu.serve``).

    A char-LM-sized model serves a synthetic continuous-batching workload
    (mixed prompt/generation lengths, greedy) on ONE engine: a small
    warmup batch pays the two compiles, ``reset_metrics()`` zeroes the
    latency aggregates (jit caches are per-engine, so the warmup must run
    on the SAME engine), then the measured batch reflects steady-state
    serving with no compile time in the percentiles. Records tokens/sec,
    TTFT/ITL percentiles, the compiled-once counters and the pool/slot
    shape. Runs on the device: a failure raises (``main`` records it and
    exits non-zero)."""
    import numpy as np

    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.serve import ServeConfig, ServeEngine

    config = TransformerConfig(
        vocab_size=128, max_seq_len=256, dim=256, num_layers=6,
        num_heads=4, dropout=0.0, activation_dtype="bfloat16",
    )
    model = TransformerLM(config)
    params = jax.jit(model.init)(jax.random.key(0))["params"]
    # Byte-identical to the serve_audit `charlm` target (including
    # the k-wave scan) so the calibration leg compares like with
    # like: k=4 pays one dispatch and one device_get per 4 waves.
    serve_cfg = ServeConfig(
        max_slots=8, block_len=16, prefill_chunk=32, max_model_len=256,
        decode_waves_per_dispatch=4,
    )

    def run(engine, n, seed):
        rng = np.random.default_rng(seed)
        for _ in range(n):
            plen = int(rng.integers(1, 65))
            engine.submit(
                rng.integers(0, 128, size=plen).astype(np.int32),
                max_new_tokens=int(rng.integers(8, 65)),
                temperature=0.0,
            )
        engine.drain()
        return engine.report()

    engine = ServeEngine(model, params, serve_cfg)
    run(engine, warmup_requests, 1)
    engine.reset_metrics()
    report = run(engine, requests, 2)

    def _ms(block):
        return {
            k: round(v * 1e3, 3)
            for k, v in (block or {}).items() if k != "count"
        }

    dispatch = report["dispatch"]
    return {
        "config": "charlm_256",
        "requests": requests,
        "tokens_generated": report["tokens_generated"],
        "tokens_per_sec": round(report["tokens_per_sec"], 1),
        "ttft_ms": _ms(report["time_to_first_token_s"]),
        "itl_ms": _ms(report["inter_token_latency_s"]),
        "decode_traces": report["compiled"]["decode_traces"],
        "prefill_traces": report["compiled"]["prefill_traces"],
        # Dispatch amortization (k-wave scan): decoded tokens per
        # device dispatch, host syncs actually paid, and the host time
        # spent blocked on them.
        "waves_per_dispatch": dispatch["waves_per_dispatch"],
        "tokens_per_dispatch": dispatch["tokens_per_dispatch"],
        "device_get_count": dispatch["device_get_count"],
        "harvest_wait_s": dispatch["harvest_wait_s"],
        "occupancy_mean": round(report["slots"]["occupancy_mean"], 2),
        "kv_pool_mib": round(
            report["pool"]["kv_pool_bytes"] / 2**20, 1
        ),
        # Request-phase attribution (obs.reqtrace): where retained
        # requests' wall time went + ITL-gap split. The overhead
        # contract (tokens/sec with tracing on ≈ off) is gated in
        # scripts/serve_smoke.py; the bench just publishes phases.
        "phases": report["phases"],
    }


def resilience_summary(timeout_s=600):
    """Goodput under an injected worker kill, through the REAL supervised
    launcher, for BENCH_DETAIL.json (``rocket_tpu.resilience``).

    Runs the resilience smoke's kill leg as a subprocess on the CPU
    backend (the accelerator stays with the bench parent — a supervised
    child grabbing the TPU mid-bench would wedge both): a checkpointed
    MLP run whose rank 0 is SIGKILLed mid-training by the fault plan
    (``ROCKET_TPU_FAULTS=kill:step=23``); the supervisor must restart it
    from the latest checkpoint and finish. Records the supervisor.json
    headline (restarts, goodput_fraction — productive wall-clock over
    total, crashed generations credited only up to their last durable
    checkpoint). Best effort: None on any failure — emission must never
    die on the resilience probe."""
    try:
        import subprocess
        import tempfile

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # TPU/XLA flags from the bench parent don't apply to cpu children.
        env.pop("XLA_FLAGS", None)
        smoke = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "scripts", "resilience_smoke.py",
        )
        with tempfile.TemporaryDirectory(prefix="bench_resilience_") as tmp:
            out_path = os.path.join(tmp, "resilience.json")
            proc = subprocess.run(
                [sys.executable, smoke,
                 "--leg", "kill", "--json-out", out_path],
                env=env, capture_output=True, text=True, timeout=timeout_s,
            )
            if proc.returncode != 0:
                log("bench: resilience probe failed: "
                    f"{(proc.stderr or proc.stdout)[-300:]}")
                return None
            with open(out_path) as f:
                return json.load(f)
    except Exception as exc:  # noqa: BLE001 — best-effort, like the audits
        log(f"bench: resilience_summary failed: {exc!r}")
        return None


def _carry_calibration(section, prior_section):
    """Merge a committed audit section's calibration entries under the
    freshly-computed ones. A partial bench run only re-predicts the
    configs it measured; the entries it could not recompute must survive
    from the committed record or tracked model/reality drift silently
    vanishes on every ``--config`` debug run."""
    prior_cal = (prior_section or {}).get("calibration")
    if not isinstance(prior_cal, dict) or not prior_cal:
        return
    fresh = section.get("calibration")
    if not isinstance(fresh, dict) or not fresh:
        # Nothing recomputed this run — carry the committed block whole.
        section["calibration"] = prior_cal
        return
    # Per-config entries (sched: name -> entry dict) merge; a flat
    # single-entry block (serve) was fully recomputed, so fresh wins.
    for key, val in prior_cal.items():
        if isinstance(val, dict) and key not in fresh:
            fresh[key] = val


def write_detail(results, path=DETAIL_PATH, health=None, serve=None,
                 resilience=None, overlap=None, calib=None):
    """Full per-config results → a committed repo file. The stdout line
    (``format_line``) carries only the headline + one number per config;
    this file is the complete record it points at.

    MERGES into an existing file rather than overwriting: a single-config
    debugging run (``--config gpt2``) must not clobber the committed
    full-sweep record the stdout ``detail`` pointer references — neither
    its per-config records nor the audit calibration entries, which a
    partial run cannot recompute (each needs that config's measured
    value from THIS run). Best effort only — the caller guards it so a
    filesystem failure can never eat the stdout line."""
    configs = {}
    prior = {}
    try:
        with open(path) as f:
            prior = json.load(f)
        configs = {k: v for k, v in prior["configs"].items()
                   if isinstance(v, dict)}
    except Exception:  # noqa: BLE001 — any malformed prior starts fresh
        prior = {}
    for name, r in results.items():
        if "error" in r and "error" not in configs.get(name, {"error": 1}):
            # An errored re-run (debugging OOM, transient XLA failure) must
            # not destroy a committed good record — annotate it instead.
            configs[name] = dict(configs[name],
                                 last_error=str(r["error"])[:200])
        else:
            configs[name] = r
    detail = {
        # Headline from the MERGED set: a --config mlp debug run must not
        # repoint the full-sweep record's headline away from gpt2.
        "headline_metric": _pick_headline(configs).get("metric"),
        "value_policy": VALUE_POLICY,
        "configs": configs,
    }
    audit = shard_audit_summary(BUDGETS_DIR)
    if audit is not None:
        # Statically-audited SPMD cost alongside the measured throughput:
        # per-device HBM estimate + per-step collective bytes per target.
        detail["shard_audit"] = audit
    prec = prec_audit_summary(PREC_BUDGETS_DIR)
    if prec is not None:
        # Statically-audited numerics next to the measured throughput:
        # fp32-bytes fraction of the traced step + cast counts per target.
        detail["prec_audit"] = prec
    tune_rec = tune_summary(TUNE_CONFIGS_DIR)
    if tune_rec is not None:
        # Tuned-kernel config tables (rocket_tpu.tune) next to the
        # throughput they shape: per-kernel entries with the tuner's
        # measured tuned-vs-default speedup per device kind, plus this
        # run's device kind so table applicability is explicit.
        detail["tune"] = tune_rec
    sched = sched_audit_summary(results, SCHED_BUDGETS_DIR)
    if sched is not None:
        # Predicted step-time attribution (compute/memory/exposed-comm)
        # per audited target + predicted-vs-measured calibration for the
        # configs this run measured — model/reality drift is tracked.
        _carry_calibration(sched, prior.get("sched_audit"))
        detail["sched_audit"] = sched
    telemetry = telemetry_summary()
    if telemetry is not None:
        # Live-run goodput split (rocket_tpu.obs) from a telemetry-enabled
        # bench run: measured compile/data-wait/step fractions next to the
        # throughput they explain.
        detail["telemetry"] = telemetry
    if health is not None:
        # Measured health-sentinel overhead (obs.health): steps/sec with
        # the in-step sentinels + lax.cond gate on vs off, plus the
        # probe's anomaly/skip accounting. Target: overhead_frac < 0.02.
        detail["health_sentinels"] = health
    if serve is not None:
        # Steady-state serving metrics (rocket_tpu.serve): continuous-
        # batching tokens/sec + TTFT/ITL percentiles on the char-LM-sized
        # model, with the compiled-once trace counters alongside.
        detail["serve"] = serve
    if resilience is not None:
        # Measured fault tolerance (rocket_tpu.resilience): the supervised
        # launcher surviving one injected SIGKILL — restart count and
        # goodput_fraction (productive/total wall-clock, crashed
        # generations credited to their last durable checkpoint).
        # Target: goodput_fraction >= 0.5 under a single mid-run kill.
        detail["resilience"] = resilience
    if overlap is None:
        # A probe-less (budget-blown or partial) run must not drop the
        # committed on/off record — carry it like the calibrations.
        overlap = prior.get("overlap")
    if overlap is not None:
        # Overlap-on/off diff of the statically audited communication
        # (collective bytes, simulated exposed-comm time) per TP/FSDP
        # target — the comm/compute-overlap win recorded even on
        # CPU-only runs.
        detail["overlap"] = overlap
    if calib is None:
        # A probe-less run keeps the committed measured-vs-predicted
        # record (the live leg needs a capture from THIS run).
        calib = prior.get("calib")
    if calib is not None:
        # Measured-vs-predicted calibration (obs.prof + analysis.calib):
        # per-target |calibration error| + unjoined fraction from the
        # committed budgets, plus a live capture->parse->reconcile leg
        # of the gpt2 sentinel step on this run's hardware.
        detail["calib"] = calib
    serve_audit = serve_audit_summary(serve, SERVE_BUDGETS_DIR)
    if serve_audit is not None:
        # Statically-predicted serving latency/HBM (serve_audit budgets)
        # next to the measured serving record, plus the predicted-vs-
        # measured ITL/TTFT calibration — model/reality drift is tracked.
        _carry_calibration(serve_audit, prior.get("serve_audit"))
        detail["serve_audit"] = serve_audit
    mem = mem_audit_summary(MEM_BUDGETS_DIR)
    if mem is not None:
        # Statically-predicted peak HBM + saved-for-backward bytes per
        # train target (mem_audit budgets) — the liveness simulation's
        # numbers the memory self-gate verifies every CI run.
        detail["mem"] = mem
    repro = repro_audit_summary(REPRO_BUDGETS_DIR)
    if repro is not None:
        # The determinism audit's committed identities (program
        # fingerprints, exact-equality gated in CI) + RNG-discipline
        # counters — the reproducibility claim the bench numbers rest on.
        detail["repro"] = repro
    fault = fault_audit_summary(FAULT_BUDGETS_DIR)
    if fault is not None:
        # The crash-consistency audit's committed coverage (crash
        # points enumerated, supervisor states explored, handlers
        # checked — drift-gated in CI): the resume-from-any-crash claim
        # the goodput numbers rest on.
        detail["fault"] = fault
    # Atomic replace: a driver timeout mid-dump must not truncate the
    # accumulated record (the corrupt-prior recovery above would then
    # silently discard it on the next run).
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(detail, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def format_line(results, detail_path="BENCH_DETAIL.json"):
    """The single stdout JSON line: compact headline + per-config value
    summary + the device it all ran on. Guaranteed ≤ MAX_LINE_BYTES —
    degrades by dropping summary fields (never headline fields) and
    asserts the invariant, so adding bench configs can never silently
    overflow a tail capture."""
    headline = _pick_headline(results)
    keep = ("metric", "value", "unit", "vs_baseline", "mfu",
            "best_value", "best_mfu", "error", "device")
    line = {k: headline[k] for k in keep if k in headline}
    if isinstance(line.get("error"), str):
        # str(exc) from an XLA failure routinely runs kilobytes; the line
        # must fit the capture even when every config errors.
        line["error"] = line["error"][:400]
    line["value_policy"] = VALUE_POLICY
    others = {}
    for name, r in results.items():
        if r is headline:
            continue
        if "error" in r:
            others[name] = "ERR"
        else:
            v = r.get("value")
            others[name] = round(v, 1) if isinstance(v, (int, float)) else "?"
            if isinstance(r.get("mfu"), (int, float)):
                others[name + "_mfu"] = round(r["mfu"], 3)
    line["others"] = others
    line["detail"] = detail_path

    def dumps(d):
        return json.dumps(d, separators=(",", ":"))

    s = dumps(line)
    if len(s) > MAX_LINE_BYTES:  # drop per-config mfu summaries first
        line["others"] = {n: v for n, v in others.items()
                          if not n.endswith("_mfu")}
        s = dumps(line)
    if len(s) > MAX_LINE_BYTES:  # then the summary entirely
        line.pop("others")
        s = dumps(line)
    if len(s) > MAX_LINE_BYTES:  # last resort: shrink the error text
        line["error"] = line.get("error", "")[:100]
        s = dumps(line)
    assert len(s) <= MAX_LINE_BYTES, (len(s), s[:200])
    return s


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--config", default="all", choices=["all", *BENCHES.keys()]
    )
    parser.add_argument(
        "--budget-s", type=float, default=None,
        help="soft wall-clock budget: once exceeded, remaining configs are "
             "skipped (and the run exits non-zero) so the JSON line always "
             "reaches stdout (default: $ROCKET_BENCH_BUDGET_S or 1200)",
    )
    args = parser.parse_args()
    if args.budget_s is None:
        try:
            args.budget_s = float(os.environ.get("ROCKET_BENCH_BUDGET_S", 1200))
        except ValueError:
            log("bench: bad ROCKET_BENCH_BUDGET_S — using 1200s")
            args.budget_s = 1200.0

    # A missing or broken backend raises right here.
    device = device_record()
    names = list(BENCHES) if args.config == "all" else [args.config]
    results = {}
    #: What was asked for and did not produce its number: the exit code.
    failed = []
    start = time.time()
    for name in names:
        elapsed = time.time() - start
        if elapsed > args.budget_s:
            # Over budget: stop starting configs — a JSON line that says
            # what was skipped beats being killed by an outer timeout with
            # NOTHING on stdout. (A fast early failure never trips this:
            # elapsed must exceed the budget.)
            log(f"bench: {name} skipped (elapsed {elapsed:.0f}s > "
                f"budget {args.budget_s:.0f}s)")
            results[name] = {
                "metric": METRIC_NAMES[name], "error": "skipped: time budget"
            }
            failed.append(name)
            continue
        log(f"bench: {name} ...")
        t0 = time.time()
        try:
            _reset_tune_provenance()
            results[name] = BENCHES[name]()
            prov = _tune_provenance()
            if prov is not None:
                # Which kernel configs this config actually resolved
                # (table hit vs default fallback, with the entry key).
                results[name]["kernel_configs"] = prov
            log(f"bench: {name} -> {results[name]} ({time.time()-t0:.0f}s)")
        except Exception as exc:  # noqa: BLE001 — record, keep benching
            log(f"bench: {name} FAILED: {exc!r}")
            results[name] = {"metric": METRIC_NAMES[name], "error": str(exc)}
            failed.append(name)
        results[name]["device"] = device

    def probe(label, fn):
        """One detail probe, run after the configs so that it never eats
        their time: skipped (and said so) once the budget is spent; one
        that raises is a failure of the run like a config's."""
        if time.time() - start > args.budget_s:
            log(f"bench: {label} probe skipped (time budget)")
            return None
        log(f"bench: {label} probe ...")
        try:
            out = fn()
        except Exception as exc:  # noqa: BLE001 — record, keep probing
            log(f"bench: {label} probe FAILED: {exc!r}")
            failed.append(f"probe:{label}")
            return None
        if out is not None:
            log(f"bench: {label} -> {out}")
        return out

    # Sentinel overhead (paired MLP run) and serving throughput/latency
    # run on the device; the supervised-restart goodput probe runs CPU
    # subprocesses; the overlap on/off comm probe is fake-mesh compiles;
    # the calibration probe captures the gpt2 sentinel step (obs.prof).
    health = probe("health", health_summary)
    serve = probe("serve", serve_summary)
    resilience = probe("resilience", resilience_summary)
    overlap = probe("overlap", overlap_summary)
    calib = probe("calib", calib_summary)

    # The stdout line is the hard contract and goes out FIRST — a kill or
    # hang during the detail write must not eat it. It still ends up last
    # in a tail capture because nothing else prints to stdout after it.
    print(format_line(results), flush=True)
    try:
        write_detail(results, health=health, serve=serve,
                     resilience=resilience, overlap=overlap, calib=calib)
    except Exception as exc:  # noqa: BLE001 — say so, fail the run
        log(f"bench: could not write {DETAIL_PATH}: {exc!r}")
        failed.append("detail")
    if failed:
        log(f"bench: FAILED: {', '.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
