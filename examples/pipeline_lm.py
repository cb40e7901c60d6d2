"""Pipeline-parallel LM training — GPipe or 1F1B over a 'pipe' mesh axis.

The transformer's stacked layers (``scan_layers=True``) are sharded per
stage over the 'pipe' axis; microbatches ``ppermute`` between stages
inside one compiled program (``parallel/pipeline.py``). Two schedules:

* ``--schedule gpipe`` (default): forward pipeline differentiated by
  autodiff — simple, but per-stage live activations grow with the
  microbatch count;
* ``--schedule 1f1b``: loss and backward run INSIDE the pipelined
  program (one-forward-one-backward interleave) — per-stage live
  activations are O(stages), the standard at real pipeline depth.

On real hardware you would run e.g. ``--pipe-devices 4`` on a v4-8 slice;
the defaults run anywhere, including the virtual CPU mesh:
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
python examples/pipeline_lm.py --schedule 1f1b``.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

import rocket_tpu as rt
from rocket_tpu import optim
from rocket_tpu.data.text import CharTokenizer, TokenDataset, synthetic_corpus
from rocket_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    next_token_loss,
)
from rocket_tpu.parallel.sharding import pipeline_rules


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--schedule", choices=["gpipe", "1f1b"],
                        default="gpipe")
    parser.add_argument("--pipe-devices", type=int, default=None,
                        help="pipeline stages (default: half the devices)")
    parser.add_argument("--microbatches", type=int, default=4)
    parser.add_argument("--epochs", type=int, default=2)
    args = parser.parse_args()

    n = len(jax.devices())
    pipe = args.pipe_devices or max(2, n // 2)
    if n % pipe or pipe < 2:
        raise SystemExit(
            f"--pipe-devices {pipe} must be >= 2 and divide the {n} "
            "available devices; on one chip run under a virtual CPU mesh "
            "— see the module docstring."
        )
    data_par = n // pipe
    runtime = rt.Runtime(mesh_shape={"data": data_par, "pipe": pipe}, seed=0)

    corpus = synthetic_corpus(num_chars=60_000)
    tok = CharTokenizer(corpus)
    seq_len = 64
    data = TokenDataset(tok.encode(corpus), seq_len=seq_len)

    config = TransformerConfig(
        vocab_size=tok.vocab_size, max_seq_len=seq_len, dim=64,
        num_layers=2 * pipe, num_heads=4, dropout=0.0,
        scan_layers=True, pipeline_axis="pipe",
        pipeline_microbatches=args.microbatches,
        pipeline_schedule=args.schedule,
        loss_chunk=32,
    )
    module = rt.Module(
        TransformerLM(config),
        capsules=[
            rt.Loss(next_token_loss()),
            rt.Optimizer(optim.adamw(), learning_rate=3e-3),
        ],
        param_sharding=pipeline_rules(),
    )

    losses = []

    class Spy(rt.Capsule):
        def __init__(self):
            super().__init__(priority=500)

        def launch(self, attrs=None):
            if attrs.looper.state.loss is not None:
                # Device scalar — converted to host floats ONCE after the
                # run (a float() here would block the pipeline every step).
                losses.append(attrs.looper.state.loss)

    batch_size = 8 * data_par * args.microbatches
    if batch_size > len(data):
        raise SystemExit(
            f"batch size {batch_size} exceeds the {len(data)}-sequence "
            "dataset; lower --microbatches."
        )
    rt.Launcher(
        [rt.Looper(
            [rt.Dataset(data, batch_size=batch_size,
                        drop_last=True, shuffle=True),
             module, Spy()],
            tag="train", progress=False,
        )],
        num_epochs=args.epochs,
        runtime=runtime,
    ).launch()
    first, last = float(np.asarray(losses[0])), float(np.asarray(losses[-1]))
    print(f"{args.schedule} over {pipe} stages x {data_par} data shards: "
          f"loss {first:.3f} -> {last:.3f} ({len(losses)} steps)")
    assert last < first, "loss did not improve"


if __name__ == "__main__":
    main()
