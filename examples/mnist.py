"""MNIST with the canonical capsule tree.

The TPU-native analogue of the reference's example (``examples/mnist.py:76-107``)
— same composition: LeNet, whole-batch cross-entropy objective, AdamW +
StepLR, gradient accumulation 2, train/val loopers, Meter/Accuracy,
Checkpointer, Tracker — with the reference's bugs fixed (its version never
calls ``launch()`` and crashes on an unimported name; SURVEY §2a Example row).

Run: ``python examples/mnist.py`` (uses real MNIST if cached under ./data,
synthetic otherwise).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import optax

import rocket_tpu as rt
from rocket_tpu import optim
from rocket_tpu.data.datasets import mnist
from rocket_tpu.models.lenet import LeNet
from rocket_tpu.utils.metrics import Accuracy


def cross_entropy(batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        batch["logits"], batch["label"]
    ).mean()


def main(num_epochs: int = 3, batch_size: int = 1024):
    runtime = rt.Runtime(seed=0, gradient_accumulation_steps=2)

    model = LeNet(num_classes=10)
    train_data = mnist(train=True)
    val_data = mnist(train=False)
    accuracy = Accuracy()

    launcher = rt.Launcher(
        [
            rt.Looper(
                [
                    rt.Dataset(train_data, batch_size=batch_size, shuffle=True),
                    rt.Module(
                        model,
                        capsules=[
                            rt.Loss(cross_entropy),
                            rt.Optimizer(optim.adamw(weight_decay=0.01)),
                            rt.Scheduler(optim.step_lr(1e-3, step_size=100, gamma=0.5)),
                        ],
                    ),
                    rt.Checkpointer(output_dir="checkpoints/mnist", save_every=50),
                    rt.Tracker(backend="jsonl", project="mnist"),
                ],
                tag="train",
            ),
            rt.Looper(
                [
                    rt.Dataset(val_data, batch_size=batch_size),
                    rt.Module(model),
                    rt.Meter(["logits", "label"], [accuracy]),
                    rt.Tracker(backend="jsonl", project="mnist"),
                ],
                tag="val",
                grad_enabled=False,
            ),
        ],
        num_epochs=num_epochs,
        statefull=True,
        runtime=runtime,
    )
    print(launcher)
    launcher.launch()
    print(f"val accuracy: {accuracy.value:.4f}")
    return accuracy.value


if __name__ == "__main__":
    main()
