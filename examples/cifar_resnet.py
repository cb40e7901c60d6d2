"""CIFAR-10 ResNet-18 (BASELINE.json configs[1]).

Real CIFAR-10 when cached under ./data (torchvision layout), synthetic
separable image data otherwise. SGD momentum + cosine decay, data-parallel
over all local devices, eval with gathered accuracy.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import optax

import rocket_tpu as rt
from rocket_tpu import optim
from rocket_tpu.data.datasets import ArrayDataset
from rocket_tpu.data.augment import image_augment
from rocket_tpu.models.resnet import resnet18
from rocket_tpu.utils.metrics import Accuracy


def cifar10(train=True):
    try:
        from torchvision.datasets import CIFAR10

        tv = CIFAR10(root=os.environ.get("CIFAR_ROOT", "data"), train=train, download=False)
        images = tv.data.astype(np.float32) / 255.0  # (N, 32, 32, 3) NHWC already
        mean = np.asarray([0.4914, 0.4822, 0.4465], np.float32)
        std = np.asarray([0.247, 0.243, 0.261], np.float32)
        images = (images - mean) / std
        labels = np.asarray(tv.targets, np.int32)
        return ArrayDataset(images, labels)
    except Exception:
        rng = np.random.default_rng(0 if train else 1)
        n = 50_000 if train else 10_000
        labels = rng.integers(0, 10, size=n).astype(np.int32)
        templates = np.random.default_rng(7).normal(size=(10, 32, 32, 3)).astype(np.float32)
        images = templates[labels] + rng.normal(size=(n, 32, 32, 3)).astype(np.float32) * 0.6
        return ArrayDataset(images, labels)


def cross_entropy(batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        batch["logits"], batch["label"]
    ).mean()


def main(num_epochs: int = 5, batch_size: int = 512):
    runtime = rt.Runtime(seed=0)
    model = resnet18(num_classes=10, stem="cifar")
    accuracy = Accuracy()

    train_data = cifar10(train=True)
    steps = max(1, len(train_data) // batch_size * num_epochs)

    launcher = rt.Launcher(
        [
            rt.Looper(
                [
                    rt.Dataset(train_data, batch_size=batch_size, shuffle=True,
                               drop_last=True),
                    rt.Module(
                        model,
                        capsules=[
                            rt.Loss(cross_entropy),
                            rt.Optimizer(optim.momentum(beta=0.9)),
                            rt.Scheduler(optim.cosine_lr(0.2, decay_steps=steps)),
                        ],
                        # On-device augmentation: the host ships raw samples
                        # once (device-cached); each step crops+flips with
                        # its own PRNG fold inside the compiled step.
                        batch_transform=image_augment(crop_padding=4, flip=True),
                    ),
                    rt.Checkpointer(output_dir="checkpoints/cifar", save_every=200,
                                    keep_last=2),
                    rt.Tracker(backend="jsonl", project="cifar_resnet18"),
                ],
                tag="train",
            ),
            rt.Looper(
                [
                    rt.Dataset(cifar10(train=False), batch_size=batch_size),
                    rt.Module(model),
                    rt.Meter(["logits", "label"], [accuracy]),
                    rt.Tracker(backend="jsonl", project="cifar_resnet18"),
                ],
                tag="val",
                grad_enabled=False,
            ),
        ],
        num_epochs=num_epochs,
        statefull=True,
        runtime=runtime,
    )
    launcher.launch()
    print(f"val accuracy: {accuracy.value:.4f}")


if __name__ == "__main__":
    main()
