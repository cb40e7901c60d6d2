"""CIFAR-10 Vision Transformer — the third transformer family.

Non-causal encoder over patches (``rocket_tpu.models.vit``): same capsule
tree shape as ``cifar_resnet.py`` (train looper with on-device
augmentation + eval looper with gathered accuracy), AdamW + warmup-cosine,
bf16 compute. Real CIFAR-10 when cached under ./data, synthetic separable
data otherwise.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax.numpy as jnp

import rocket_tpu as rt
from rocket_tpu import optim
from rocket_tpu.data.augment import image_augment
from rocket_tpu.models.vit import vit_tiny
from rocket_tpu.utils.metrics import Accuracy

from cifar_resnet import cifar10, cross_entropy  # shared data + objective


def main(num_epochs: int = 5, batch_size: int = 512):
    runtime = rt.Runtime(seed=0)
    model = vit_tiny(image_size=32, patch_size=4, num_classes=10, dropout=0.1)
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
                            rt.Optimizer(optim.adamw(), clip_norm=1.0),
                            rt.Scheduler(optim.warmup_cosine_lr(
                                3e-3, warmup_steps=max(1, steps // 20),
                                decay_steps=steps,
                            )),
                        ],
                        compute_dtype=jnp.bfloat16,
                        batch_transform=image_augment(crop_padding=4, flip=True),
                    ),
                    rt.Checkpointer(output_dir="checkpoints/vit_cifar",
                                    save_every=200, keep_last=2),
                    rt.Tracker(backend="jsonl", project="vit_cifar"),
                ],
                tag="train",
            ),
            rt.Looper(
                [
                    rt.Dataset(cifar10(train=False), batch_size=batch_size),
                    rt.Module(model, compute_dtype=jnp.bfloat16),
                    rt.Meter(["logits", "label"], [accuracy]),
                    rt.Tracker(backend="jsonl", project="vit_cifar"),
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
