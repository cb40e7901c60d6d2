"""Core layers: dense, conv (NHWC), norms, pooling, embedding, dropout.

All convolutional layers use **NHWC** layout with **HWIO** kernels — the
native TPU layout (channels on the 128-wide lane dimension feeds the MXU
without transposes). Matmul-heavy layers default their compute to the caller's
dtype; params are stored in float32 and cast at use (master-weight mixed
precision when the activations are bfloat16).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from rocket_tpu.nn.module import Layer, Lambda

__all__ = [
    "Dense",
    "Conv2D",
    "MaxPool2D",
    "AvgPool2D",
    "GlobalAvgPool2D",
    "BatchNorm",
    "bn_act_train",
    "LayerNorm",
    "gated_rms_norm",
    "Embedding",
    "Dropout",
    "Flatten",
    "relu",
    "gelu",
    "tanh",
    "silu",
    "softmax",
]


def _pair(v: Union[int, Sequence[int]]) -> tuple[int, int]:
    return (v, v) if isinstance(v, int) else (v[0], v[1])


class Dense(Layer):
    """``tp_role`` opts a layer into the overlapped collective-matmul
    path (``parallel/collectives.py``) when a TP-overlap context is
    active: ``"column"`` (kernel output-dim sharded — the layer gathers
    its sequence-sharded input into the matmul), ``"row"`` (kernel
    input-dim sharded — the layer reduce-scatters its output onto the
    sequence shards). The role only ACTS under an active context with
    compatible shapes; otherwise the layer is the plain matmul. The
    transformer Block/attention wire their projections through the
    grouped primitives directly (one shared gather for fused QKV /
    swiglu), so their Dense sublayers keep ``tp_role=None``."""

    def __init__(
        self,
        in_features: int,
        out_features: int,
        use_bias: bool = True,
        kernel_init: Callable = jax.nn.initializers.lecun_normal(),
        tp_role: Optional[str] = None,
    ):
        if tp_role not in (None, "column", "row"):
            raise ValueError(
                f"Dense: tp_role must be None|'column'|'row', got {tp_role!r}"
            )
        self.in_features = in_features
        self.out_features = out_features
        self.use_bias = use_bias
        self.kernel_init = kernel_init
        self.tp_role = tp_role

    def init_params(self, key):
        params = {
            "w": self.kernel_init(key, (self.in_features, self.out_features), jnp.float32)
        }
        if self.use_bias:
            params["b"] = jnp.zeros((self.out_features,), jnp.float32)
        return params

    def _tp_spec(self, x):
        """The active overlap spec when this layer's role can engage on
        ``x`` — (B, T, F) activations whose sequence and the sharded
        kernel dim both divide the TP axis."""
        if self.tp_role is None or x.ndim != 3:
            return None
        from rocket_tpu.parallel import collectives as coll

        spec = coll.current_tp()
        if spec is None:
            return None
        n = spec.tp_size
        sharded_dim = (
            self.out_features if self.tp_role == "column" else self.in_features
        )
        if x.shape[1] % n or sharded_dim % n:
            return None
        return spec

    def apply(self, variables, x, *, mode="train", rng=None):
        p = variables["params"]
        w = p["w"].astype(x.dtype)
        spec = self._tp_spec(x)
        if spec is not None:
            from rocket_tpu.parallel import collectives as coll

            if self.tp_role == "column":
                (y,) = coll.all_gather_matmul(spec, x, (w,))
            else:
                y = coll.matmul_reduce_scatter(spec, x, w)
        else:
            y = x @ w
        if self.use_bias:
            y = y + p["b"].astype(x.dtype)
        return y, variables["state"]

    def __repr__(self):
        return f"Dense({self.in_features}->{self.out_features})"


class Conv2D(Layer):
    """NHWC convolution with HWIO kernel (TPU-native layout)."""

    def __init__(
        self,
        in_channels: int,
        out_channels: int,
        kernel_size: Union[int, Sequence[int]] = 3,
        stride: Union[int, Sequence[int]] = 1,
        padding: Union[str, int] = "SAME",
        use_bias: bool = True,
        kernel_init: Callable = jax.nn.initializers.he_normal(),
    ):
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride)
        if isinstance(padding, int):
            padding = [(padding, padding), (padding, padding)]
        self.padding = padding
        self.use_bias = use_bias
        self.kernel_init = kernel_init

    def init_params(self, key):
        kh, kw = self.kernel_size
        shape = (kh, kw, self.in_channels, self.out_channels)
        params = {"w": self.kernel_init(key, shape, jnp.float32)}
        if self.use_bias:
            params["b"] = jnp.zeros((self.out_channels,), jnp.float32)
        return params

    def apply(self, variables, x, *, mode="train", rng=None):
        p = variables["params"]
        y = jax.lax.conv_general_dilated(
            x,
            p["w"].astype(x.dtype),
            window_strides=self.stride,
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            y = y + p["b"].astype(x.dtype)
        return y, variables["state"]

    def __repr__(self):
        return (
            f"Conv2D({self.in_channels}->{self.out_channels}, "
            f"k={self.kernel_size}, s={self.stride})"
        )


class _Pool2D(Layer):
    def __init__(self, window, stride=None, padding="VALID"):
        self.window = _pair(window)
        self.stride = _pair(stride if stride is not None else window)
        self.padding = padding

    def _reduce(self, x, init, op):
        return jax.lax.reduce_window(
            x,
            init,
            op,
            window_dimensions=(1, *self.window, 1),
            window_strides=(1, *self.stride, 1),
            padding=self.padding,
        )


class MaxPool2D(_Pool2D):
    def apply(self, variables, x, *, mode="train", rng=None):
        # init must be a Python scalar: reduce_window's autodiff rule pattern
        # -matches the (max, -inf) monoid and a traced init breaks it.
        return self._reduce(x, -jnp.inf, jax.lax.max), variables["state"]


class AvgPool2D(_Pool2D):
    def apply(self, variables, x, *, mode="train", rng=None):
        summed = self._reduce(x, 0.0, jax.lax.add)
        denom = self.window[0] * self.window[1]
        return (summed / denom).astype(x.dtype), variables["state"]


class GlobalAvgPool2D(Layer):
    def apply(self, variables, x, *, mode="train", rng=None):
        return jnp.mean(x, axis=(1, 2)), variables["state"]


def _bn_train_impl(x, scale, bias, eps, moments=None):
    axes = tuple(range(x.ndim - 1))
    xf = x.astype(jnp.float32)
    # One-pass statistics: var = E[x^2] - E[x]^2 lets XLA compute both
    # reductions in a single read of the activation, where mean + jnp.var
    # costs two (chip A/B on ResNet-50 @224 B=128: 27.0 -> 29.1% MFU). f32
    # accumulation over bf16 activations keeps the cancellation error
    # negligible at BN's post-conv activation scales; the max() guards the
    # tiny negative residue cancellation can leave.
    #
    # Both moments reduce as ONE stacked (C, 2) reduction: under a
    # data-sharded batch GSPMD then inserts a single cross-replica
    # all-reduce of the (C, 2) stats where separate mean/E[x^2] reductions
    # cost two ~1us-latency collectives per BN layer per pass — sched_audit
    # RKT501/RKT502 flagged the pairs on the dp_resnet_1x8 target (105
    # tiny all-reduces/step).
    #
    # The moment form is tunable (tune kernel "fused_bn": "stacked" is
    # the measured default; "separate" keeps the two reductions XLA can
    # sometimes fuse differently on single-device conv stacks) — both
    # compute the same two means, so outputs are parity-equal.
    if moments is None:
        from rocket_tpu.tune import get_config

        config = get_config(
            "fused_bn", shape={"c": x.shape[-1]}, dtype=x.dtype
        )
        moments = (config or {}).get("moments", "stacked")
    if moments == "separate":
        stats = jnp.stack(
            [jnp.mean(xf, axis=axes), jnp.mean(jnp.square(xf), axis=axes)],
            axis=-1,
        )
    else:
        stats = jnp.mean(jnp.stack([xf, jnp.square(xf)], axis=-1), axis=axes)
    mean = stats[..., 0]
    var = jnp.maximum(stats[..., 1] - jnp.square(mean), 0.0)
    inv = jax.lax.rsqrt(var + eps)
    y = ((xf - mean) * (inv * scale) + bias).astype(x.dtype)
    return y, stats, mean, inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(x, scale, bias, eps, moments=None):
    """Train-mode batchnorm with a FUSED backward: autodiff of the stacked
    forward still emits three per-channel reductions in the backward
    (d_bias, d_scale and the dmean/dvar chain) — three ~1us cross-replica
    all-reduces per BN layer per step under data sharding. The hand
    backward below needs exactly sum(dy) and sum(dy*xhat), computed as ONE
    stacked (C, 2) reduction, from which d_bias, d_scale AND dx all
    follow. Returns ``(y, stats)``; ``stats`` (C, 2) raw moments feed the
    running-average state ONLY (callers stop_gradient them — the backward
    ignores their cotangent)."""
    y, stats, _, _ = _bn_train_impl(x, scale, bias, eps, moments)
    return y, stats


def _bn_train_fwd(x, scale, bias, eps, moments=None):
    y, stats, mean, inv = _bn_train_impl(x, scale, bias, eps, moments)
    return (y, stats), (x, scale, mean, inv)


def _bn_train_bwd(eps, moments, res, cts):
    dy, _ = cts  # stats feed only the stop_gradient'd EMA state
    x, scale, mean, inv = res
    axes = tuple(range(x.ndim - 1))
    n = 1
    for axis in axes:
        n *= x.shape[axis]
    dyf = dy.astype(jnp.float32)
    xhat = (x.astype(jnp.float32) - mean) * inv
    # The whole backward's reduction work as one stacked (C, 2) sum ->
    # one collective per layer per backward pass under data sharding.
    sums = jnp.sum(jnp.stack([dyf, dyf * xhat], axis=-1), axis=axes)
    sum_dy = sums[..., 0]
    sum_dy_xhat = sums[..., 1]
    # Standard fused-BN gradient (mean/var terms folded in; the var>=0
    # clamp is ignored — it only binds at var == 0 numerical residue).
    dx = (scale * inv) * (dyf - sum_dy / n - xhat * (sum_dy_xhat / n))
    return dx.astype(x.dtype), sum_dy_xhat, sum_dy


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


def bn_act_train(x, scale, bias, eps, act: bool = False):
    """Train-mode BN with an optionally FUSED activation — the conv
    stack's structural seam (ISSUE 14 / ROADMAP item 4).

    Resolves the ``fused_conv`` tune table: ``impl="reference"`` (the
    default, and the only behavior with absent tables or
    ``ROCKET_TPU_TUNE=0``) is bitwise the pre-existing path —
    :func:`_bn_train` followed by ``jax.nn.relu`` when ``act``;
    ``impl="pallas"`` routes through the fused stats+normalize+relu
    kernel (``ops/fused_conv.py``) under the table's schedule/block_rows.

    The pallas variant engages on a SINGLE-device accelerator only: the
    reference path's moment reduction is what GSPMD turns into the
    cross-replica sync-BN collective under a data-sharded batch, and the
    fused kernel deliberately has no shard_map seam yet (multi-chip conv
    is not the flat soft spot). ``ROCKET_TPU_FUSED_CONV`` force-overrides
    the impl (``pallas`` runs interpreted on CPU — tests and triage).
    Returns ``(y, stats)`` like ``_bn_train``.
    """
    import os

    from rocket_tpu.tune import get_config

    c = x.shape[-1]
    n = 1
    for dim in x.shape[:-1]:
        n *= dim
    config = get_config(
        "fused_conv", shape={"n": n, "c": c}, dtype=x.dtype
    ) or {}
    forced = os.environ.get("ROCKET_TPU_FUSED_CONV")
    impl = forced or config.get("impl", "reference")
    if impl == "pallas":
        from rocket_tpu.ops.fused_conv import (
            fused_bn_act,
            fused_bn_act_supported,
        )

        block_rows = config.get("block_rows", 512)
        on_cpu = jax.devices()[0].platform == "cpu"
        single = jax.device_count() == 1
        if fused_bn_act_supported(
            n, block_rows, jnp.dtype(x.dtype).itemsize
        ) and (bool(forced) or (not on_cpu and single)):
            return fused_bn_act(
                x, scale, bias, eps=eps, act=act,
                schedule=config.get("schedule", "twopass"),
                block_rows=block_rows,
                interpret=True if on_cpu else None,
            )
    # ONE spelling of the fallback: the same composition the tuner's
    # parity baseline runs (it wraps this module's _bn_train + relu).
    from rocket_tpu.ops.fused_conv import reference_bn_act

    return reference_bn_act(x, scale, bias, eps, act)


class BatchNorm(Layer):
    """Batch normalization over all but the last (channel) axis.

    Under a data-sharded batch the reductions are over the *global* logical
    batch — XLA GSPMD turns them into ICI collectives automatically, so this
    is cross-replica (sync) batchnorm by construction. Forward AND backward
    each reduce their per-channel statistics as one stacked (C, 2)
    collective (``_bn_train`` / ``_bn_train_bwd``).
    """

    def __init__(self, num_features: int, momentum: float = 0.9, eps: float = 1e-5):
        self.num_features = num_features
        self.momentum = momentum
        self.eps = eps

    def init_params(self, key):
        return {
            "scale": jnp.ones((self.num_features,), jnp.float32),
            "bias": jnp.zeros((self.num_features,), jnp.float32),
        }

    def init_state(self):
        return {
            "mean": jnp.zeros((self.num_features,), jnp.float32),
            "var": jnp.ones((self.num_features,), jnp.float32),
        }

    def apply(self, variables, x, *, mode="train", rng=None):
        return self.apply_act(variables, x, mode=mode, act=False)

    def apply_act(self, variables, x, *, mode="train", act=False):
        """``apply`` with the activation folded into the BN epilogue —
        the conv-stack call sites (``models/resnet._ConvBN``) route here
        so the ``fused_conv`` structural candidate can fuse
        stats+normalize+relu into one program (:func:`bn_act_train`).
        With ``act=False`` this IS ``apply``; with ``act=True`` and no
        table entry it is bitwise ``relu(apply(...))``."""
        p, s = variables["params"], variables["state"]
        if mode == "train":
            y, stats = bn_act_train(
                x, p["scale"], p["bias"], self.eps, act=act
            )
            # The EMA is bookkeeping, not a gradient path — stop_gradient
            # makes the fused backward's ignored stats-cotangent provably
            # zero by construction.
            stats = jax.lax.stop_gradient(stats)
            mean = stats[..., 0]
            var = jnp.maximum(stats[..., 1] - jnp.square(mean), 0.0)
            m = self.momentum
            new_state = {
                "mean": m * s["mean"] + (1 - m) * mean,
                "var": m * s["var"] + (1 - m) * var,
            }
            return y, new_state
        mean, var = s["mean"], s["var"]
        inv = jax.lax.rsqrt(var + self.eps) * p["scale"]
        y = (x.astype(jnp.float32) - mean) * inv + p["bias"]
        y = y.astype(x.dtype)
        if act:
            # Eval stacks are XLA-fused fine; same op order as the
            # pre-seam external relu.
            y = jax.nn.relu(y)
        return y, s

    def __repr__(self):
        return f"BatchNorm({self.num_features})"


class LayerNorm(Layer):
    def __init__(self, num_features: int, eps: float = 1e-5, use_bias: bool = True):
        self.num_features = num_features
        self.eps = eps
        self.use_bias = use_bias

    def init_params(self, key):
        params = {"scale": jnp.ones((self.num_features,), jnp.float32)}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.num_features,), jnp.float32)
        return params

    def apply(self, variables, x, *, mode="train", rng=None):
        p = variables["params"]
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps) * p["scale"]
        if self.use_bias:
            y = y + p["bias"]
        return y.astype(x.dtype), variables["state"]

    def __repr__(self):
        return f"LayerNorm({self.num_features})"


class RMSNorm(Layer):
    """Root-mean-square norm (no centering, no bias) — the Llama-family
    normalizer. f32 statistics inside any compute dtype, like LayerNorm.
    ``zero_centered``: the weight is stored about zero and applied as ``1 +
    scale`` (Qwen3-Next's, Gemma's), so that it starts at zeros."""

    def __init__(self, num_features: int, eps: float = 1e-6,
                 zero_centered: bool = False):
        self.num_features = num_features
        self.eps = eps
        self.zero_centered = zero_centered

    def init_params(self, key):
        init = jnp.zeros if self.zero_centered else jnp.ones
        return {"scale": init((self.num_features,), jnp.float32)}

    def apply(self, variables, x, *, mode="train", rng=None):
        p = variables["params"]
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        scale = p["scale"]
        if self.zero_centered:
            scale = 1.0 + scale.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(ms + self.eps) * scale
        return y.astype(x.dtype), variables["state"]

    def __repr__(self):
        return f"RMSNorm({self.num_features})"


def gated_rms_norm(x, gate, scale, eps: float = 1e-6):
    """``RMSNorm(x) * scale * silu(gate)`` over the last axis, in float32
    (the output norm of a gated linear-attention head: plain weight, the
    gate applied after the norm). Returns float32."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
    return y * jax.nn.silu(gate.astype(jnp.float32))


class Embedding(Layer):
    def __init__(
        self,
        num_embeddings: int,
        features: int,
        embedding_init: Callable = jax.nn.initializers.normal(stddev=0.02),
    ):
        self.num_embeddings = num_embeddings
        self.features = features
        self.embedding_init = embedding_init

    def init_params(self, key):
        return {
            "table": self.embedding_init(
                key, (self.num_embeddings, self.features), jnp.float32
            )
        }

    def apply(self, variables, x, *, mode="train", rng=None):
        return jnp.take(variables["params"]["table"], x, axis=0), variables["state"]

    def __repr__(self):
        return f"Embedding({self.num_embeddings}, {self.features})"


class Dropout(Layer):
    def __init__(self, rate: float):
        self.rate = rate

    def apply(self, variables, x, *, mode="train", rng=None):
        if mode != "train" or self.rate == 0.0:
            return x, variables["state"]
        if rng is None:
            raise ValueError("Dropout needs an rng in train mode")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), variables["state"]

    def __repr__(self):
        return f"Dropout({self.rate})"


class Flatten(Layer):
    def apply(self, variables, x, *, mode="train", rng=None):
        return x.reshape(x.shape[0], -1), variables["state"]


# Activation layer shorthands.
def relu() -> Lambda:
    return Lambda(jax.nn.relu, "relu")


def gelu() -> Lambda:
    return Lambda(jax.nn.gelu, "gelu")


def tanh() -> Lambda:
    return Lambda(jnp.tanh, "tanh")


def silu() -> Lambda:
    return Lambda(jax.nn.silu, "silu")


def softmax() -> Lambda:
    return Lambda(jax.nn.softmax, "softmax")
