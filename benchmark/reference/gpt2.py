"""Plain GPT-2 in ``jax.numpy``: weights from a seed, forward, loss,
gradients and AdamW — float32, ``"highest"`` matmul precision, no kernels,
no cache, nothing imported from ``rocket_tpu``.

The benchmark makes the weights here and hands them to the program; after
the window the reference makes the same weights again from the seed, so it
takes nothing the program has touched.

Follows the published GPT-2 (Radford et al. 2019; ``modeling_gpt2``):
learned positions, pre-LayerNorm blocks (eps 1e-5), fused QKV projection
``[q | k | v]``, causal softmax attention scaled by ``1/sqrt(head_dim)``,
tanh-approximated GELU, tied input/output embedding. Departures: dropout
0 (the configuration's changed key), biases and LayerNorm parameters get
small random values instead of 0/1 so that every leaf carries a gradient
and a dropped bias shows.

Layers are held STACKED (leading dim ``n_layer``) and run under
``lax.scan`` with per-layer rematerialisation, so the reference compiles
in seconds and fits beside nothing else on one chip.

``quant`` (the control): a function applied to BOTH operands of every
matrix multiplication in the forward pass — :func:`fp8` rounds them to
float8 e4m3 with a per-tensor scale, the nearest precision below the
bfloat16 the configurations state.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


# -- the seed ---------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (the driver's seeds
    pass 2**31): the low 31 bits seed the key, the rest are folded in.
    The key is of the ``rbg`` kind: the chip's own bit generator makes a
    billion weights in a fraction of the time threefry takes, and program
    and reference draw theirs in the same process on the same device."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), (seed >> 31) & 0x7FFFFFFF
    )


# -- weights ----------------------------------------------------------------

def make_params(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Stacked GPT-2 weights from ``key``, in ``dtype``. Traceable: call
    it under ``jax.jit`` so the weights are made on the device at once."""
    d, L, V, T = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"], cfg["n_positions"]
    inner = cfg.get("n_inner") or 4 * d
    std = float(cfg.get("initializer_range", 0.02))
    resid = std / math.sqrt(2 * L)
    ks = iter(jax.random.split(key, 20))

    def normal(shape, s):
        return (s * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    def ln(shape):
        return {"scale": (1.0 + normal(shape, std).astype(jnp.float32)).astype(dtype),
                "bias": normal(shape, std)}

    return {
        "wte": {"table": normal((V, d), std)},
        "wpe": {"table": normal((T, d), std / 2)},
        "ln_f": ln((d,)),
        "blocks": {
            "ln1": ln((L, d)),
            "attn": {
                "qkv": {"w": normal((L, d, 3 * d), std), "b": normal((L, 3 * d), std)},
                "proj": {"w": normal((L, d, d), resid), "b": normal((L, d), std)},
            },
            "ln2": ln((L, d)),
            "mlp": {
                "fc_in": {"w": normal((L, d, inner), std), "b": normal((L, inner), std)},
                "fc_out": {"w": normal((L, inner, d), resid), "b": normal((L, d), std)},
            },
        },
    }


def unstack(params: dict, n_layer: int) -> dict:
    """The same weights with one subtree per layer, ``blocks/<i>/...``."""
    out = {k: v for k, v in params.items() if k != "blocks"}
    out["blocks"] = {
        str(i): jax.tree.map(lambda a, i=i: a[i], params["blocks"])
        for i in range(n_layer)
    }
    return out


def leaf_names(params: dict) -> list[str]:
    """``a/b/c`` names of a tree's leaves, in ``jax.tree`` order."""
    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in paths]


def _part_norms(name: str, leaf, n_embd: int, lead: int = 0) -> dict:
    """L2 norm of ``leaf`` (one per leading index if ``lead``), the fused
    ``[q | k | v]`` leaves split into their three parts: the key's bias
    has no gradient under softmax, the query's and the value's do, and
    the comparison leaves out by gradient, not by name."""
    sq = jnp.square(leaf.astype(jnp.float32))
    parts = {"": sq}
    if leaf.shape[-1] == 3 * n_embd:
        parts = {
            f"[{c}]": sq[..., i * n_embd:(i + 1) * n_embd]
            for i, c in enumerate("qkv")
        }
    if lead:
        return {
            f"{name}{suffix}": jnp.sqrt(jnp.sum(part.reshape(lead, -1), axis=1))
            for suffix, part in parts.items()
        }
    return {f"{name}{suffix}": jnp.sqrt(jnp.sum(part)) for suffix, part in parts.items()}


def tree_norms(tree: dict, n_embd: int) -> dict:
    """Norm of every leaf (part) of an UNSTACKED tree, by name. Traceable."""
    out = {}
    for name, leaf in zip(leaf_names(tree), jax.tree.leaves(tree)):
        out.update(_part_norms(name, leaf, n_embd))
    return out


def per_layer_norms(stacked: dict, n_layer: int, n_embd: int) -> dict:
    """The same norms under the same names, computed on the STACKED tree
    (a stacked leaf gives one norm per layer)."""
    rest = {k: v for k, v in stacked.items() if k != "blocks"}
    out = tree_norms(rest, n_embd)
    blocks = stacked["blocks"]
    for name, leaf in zip(leaf_names(blocks), jax.tree.leaves(blocks)):
        for part, vec in _part_norms(name, leaf, n_embd, lead=n_layer).items():
            for i in range(n_layer):
                out[f"blocks/{i}/{part}"] = vec[i]
    return out


# -- the control's precision --------------------------------------------------

def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448), back
    in float32: what an fp8 matmul would be fed."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(jax.lax.stop_gradient(x)))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


# -- forward ------------------------------------------------------------------

def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def _layer(x, p, n_head: int, quant: Optional[Callable]):
    q8 = quant or (lambda a: a)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b), precision=HIGHEST)

    b, t, d = x.shape
    dh = d // n_head
    h = _ln(x, p["ln1"])
    qkv = mm("btd,de->bte", h, p["attn"]["qkv"]["w"]) + p["attn"]["qkv"]["b"]
    q, k, v = (
        qkv[..., i * d:(i + 1) * d].reshape(b, t, n_head, dh) for i in range(3)
    )
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", a, v).reshape(b, t, d)
    x = x + mm("btd,de->bte", o, p["attn"]["proj"]["w"]) + p["attn"]["proj"]["b"]
    h = _ln(x, p["ln2"])
    h = _gelu(mm("btd,de->bte", h, p["mlp"]["fc_in"]["w"]) + p["mlp"]["fc_in"]["b"])
    return x + mm("btd,de->bte", h, p["mlp"]["fc_out"]["w"]) + p["mlp"]["fc_out"]["b"]


def hidden(params: dict, tokens, n_head: int, quant=None):
    """Final-LayerNorm hidden states ``(B, T, D)`` for ``tokens`` (B, T)."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    t = tokens.shape[1]
    x = params["wte"]["table"][tokens] + params["wpe"]["table"][:t]

    @jax.checkpoint
    def body(x, p):
        return _layer(x, p, n_head, quant), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return _ln(x, params["ln_f"])


def logits(params: dict, tokens, n_head: int, quant=None):
    """``(B, T, V)`` float32 logits through the tied head."""
    x = hidden(params, tokens, n_head, quant)
    q8 = quant or (lambda a: a)
    table = params["wte"]["table"].astype(jnp.float32)
    return jnp.einsum("btd,vd->btv", q8(x), q8(table), precision=HIGHEST)


def nll_sum(params: dict, tokens, n_head: int, quant=None):
    """Summed next-token negative log-likelihood over ``tokens`` (B, T):
    position i predicts token i+1; B*(T-1) terms."""
    lg = logits(params, tokens, n_head, quant)[:, :-1]
    lse = jax.nn.logsumexp(lg, axis=-1)
    lab = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - lab)


# -- training -----------------------------------------------------------------

def warmup_cosine(step, *, peak, warmup_steps, decay_steps, end=0.0):
    """Linear 0 -> peak over ``warmup_steps``, then cosine to ``end`` at
    ``decay_steps`` (``step`` counts from 0: the first update runs at 0)."""
    step = jnp.asarray(step, jnp.float32)
    warm = peak * step / max(warmup_steps, 1)
    frac = jnp.clip((step - warmup_steps) / max(decay_steps - warmup_steps, 1), 0.0, 1.0)
    cos = end + (peak - end) * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.where(step < warmup_steps, warm, cos)


def loss_and_grads(params, tokens, n_head: int, *, block_rows: int, quant=None,
                   rows: Optional[int] = None):
    """Mean next-token loss over the first ``rows`` rows of ``tokens``
    (all by default) and its gradients, accumulated over blocks of
    ``block_rows`` rows so the float32 logits fit."""
    n, t = tokens.shape
    rows = n if rows is None else rows
    denom = rows * (t - 1)
    vg = jax.jit(jax.value_and_grad(
        lambda p, tk: nll_sum(p, tk, n_head, quant) / denom
    ))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    loss = grads = None
    for start in range(0, rows, block_rows):
        l, g = vg(params, tokens[start:min(start + block_rows, rows)])
        loss = l if loss is None else loss + l
        grads = g if grads is None else add(grads, g)
    return loss, grads


def adamw_init(params):
    zeros = jax.tree.map(lambda a: jnp.zeros_like(a, jnp.float32), params)
    return {"m": zeros, "v": jax.tree.map(jnp.copy, zeros)}


def adamw_update(params, grads, opt, count: int, lr, *, b1=0.9, b2=0.999,
                 eps=1e-8, weight_decay=0.0):
    """One AdamW step (Loshchilov & Hutter): bias-corrected moments, decay
    on weights with two or more dims in the UNSTACKED model (matrices and
    embeddings; biases and LayerNorm parameters exempt), all scaled by
    ``lr``. ``count`` is the number of updates already made."""
    t = count + 1

    @jax.jit
    def step(params, grads, opt, lr):
        def decays(path):
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            return name.endswith("/w") or name.endswith("table")

        def one(path, p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            if weight_decay and decays(path):
                u = u + weight_decay * p
            return p - lr * u, m, v

        out = jax.tree_util.tree_map_with_path(
            one, params, grads, opt["m"], opt["v"]
        )
        pick = lambda i: jax.tree.map(  # noqa: E731
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple)
        )
        return pick(0), {"m": pick(1), "v": pick(2)}

    return step(params, grads, opt, jnp.asarray(lr, jnp.float32))
