"""Plain MiniCPM-SALA language model in ``jax.numpy``: weights from a seed
and the forward pass of a cut of the published stack — float32,
``"highest"`` matmul precision, no kernels, no cache, no batching, nothing
imported from ``rocket_tpu``.

Follows the source's ``config.json`` (``mixer_types``, ``sparse`` layers
``minicpm4`` and ``lightning-attn`` ones), MiniCPM4's InfLLM v2 block-sparse
attention and MiniMax's lightning attention, with every point that could
not be checked under ``assumed`` in the configuration file:

* **Stack.** ``h = scale_emb * E[token]``; per layer ``h += alpha *
  mixer(N(h))``, then ``h += alpha * mlp(N'(h))`` with ``alpha =
  scale_depth / sqrt(published layers)``; ``N`` the RMSNorm ``x *
  rsqrt(mean(x^2) + eps) * w``; ``mlp = W_down(silu(W_gate x) * W_up x)``;
  ``logits = W_head(N_f(h) / (hidden_size / dim_model_base))``, untied.
  Layer ``i`` of the cut is the published layer ``layer_indices[i]`` and
  is of kind ``mixer_types[i]``.
* **Lightning layer.** ``q, k, v, g = x W_q, x W_k, x W_v, x W_g`` (heads
  of ``lightning_head_dim``); ``q`` and ``k`` through a per-head RMSNorm
  (one weight for all heads), then rotate-half rotary at the token's
  position (``rope_theta``); per head ``S_t = lambda S_{t-1} + k_t^T v_t``
  (zero before position 0) and ``o_t = (q_t / sqrt(head)) S_t``, ``lambda
  = exp(-slope * (1 - l / (L - 1) + 1e-5))`` with MiniMax's ALiBi slopes,
  ``l`` the published index and ``L`` the published depth; ``out = W_o(
  RMSNorm(concat o) * sigmoid(g))``, the norm over every head at once.
  Computed in blocks of rows, in float32 (the chunked form of the same
  recurrence).
* **Sparse layer.** No rotary; ``q``, ``k`` through the per-head RMSNorm;
  ``out = W_o(attention * sigmoid(x W_g))``. A query at position ``p <
  dense_len`` attends every position ``<= p``. Past it, per K/V head ``g``:
  compressed keys ``Kc_j = mean(K[stride j .. stride j + kernel - 1])`` for
  every unit with ``stride j + kernel - 1 <= p``; unit scores ``c_j =
  sum over the group's query heads of softmax_j(q_h . Kc_j / sqrt(head))``;
  block ``b`` (positions ``block b .. block b + block - 1``) scores the
  most of ``c`` over the units that overlap it; blocks ``< init_blocks``
  and those holding a position in ``(p - window, p]`` score +inf; the
  ``topk`` best blocks that start at or before ``p`` (ties to the lower
  id) are attended, positions ``<= p`` only, at scale ``head^-0.5``.

Departures from the source, each on purpose:

* **The switch from dense to sparse** is made by the QUERY's position, not
  by the sequence's length, so that a chunked prefill and a decode step
  agree on every row.
* **The cut.** Only the configuration's layers are run, with their
  published indices; the stack's input is the embedding (this stage is the
  first of its pipeline in the benchmark: what the earlier stages would
  have added is left out, in the program and here alike).

Selection is computed per position. ``margin`` (per position): the
smallest, over the sparse layers and K/V heads, relative distance of the
``topk``-th block score ``r_64`` from the nearest score on either side of
it that differs from it — ``min(above - r_64, r_64 - below) / r_64``, where
it is small float rounding may pick another block. Two blocks that the
same unit overlaps score the same number exactly, in any precision, and
fall on either side of the cut by their ids alone, so such a tie is no
ambiguity. +inf where the position attends densely or the ``topk``-th
block was forced.

``quant`` (the controls): a function applied to BOTH operands of every
matrix multiplication — :func:`fp8` rounds to float8 e4m3. ``dense``: the
sparse layers attend every position (a build that ignores the selection);
``decay_one``: every lightning head keeps ``lambda = 1``.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: Rows a block of the layer scans takes at once; query rows of a block
#: of sparse attention; rows of a block of the lightning recurrence.
ROWS = 1024
QUERY_ROWS = 64
RECUR_ROWS = 64


# -- the seed ---------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A PRNG key (``rbg``) from any non-negative whole number: the low 31
    bits seed it, the rest are folded in."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), (seed >> 31) & 0x7FFFFFFF
    )


def fp8(a):
    """Round to float8 e4m3 at a per-tensor scale and back to float32."""
    a = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(a)) / 448.0 + 1e-30
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# -- sizes ------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    """The numbers the forward pass needs, under short names."""
    sc = cfg["sparse_config"]
    published = cfg.get("published", {}).get("num_hidden_layers", cfg["num_hidden_layers"])
    return {
        "d": cfg["hidden_size"], "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
        "H": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
        "head": cfg["head_dim"], "inter": cfg["intermediate_size"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
        "Hl": cfg["lightning_nh"], "dl": cfg["lightning_head_dim"],
        "published": int(published),
        "alpha": cfg["scale_depth"] / math.sqrt(published),
        "emb": float(cfg["scale_emb"]),
        "width_ratio": cfg["hidden_size"] / cfg["dim_model_base"],
        "kernel": sc["kernel_size"], "stride": sc["kernel_stride"],
        "block": sc["block_size"], "topk": sc["topk"], "init": sc["init_blocks"],
        "window": sc["window_size"], "dense_len": sc["dense_len"],
    }


def is_sparse(cfg: dict, i: int) -> bool:
    return cfg["mixer_types"][i] == "minicpm4"


def published_index(cfg: dict, i: int) -> int:
    return int(cfg.get("layer_indices", range(cfg["num_hidden_layers"]))[i])


def alibi_slopes(heads: int) -> np.ndarray:
    """MiniMax's (ALiBi's) slopes of ``heads`` heads: ``2^(-8 i / n)``."""
    def power_of_two(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    n = 2 ** int(math.floor(math.log2(heads)))
    slopes = power_of_two(n)
    if n < heads:
        slopes += power_of_two(2 * n)[0::2][:heads - n]
    return np.asarray(slopes, np.float64)


def layer_decay(cfg: dict, i: int):
    """:func:`log_decay` of layer ``i`` as an array, None for a sparse
    layer."""
    return None if is_sparse(cfg, i) else jnp.asarray(log_decay(cfg, i))


def log_decay(cfg: dict, i: int) -> np.ndarray:
    """``log lambda`` of each lightning head of layer ``i`` of the cut."""
    z = sizes(cfg)
    depth = 1.0 - published_index(cfg, i) / (z["published"] - 1) + 1e-5
    return (-alibi_slopes(z["Hl"]) * depth).astype(np.float32)


# -- weights ----------------------------------------------------------------

def _normal(k, shape, s, dtype):
    return (s * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def layer_params(k, cfg: dict, sparse: bool, dtype=jnp.float32) -> dict:
    """One layer's weights from its key ``k`` (traceable). Scales are the
    configuration file's ``assumed.weights``."""
    z = sizes(cfg)
    d = z["d"]
    ks = jax.random.split(k, 16)
    near = lambda key, n, centre: (centre + _normal(key, (n,), 0.05, jnp.float32)).astype(dtype)
    out = {"ln1": near(ks[0], d, 1.0), "ln2": near(ks[1], d, 1.0),
           "mlp": {"w_gate": _normal(ks[2], (d, z["inter"]), d ** -0.5, dtype),
                   "w_up": _normal(ks[3], (d, z["inter"]), d ** -0.5, dtype),
                   "w_down": _normal(ks[4], (z["inter"], d), z["inter"] ** -0.5, dtype)}}
    if sparse:
        hq, hkv = z["H"] * z["head"], z["Hkv"] * z["head"]
        out["attn"] = {
            "w_q": _normal(ks[5], (d, hq), d ** -0.5, dtype),
            "w_gate": _normal(ks[6], (d, hq), d ** -0.5, dtype),
            "w_k": _normal(ks[7], (d, hkv), d ** -0.5, dtype),
            "w_v": _normal(ks[8], (d, hkv), d ** -0.5, dtype),
            "w_o": _normal(ks[9], (hq, d), hq ** -0.5, dtype),
            "q_norm": near(ks[10], z["head"], 2.0),
            "k_norm": near(ks[11], z["head"], 2.0),
        }
    else:
        w = z["Hl"] * z["dl"]
        out["lightning"] = {
            "w_q": _normal(ks[5], (d, w), d ** -0.5, dtype),
            "w_k": _normal(ks[6], (d, w), d ** -0.5, dtype),
            "w_v": _normal(ks[7], (d, w), d ** -0.5, dtype),
            "w_gate": _normal(ks[8], (d, w), d ** -0.5, dtype),
            "w_o": _normal(ks[9], (w, d), w ** -0.5, dtype),
            "q_norm": near(ks[10], z["dl"], 1.0),
            "k_norm": near(ks[11], z["dl"], 1.0),
            "o_norm": near(ks[12], w, 1.0),
        }
    return out


def make_params(key, cfg: dict, dtype=jnp.float32, *, layer_jit: bool = False) -> dict:
    """Weights from ``key`` in ``dtype``: ``embed``, ``head`` (untied),
    ``norm``, one subtree per layer (``layers/<i>``). ``layer_jit`` (call
    it eagerly then) makes each layer in a jitted call of its own, so that
    one layer's float32 temporaries are alive at a time."""
    z = sizes(cfg)
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)

    def top(k_embed, k_head, k_norm):
        return {
            "embed": _normal(k_embed, (z["V"], z["d"]), 0.1, dtype),
            "head": _normal(k_head, (z["d"], z["V"]), z["width_ratio"] * z["d"] ** -0.5, dtype),
            "norm": (1.0 + _normal(k_norm, (z["d"],), 0.05, jnp.float32)).astype(dtype),
        }

    def layer(k, sparse):
        return layer_params(k, cfg, sparse, dtype)

    if layer_jit:
        top, layer = jax.jit(top), jax.jit(layer, static_argnums=1)
    out = top(k_embed, k_head, k_norm)
    out["layers"] = {
        str(i): layer(jax.random.fold_in(k_layers, published_index(cfg, i)), is_sparse(cfg, i))
        for i in range(z["L"])
    }
    return out


def program_params(params: dict, cfg: dict) -> dict:
    """The same weights in the PROGRAM's layout (``TransformerLM`` with
    lightning state layers and block-sparse gated attention): a renaming,
    and the projections of one input side by side as the program's one
    matrix (``[q | gate | k | v]``; ``[q | k | v | g]``)."""
    side = lambda *ws: jnp.concatenate(ws, axis=1)
    blocks = {}
    for i, lp in params["layers"].items():
        m = lp["mlp"]
        block = {
            "ln1": {"scale": lp["ln1"]}, "ln2": {"scale": lp["ln2"]},
            "mlp": {"fc_gate": {"w": m["w_gate"]}, "fc_in": {"w": m["w_up"]},
                    "fc_out": {"w": m["w_down"]}},
        }
        if "attn" in lp:
            a = lp["attn"]
            block["attn"] = {
                "qkv": {"w": side(a["w_q"], a["w_gate"], a["w_k"], a["w_v"])},
                "proj": {"w": a["w_o"]},
                "q_norm": {"scale": a["q_norm"]}, "k_norm": {"scale": a["k_norm"]},
            }
        else:
            g = lp["lightning"]
            block["mixer"] = {
                "in_proj": {"w": side(g["w_q"], g["w_k"], g["w_v"], g["w_gate"])},
                "q_norm": {"scale": g["q_norm"]}, "k_norm": {"scale": g["k_norm"]},
                "norm": {"scale": g["o_norm"]}, "out_proj": {"w": g["w_o"]},
            }
        blocks[i] = block
    return {
        "wte": {"table": params["embed"]}, "ln_f": {"scale": params["norm"]},
        "head": {"w": params["head"]}, "blocks": blocks,
    }


# -- the forward pass ---------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rope(x, positions, theta: float):
    """Rotate-half rotary over every lane of each head of ``x`` (T, heads,
    head) at ``positions`` (T,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def mlp(p, x, quant=None):
    f32 = lambda a: a.astype(jnp.float32)
    hidden = jax.nn.silu(_mm(x, f32(p["w_gate"]), quant)) * _mm(x, f32(p["w_up"]), quant)
    return _mm(hidden, f32(p["w_down"]), quant)


def _blocks(t: int, rows: int) -> int:
    rows = min(rows, t)
    if t % rows:
        raise ValueError(f"{t} positions are not whole blocks of {rows}")
    return rows


def lightning_rule(s, q, k, v, g):
    """``(S', o (B, H, dh))``: the recurrence over ``B`` rows from ``S``
    (H, dh, dh) float32 — ``q`` (scaled), ``k``, ``v`` (B, H, dh), ``g`` (B,
    H) ``log lambda`` of each row (0 in a row that advances nothing, whose
    ``k`` is 0) — in blocks of ``RECUR_ROWS`` rows: ``O = ((Q K^T) * D) V +
    (lambda^(i+1) Q) S0``, ``S' = lambda^B S0 + (lambda^(B-1-j) K)^T V``."""
    t, h, dh = q.shape
    b = _blocks(t, RECUR_ROWS)
    split = lambda a: a.reshape((t // b, b) + a.shape[1:])

    def step(s, xs):
        qb, kb, vb, gb = xs
        cum = jnp.cumsum(gb, axis=0)                                     # (B, H)
        diff = cum[:, None, :] - cum[None, :, :]                         # (B, B, H)
        lower = (jnp.arange(b)[:, None] >= jnp.arange(b)[None, :])[..., None]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        scores = jnp.einsum("ihd,jhd->ijh", qb, kb, precision=HIGHEST) * decay
        o = jnp.einsum("ijh,jhd->ihd", scores, vb, precision=HIGHEST) \
            + jnp.exp(cum)[..., None] * jnp.einsum("ihd,hde->ihe", qb, s, precision=HIGHEST)
        last = cum[-1]
        kd = kb * jnp.exp(last[None, :] - cum)[..., None]
        s = jnp.exp(last)[:, None, None] * s + jnp.einsum(
            "jhd,jhe->hde", kd, vb, precision=HIGHEST)
        return s, o

    s, o = jax.lax.scan(step, s, (split(q), split(k), split(v), split(g)))
    return s, o.reshape(t, h, dh)


def lightning(p, x, cfg: dict, log_lam, quant=None, *, decay_one: bool = False,
              length=None):
    """The lightning mixer of ONE sequence ``x`` (T, d), normed, with
    ``log lambda`` of each head ``log_lam`` (:func:`log_decay`): ``(out,
    S)`` — ``S`` after the first ``length`` rows (all where it is None).
    A scan over blocks of ``ROWS`` rows carries ``S``."""
    z = sizes(cfg)
    t = x.shape[0]
    rows = _blocks(t, ROWS)
    f32 = lambda a: a.astype(jnp.float32)
    log_lam = jnp.zeros((z["Hl"],), jnp.float32) if decay_one else log_lam
    length = t if length is None else length

    def block(s, start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        positions = start + jnp.arange(rows)
        heads = lambda a: a.reshape(rows, z["Hl"], z["dl"])
        q = rope(rms_norm(heads(_mm(xb, f32(p["w_q"]), quant)), f32(p["q_norm"]), z["eps"]),
                 positions, z["theta"]) * z["dl"] ** -0.5
        k = rope(rms_norm(heads(_mm(xb, f32(p["w_k"]), quant)), f32(p["k_norm"]), z["eps"]),
                 positions, z["theta"])
        v = heads(_mm(xb, f32(p["w_v"]), quant))
        real = positions < length
        k = jnp.where(real[:, None, None], k, 0.0)
        g = jnp.where(real[:, None], log_lam[None, :], 0.0)
        s, o = lightning_rule(s, q, k, v, g)
        o = rms_norm(o.reshape(rows, -1), f32(p["o_norm"]), z["eps"]) \
            * jax.nn.sigmoid(_mm(xb, f32(p["w_gate"]), quant))
        return s, _mm(o, f32(p["w_o"]), quant)

    s0 = jnp.zeros((z["Hl"], z["dl"], z["dl"]), jnp.float32)
    s, out = jax.lax.scan(block, s0, jnp.arange(0, t, rows))
    return out.reshape(t, -1), s


def compressed_keys(k, cfg: dict):
    """``Kc`` (units, Hkv, head): the mean of each unit's keys, ``k`` (T,
    Hkv, head); units past the sequence's end are zeros (never complete)."""
    z = sizes(cfg)
    t = k.shape[0]
    units = t // z["stride"]
    idx = jnp.arange(units)[:, None] * z["stride"] + jnp.arange(z["kernel"])[None, :]
    rows = jnp.take(k, jnp.minimum(idx, t - 1), axis=0)                # (U, K, Hkv, hd)
    return jnp.mean(rows, axis=1)


def selection(q, kc, positions, cfg: dict):
    """``(pick (Q, Hkv, blocks) bool, margin (Q,))`` for queries ``q`` (Q,
    H, head) at ``positions`` (Q,), exactly as the module docstring says:
    the ``topk`` blocks of every position at or past ``dense_len`` (every
    block that starts at or before the query below it)."""
    z = sizes(cfg)
    nq = q.shape[0]
    units = kc.shape[0]
    group = z["H"] // z["Hkv"]
    nb = units * z["stride"] // z["block"]
    complete = (jnp.arange(units) * z["stride"] + z["kernel"] - 1)[None, :] \
        <= positions[:, None]                                           # (Q, U)
    s = jnp.einsum("qkgd,ukd->qkgu", q.reshape(nq, z["Hkv"], group, z["head"]), kc,
                   precision=HIGHEST) * z["head"] ** -0.5
    s = jnp.where(complete[:, None, None, :], s, -jnp.inf)
    c = jnp.sum(jax.nn.softmax(s, axis=-1), axis=2)                     # (Q, Hkv, U)
    c = jnp.where(complete[:, None, :], c, -jnp.inf)
    c = jnp.where(jnp.isnan(c), -jnp.inf, c)
    b = jnp.arange(nb)
    start = b * z["block"]
    # The units j that overlap block b: from the first with stride j +
    # kernel - 1 >= start, while stride j <= start + block - 1.
    width = (z["block"] + z["kernel"] - 2) // z["stride"] + 1
    first = -((z["kernel"] - 1 - start) // z["stride"])                 # ceil
    j = first[:, None] + jnp.arange(width)[None, :]                     # (nb, width)
    overlap = (j >= 0) & (j < units) & (j * z["stride"] <= start[:, None] + z["block"] - 1)
    took = jnp.take(c, jnp.clip(j, 0, units - 1), axis=-1)              # (Q, Hkv, nb, width)
    r = jnp.max(jnp.where(overlap, took, -jnp.inf), axis=-1)
    p = positions[:, None, None]
    forced = (b < z["init"]) | (start + z["block"] - 1 > p - z["window"])
    r = jnp.where(forced, jnp.inf, r)
    eligible = start <= p
    r = jnp.where(eligible, r, -jnp.inf)
    # Ties to the lower id: a stable descending sort.
    order = jnp.argsort(-r, axis=-1, stable=True)
    top = order[..., :z["topk"]]
    pick = jnp.zeros(r.shape, bool).at[
        jnp.arange(nq)[:, None, None], jnp.arange(z["Hkv"])[None, :, None], top].set(True)
    ranked = jnp.take_along_axis(r, order, axis=-1)
    cut = ranked[..., z["topk"] - 1:z["topk"]]
    # Blocks whose score is the same unit's tie exactly, in any float, and
    # fall on either side of the cut by their ids alone: what rounding can
    # move is the cut's value against the nearest OTHER value on either
    # side.
    above = jnp.min(jnp.where(ranked[..., :z["topk"]] > cut, ranked[..., :z["topk"]], jnp.inf), -1)
    rest = ranked[..., z["topk"]:]
    below = jnp.max(jnp.where(rest < cut, rest, -jnp.inf), -1, initial=-jnp.inf)
    cut = cut[..., 0]
    gap = jnp.where(jnp.isinf(cut), jnp.inf,
                    jnp.minimum(above - cut, cut - below) / jnp.abs(cut))
    sparse = positions >= z["dense_len"]
    pick = jnp.where(sparse[:, None, None], pick, eligible)
    margin = jnp.where(sparse, jnp.min(gap, axis=-1), jnp.inf)
    return pick, margin


def sparse_attention(p, x, cfg: dict, quant=None, *, dense: bool = False):
    """The block-sparse gated attention of ONE sequence ``x`` (T, d),
    normed: ``(out (T, d), margin (T,))``, in blocks of ``QUERY_ROWS``
    queries (a ``lax.map``); every key is in reach of every block."""
    z = sizes(cfg)
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    k = rms_norm(_mm(x, f32(p["w_k"]), quant).reshape(t, z["Hkv"], z["head"]),
                 f32(p["k_norm"]), z["eps"])
    v = _mm(x, f32(p["w_v"]), quant).reshape(t, z["Hkv"], z["head"])
    kc = compressed_keys(k, cfg)
    kq, vq = (k, v) if quant is None else (quant(k), quant(v))
    group = z["H"] // z["Hkv"]
    rows = _blocks(t, QUERY_ROWS)

    def one(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
        pos = start + jnp.arange(rows)
        q = rms_norm(_mm(xb, f32(p["w_q"]), quant).reshape(rows, z["H"], z["head"]),
                     f32(p["q_norm"]), z["eps"])
        pick, margin = selection(q, kc, pos, cfg)
        if dense:
            pick, margin = jnp.ones_like(pick), jnp.full_like(margin, jnp.inf)
        seen = jnp.repeat(pick, z["block"], axis=-1)[..., :t] & (
            jnp.arange(t)[None, None, :] <= pos[:, None, None])        # (Q, Hkv, T)
        qb = (q if quant is None else quant(q)).reshape(rows, z["Hkv"], group, z["head"])
        s = jnp.einsum("qkgd,tkd->qkgt", qb, kq, precision=HIGHEST) * z["head"] ** -0.5
        w = jax.nn.softmax(jnp.where(seen[:, :, None], s, -jnp.inf), axis=-1)
        w = w if quant is None else quant(w)
        out = jnp.einsum("qkgt,tkd->qkgd", w, vq, precision=HIGHEST).reshape(rows, -1)
        gate = jax.nn.sigmoid(_mm(xb, f32(p["w_gate"]), quant))
        return _mm(out * gate, f32(p["w_o"]), quant), margin

    out, margin = jax.lax.map(one, jnp.arange(0, t, rows))
    return out.reshape(t, z["d"]), margin.reshape(t)


def embed(params: dict, tokens, cfg: dict):
    return params["embed"].astype(jnp.float32)[tokens] * sizes(cfg)["emb"]


def layer_forward(lp: dict, x, cfg: dict, log_lam=None, quant: Optional[Callable] = None,
                  *, dense: bool = False, decay_one: bool = False, state_after=None):
    """A layer of the cut on ONE sequence ``x`` (T, d) — a lightning layer
    with ``log_lam`` its heads' :func:`log_decay`, an argument so that the
    layers of a kind share one compiled program: ``(x', margin (T,), S)``
    — ``margin`` the selection's (+inf for a lightning layer), ``S`` the
    state a lightning layer holds after ``state_after`` tokens (all of them
    where it is None), None for a sparse layer. The feed-forward runs in
    blocks of ``ROWS`` rows."""
    z = sizes(cfg)
    f32 = lambda a: a.astype(jnp.float32)
    t = x.shape[0]
    normed = rms_norm(x, f32(lp["ln1"]), z["eps"])
    state, margin = None, jnp.full((t,), jnp.inf, jnp.float32)
    if "attn" in lp:
        mixed, margin = sparse_attention(lp["attn"], normed, cfg, quant, dense=dense)
    else:
        mixed, state = lightning(lp["lightning"], normed, cfg, log_lam, quant,
                                 decay_one=decay_one, length=state_after)
    x = x + z["alpha"] * mixed
    rows = _blocks(t, ROWS)

    def ffn(xb):
        return xb + z["alpha"] * mlp(lp["mlp"], rms_norm(xb, f32(lp["ln2"]), z["eps"]), quant)

    x = jax.lax.map(ffn, x.reshape(t // rows, rows, -1)).reshape(t, -1)
    return x, margin, state


def head_logits(params: dict, x, cfg: dict, quant: Optional[Callable] = None):
    """Final norm, the width ratio and the untied head over the rows ``x``
    (T, d)."""
    z = sizes(cfg)
    x = rms_norm(x, params["norm"].astype(jnp.float32), z["eps"]) / z["width_ratio"]
    return _mm(x, params["head"].astype(jnp.float32), quant)


def logits(params: dict, tokens, cfg: dict, quant: Optional[Callable] = None, **how):
    """``(logits (T, V), margin (T,))`` of ONE sequence ``tokens`` (T,):
    ``margin`` the smallest selection margin over the sparse layers, per
    position."""
    x = embed(params, tokens, cfg)
    margin = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
    for i in range(sizes(cfg)["L"]):
        x, m, _ = layer_forward(params["layers"][str(i)], x, cfg, layer_decay(cfg, i),
                                quant, **how)
        margin = jnp.minimum(margin, m)
    return head_logits(params, x, cfg, quant), margin


# -- operations -----------------------------------------------------------------

def matmul_params_per_token(cfg: dict) -> float:
    """Parameters one token multiplies: every layer's projections and
    feed-forward, and the head (norms, the recurrence and attention are not
    matrix multiplications of parameters)."""
    z = sizes(cfg)
    d = z["d"]
    sparse = 2 * d * z["H"] * z["head"] + 2 * d * z["Hkv"] * z["head"] + z["H"] * z["head"] * d
    light = 5 * d * z["Hl"] * z["dl"]
    ffn = 3 * d * z["inter"]
    mixers = sum(sparse if is_sparse(cfg, i) else light for i in range(z["L"]))
    return mixers + z["L"] * ffn + d * z["V"]


def attended_rows(cfg: dict, position: int) -> int:
    """Positions a sparse layer's query at ``position`` attends (per K/V
    head): all of ``0 .. position`` below ``dense_len``, else ``topk - 1``
    whole blocks and the part of its own up to it."""
    z = sizes(cfg)
    if position < z["dense_len"]:
        return position + 1
    return min(position + 1, (z["topk"] - 1) * z["block"] + position % z["block"] + 1)


def scored_units(cfg: dict, position: int) -> int:
    """Compressed keys a sparse query at ``position`` scores (0 below
    ``dense_len``)."""
    z = sizes(cfg)
    if position < z["dense_len"]:
        return 0
    return max(0, (position - z["kernel"] + 1) // z["stride"] + 1)


def serve_flops(cfg: dict, positions, pairs_per_token: Optional[float] = None) -> float:
    """Forward operations to process one token at each of ``positions``:
    twice the parameters it multiplies, the lightning recurrence (per head
    and state element the decay, the ``k^T v`` multiply-add and the ``q S``
    multiply-add: 5), and per sparse layer the unit scores (``2 * H *
    head`` a unit scored) and attention over the rows attended (``4 * H *
    head`` a row: scores and values). ``pairs_per_token`` is unused (a
    dense model)."""
    del pairs_per_token
    z = sizes(cfg)
    positions = [int(p) for p in positions]
    sparse_layers = sum(is_sparse(cfg, i) for i in range(z["L"]))
    light_layers = z["L"] - sparse_layers
    rows = sum(attended_rows(cfg, p) for p in positions)
    units = sum(scored_units(cfg, p) for p in positions)
    per_token = 2.0 * matmul_params_per_token(cfg) \
        + 5.0 * light_layers * z["Hl"] * z["dl"] * z["dl"]
    return per_token * len(positions) + sparse_layers * z["H"] * z["head"] * (
        4.0 * rows + 2.0 * units)
