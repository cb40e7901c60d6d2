"""Plain Laguna language model in ``jax.numpy`` (``Laguna-XS.2``): weights
from a seed and the forward pass — float32, ``"highest"`` matmul
precision, no kernels, no cache, no batching, nothing imported from
``rocket_tpu``.

Follows the source's ``config.json`` (the configuration file's
``assumed`` holds each point the file does not state):

* **Layers.** Layer ``i`` is of the kind ``layer_types[i]`` —
  ``full_attention`` or ``sliding_attention`` — with
  ``num_attention_heads_per_layer[i]`` query heads over
  ``num_key_value_heads`` K/V heads of ``head_dim``, and its feed-forward
  ``mlp_layer_types[i]``: ``dense`` (SwiGLU of ``intermediate_size``) or
  ``sparse`` (routed). ``x = x + attn(N(x)); x = x + ffn(N'(x))`` with
  ``N`` the RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``; a final ``N``; an
  untied head.
* **Attention.** Bias-free ``q`` (heads x head), ``k``, ``v`` (kv heads x
  head), ``o`` and a gate ``g`` of ONE scalar a head; rotate-half rotary
  over the first ``partial_rotary_factor`` of each head's lanes with the
  kind's ``rope_parameters`` (YaRN frequencies for ``rope_type`` yarn,
  cos and sin times ``attention_factor``; plain ``theta^(-2i/rot)``
  otherwise); softmax at scale ``head^-0.5`` over the keys ``j <= i``
  (full) or ``i - sliding_window < j <= i`` (sliding); query head ``h``
  reads kv head ``h // (heads / kv heads)``; ``out = o([sigmoid(x g_h) *
  o_h]_h)``.
* **Routed feed-forward.** ``p = softmax(W_r x)`` over all
  ``num_experts_published`` experts; the ``num_experts_per_tok`` largest;
  ``w = p_chosen / sum(p_chosen) * moe_routed_scaling_factor``; ``y =
  sum_{chosen and held} w_i E_i(x) + E_shared(x)``, ``E(x) =
  W_down(silu(W_gate x) * W_up x)``.

Departures from the source, each on purpose:

* **The chip's share.** Only ``config["num_experts"]`` experts are held
  (``experts_held_offset`` on, of the ``num_experts_published`` the router
  scores); what the absent experts would add is left out and the partial
  sum goes on. ``vocab_size`` rows of the vocabulary are kept. The first
  ``num_hidden_layers`` entries of each per-layer list are read.
* **Rotary constants.** YaRN's ``factor`` is the ``rope_parameters``'
  own, not ``max_position_embeddings`` over the original context: the cut
  of ``max_position_embeddings`` moves no rotation.

Attention runs in blocks of queries (a ``lax.map``) so that 16,384
positions of 64 heads fit; a sliding layer's block reads only the
``sliding_window`` keys before it and its own. A held expert runs on the
tokens that chose it (gathered up to a capacity of an eighth of the
sequence; on every token where more did), the same sum as every expert on
every token.

``quant`` (the fp8 control): a function applied to BOTH operands of every
matrix multiplication — :func:`fp8` rounds them to float8 e4m3 with a
per-tensor scale, the nearest precision below the bfloat16 the
configuration states. ``window=False`` (the window control): the sliding
layers attend the whole context, as a build that ignored the window would.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


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
    return {
        "d": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
        "V": cfg["vocab_size"], "Hkv": cfg["num_key_value_heads"],
        "head": cfg["head_dim"], "eps": cfg["rms_norm_eps"],
        "dense": cfg["intermediate_size"], "window": cfg["sliding_window"],
        "E": int(cfg.get("num_experts_published", cfg["num_experts"])),
        "held": cfg["num_experts"],
        "offset": int(cfg.get("experts_held_offset", 0)),
        "k": cfg["num_experts_per_tok"], "expert": cfg["moe_intermediate_size"],
        "shared": cfg["shared_expert_intermediate_size"],
        "scaling": float(cfg["moe_routed_scaling_factor"]),
    }


def kind(cfg: dict, i: int) -> str:
    """``full_attention`` or ``sliding_attention``."""
    return cfg["layer_types"][i]


def heads(cfg: dict, i: int) -> int:
    return cfg["num_attention_heads_per_layer"][i]


def is_dense(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "dense"


def rope_of(cfg: dict, i: int) -> dict:
    """Layer ``i``'s rotary: ``rot`` lanes, ``inv_freq`` (rot / 2,) and the
    factor on cos and sin, from its kind's ``rope_parameters``."""
    rp = cfg["rope_parameters"][kind(cfg, i)]
    rot = int(cfg["head_dim"] * rp.get("partial_rotary_factor", 1.0))
    base = float(rp["rope_theta"])
    half = rot // 2
    plain = base ** (-np.arange(half, dtype=np.float64) * 2 / rot)
    if rp.get("rope_type", "default") == "default":
        return {"rot": rot, "inv_freq": plain.astype(np.float32), "scale": 1.0}
    if rp["rope_type"] != "yarn":
        raise ValueError(f"laguna_lm: unknown rope_type {rp['rope_type']!r}")
    factor, orig = float(rp["factor"]), float(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return rot * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    # 1 where a lane pair turns slowly (interpolated), 0 where fast (kept).
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0, 1)
    inv_freq = plain / factor * ramp + plain * (1 - ramp)
    scale = rp.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return {"rot": rot, "inv_freq": inv_freq.astype(np.float32), "scale": float(scale)}


# -- weights ----------------------------------------------------------------

def _normal(k, shape, s, dtype):
    return (s * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def _expert(k, d, width, resid, dtype):
    """A routed expert: ``W_gate`` and ``W_up`` are the two halves of ONE
    stored matrix ``w_gate_up`` (d, 2 * width), the layout the program's
    grouped matmul reads; :func:`swiglu` takes them apart again."""
    a, c = jax.random.split(k)
    return {
        "w_gate_up": _normal(a, (d, 2 * width), d ** -0.5, dtype),
        "w_down": _normal(c, (width, d), width ** -0.5 * resid, dtype),
    }


def _norm_weight(k, n, dtype):
    return (1.0 + 0.05 * jax.random.normal(k, (n,), jnp.float32)).astype(dtype)


def layer_params(k, cfg: dict, h: int, dense: bool, dtype=jnp.float32,
                 all_experts: bool = False) -> dict:
    """One layer's weights from its key ``k`` (traceable): ``h`` query
    heads, a dense or a routed feed-forward. Scales are the configuration
    file's ``assumed.weights``."""
    z = sizes(cfg)
    d = z["d"]
    resid = 1.0 / math.sqrt(2 * z["L"])
    ks = jax.random.split(k, 16)
    hq, hkv = h * z["head"], z["Hkv"] * z["head"]
    out = {
        "ln1": _norm_weight(ks[0], d, dtype), "ln2": _norm_weight(ks[1], d, dtype),
        "attn": {
            "w_q": _normal(ks[2], (d, hq), d ** -0.5, dtype),
            "w_k": _normal(ks[3], (d, hkv), d ** -0.5, dtype),
            "w_v": _normal(ks[4], (d, hkv), d ** -0.5, dtype),
            "w_g": _normal(ks[5], (d, h), d ** -0.5, dtype),
            "w_o": _normal(ks[6], (hq, d), hq ** -0.5 * resid, dtype),
        },
    }
    if dense:
        out["mlp"] = {
            "w_gate": _normal(ks[7], (d, z["dense"]), d ** -0.5, dtype),
            "w_up": _normal(ks[8], (d, z["dense"]), d ** -0.5, dtype),
            "w_down": _normal(ks[9], (z["dense"], d), z["dense"] ** -0.5 * resid, dtype),
        }
        return out
    # Every published expert has a key of its own, so a share holds the
    # same numbers whichever other experts are made beside it.
    ids = jnp.arange(z["E"]) if all_experts else z["offset"] + jnp.arange(z["held"])
    experts = jax.lax.map(
        lambda e: _expert(jax.random.fold_in(ks[10], e), d, z["expert"], resid, dtype),
        ids)
    a, b, c = jax.random.split(ks[11], 3)
    out["moe"] = {
        "w_r": _normal(ks[12], (d, z["E"]), d ** -0.5, dtype),
        "experts": experts,
        "shared": {
            "w_gate": _normal(a, (d, z["shared"]), d ** -0.5, dtype),
            "w_up": _normal(b, (d, z["shared"]), d ** -0.5, dtype),
            "w_down": _normal(c, (z["shared"], d), z["shared"] ** -0.5 * resid, dtype),
        },
    }
    return out


def make_params(key, cfg: dict, dtype=jnp.float32, *, all_experts: bool = False,
                layer_jit: bool = False) -> dict:
    """Weights from ``key`` in ``dtype``: ``embed``, ``head`` (untied),
    ``norm``, one subtree per layer (``layers/<i>``), the held experts
    stacked. ``all_experts`` makes all the published experts (the test of
    the shares). ``layer_jit`` (call it eagerly then) makes each layer in a
    jitted call of its own, so that one layer's float32 temporaries are
    alive at a time."""
    z = sizes(cfg)
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)

    def top(k_embed, k_head, k_norm):
        return {
            "embed": _normal(k_embed, (z["V"], z["d"]), 1.0, dtype),
            "head": _normal(k_head, (z["d"], z["V"]), z["d"] ** -0.5, dtype),
            "norm": _norm_weight(k_norm, z["d"], dtype),
        }

    def layer(k, h, dense):
        return layer_params(k, cfg, h, dense, dtype, all_experts)

    if layer_jit:
        top, layer = jax.jit(top), jax.jit(layer, static_argnums=(1, 2))
    out = top(k_embed, k_head, k_norm)
    out["layers"] = {
        str(i): layer(jax.random.fold_in(k_layers, i), heads(cfg, i), is_dense(cfg, i))
        for i in range(z["L"])
    }
    return out


def program_params(params: dict, cfg: dict) -> dict:
    """The same weights in the PROGRAM's layout (``TransformerLM`` with a
    head gate and routed experts): a renaming, and the projections of one
    input side by side as the program's one matrix ``[q | k | v | g]``."""
    side = lambda *ws: jnp.concatenate(ws, axis=1)
    blocks = {}
    for i, lp in params["layers"].items():
        a = lp["attn"]
        block = {
            "ln1": {"scale": lp["ln1"]}, "ln2": {"scale": lp["ln2"]},
            "attn": {"qkv": {"w": side(a["w_q"], a["w_k"], a["w_v"], a["w_g"])},
                     "proj": {"w": a["w_o"]}},
        }
        if "mlp" in lp:
            m = lp["mlp"]
            block["mlp"] = {"fc_gate": {"w": m["w_gate"]}, "fc_in": {"w": m["w_up"]},
                            "fc_out": {"w": m["w_down"]}}
        else:
            m = lp["moe"]
            block["moe"] = {"router": {"w": m["w_r"]}, "experts": m["experts"],
                            "shared": m["shared"]}
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


def rope(x, r: dict):
    """Rotate-half rotary over the first ``r["rot"]`` lanes of each head of
    ``x`` (T, heads, head) at positions ``0 .. T``; cos and sin times
    ``r["scale"]``."""
    rot, half = r["rot"], r["rot"] // 2
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(r["inv_freq"])
    cos = jnp.cos(angles)[:, None, :] * r["scale"]
    sin = jnp.sin(angles)[:, None, :] * r["scale"]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], axis=-1)


def attention(p, x, cfg: dict, i: int, quant=None, *, window: bool = True,
              query_block: int = 64):
    """Layer ``i``'s gated grouped-query attention of ONE sequence ``x``
    (T, d), in blocks of queries (a ``lax.map``). A full layer's block
    reads every key; a sliding one's the ``sliding_window`` keys before
    the block and the block's own (``window=False``: every key, the
    window control)."""
    z = sizes(cfg)
    t, h, hkv, head = x.shape[0], heads(cfg, i), z["Hkv"], z["head"]
    f32 = lambda a: a.astype(jnp.float32)
    r = rope_of(cfg, i)
    q = rope(_mm(x, f32(p["w_q"]), quant).reshape(t, h, head), r)
    k = rope(_mm(x, f32(p["w_k"]), quant).reshape(t, hkv, head), r)
    v = _mm(x, f32(p["w_v"]), quant).reshape(t, hkv, head)
    gate = jax.nn.sigmoid(_mm(x, f32(p["w_g"]), quant))                # (T, H)
    group = h // hkv
    kq, vq = (k, v) if quant is None else (quant(k), quant(v))
    block = min(query_block, t)
    if t % block:
        raise ValueError(f"attention: {t} positions are not whole blocks of {block}")
    w = z["window"] if window and kind(cfg, i) == "sliding_attention" else 0
    if w:
        # Keys of positions [start - w, start + block): w rows of zeros in
        # front so that every block reads the same number of rows.
        kq = jnp.concatenate([jnp.zeros((w,) + kq.shape[1:], kq.dtype), kq])
        vq = jnp.concatenate([jnp.zeros((w,) + vq.shape[1:], vq.dtype), vq])
    span = w + block if w else t

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        qb = (qb if quant is None else quant(qb)).reshape(block, hkv, group, head)
        first = start - w if w else 0
        kb = jax.lax.dynamic_slice_in_dim(kq, start if w else 0, span, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(vq, start if w else 0, span, axis=0)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, kb, precision=HIGHEST) * head ** -0.5
        key_pos = first + jnp.arange(span)[None, :]
        q_pos = (start + jnp.arange(block))[:, None]
        seen = (key_pos <= q_pos) & (key_pos >= 0)
        if w:
            seen &= key_pos > q_pos - w
        wts = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        wts = wts if quant is None else quant(wts)
        return jnp.einsum("kgqt,tkd->qkgd", wts, vb, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, h, head)
    out = (out * gate[:, :, None]).reshape(t, h * head)
    return _mm(out, f32(p["w_o"]), quant)


def swiglu(f, x, quant=None):
    """``W_down(silu(W_gate x) * W_up x)``; ``f`` holds ``w_gate`` and
    ``w_up``, or the two side by side as ``w_gate_up``."""
    f32 = lambda a: a.astype(jnp.float32)
    if "w_gate_up" in f:
        both = _mm(x, f32(f["w_gate_up"]), quant)
        width = both.shape[-1] // 2
        hidden = jax.nn.silu(both[..., :width]) * both[..., width:]
    else:
        hidden = jax.nn.silu(_mm(x, f32(f["w_gate"]), quant)) * _mm(x, f32(f["w_up"]), quant)
    return _mm(hidden, f32(f["w_down"]), quant)


def route(p, x, cfg: dict, quant=None, *, experts_held=None):
    """``(weights (T, k), experts (T, k), margin (T,))``: the softmax
    router of the module's docstring (the weights scaled by
    ``moe_routed_scaling_factor``), and per token how near the choice is to
    another one THAT THIS CHIP WOULD FEEL: the smallest distance, in router
    logits, of a held expert from the boundary it would have to cross (a
    chosen one from the (k+1)-th best, one not chosen from the k-th
    best)."""
    z = sizes(cfg)
    t = x.shape[0]
    offset, count = experts_held or (z["offset"], z["held"])
    logits = _mm(x, p["w_r"].astype(jnp.float32), quant)
    probs = jax.nn.softmax(logits, axis=-1)
    top, experts = jax.lax.top_k(logits, z["k"] + 1)
    experts = experts[:, :z["k"]]
    w = jnp.take_along_axis(probs, experts, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True) * z["scaling"]
    held = (jnp.arange(z["E"]) >= offset) & (jnp.arange(z["E"]) < offset + count)
    chosen = jnp.zeros((t, z["E"]), bool).at[jnp.arange(t)[:, None], experts].set(True)
    distance = jnp.where(chosen, logits - top[:, z["k"]:],
                         top[:, z["k"] - 1:z["k"]] - logits)
    margin = jnp.min(jnp.where(held[None, :], distance, jnp.inf), axis=1)
    return w, experts, margin


def expert_layer(p, x, cfg: dict, quant=None, *, experts_held=None,
                 shared: bool = True):
    """``(y (T, d), margin (T,))``: the held experts' part of the routed
    sum plus (``shared``) the ungated shared expert. Each held expert runs
    on the tokens that chose it — gathered, up to a capacity of an eighth of
    the sequence; on every token, weighted by 0 where it was not chosen, if
    more did: the same sum either way."""
    z = sizes(cfg)
    t = x.shape[0]
    offset, count = experts_held or (z["offset"], z["held"])
    w, experts, margin = route(p, x, cfg, quant, experts_held=(offset, count))
    capacity = min(t, max(8, t // 8))

    def one(y, xs):
        f, e = xs
        weight = jnp.sum(jnp.where(experts == e, w, 0.0), axis=1)       # (T,)

        def gathered(y):
            rows = jnp.nonzero(weight > 0, size=capacity, fill_value=t)[0]
            took = jnp.take(x, rows, axis=0, mode="fill", fill_value=0.0)
            out = swiglu(f, took, quant) * jnp.take(
                weight, rows, mode="fill", fill_value=0.0)[:, None]
            return y.at[rows].add(out, mode="drop")

        def every(y):
            return y + weight[:, None] * swiglu(f, x, quant)

        return jax.lax.cond(jnp.sum(weight > 0) <= capacity, gathered, every, y), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["experts"], offset + jnp.arange(count)))
    if shared:
        y = y + swiglu(p["shared"], x, quant)
    return y, margin


def embed(params: dict, tokens):
    return params["embed"].astype(jnp.float32)[tokens]


def layer_forward(lp: dict, x, cfg: dict, i: int, quant: Optional[Callable] = None,
                  window: bool = True):
    """Layer ``i`` on ONE sequence ``x`` (T, d): ``(x', margin (T,))`` —
    ``margin`` the router's (:func:`route`; ``inf`` for the dense layer).
    A driver that jits this once per kind of layer keeps one layer's
    float32 temporaries alive at a time."""
    z = sizes(cfg)
    f32 = lambda a: a.astype(jnp.float32)
    x = x + attention(lp["attn"], rms_norm(x, f32(lp["ln1"]), z["eps"]), cfg, i,
                      quant, window=window)
    normed = rms_norm(x, f32(lp["ln2"]), z["eps"])
    if "mlp" in lp:
        return x + swiglu(lp["mlp"], normed, quant), jnp.full((x.shape[0],), jnp.inf)
    y, margin = expert_layer(lp["moe"], normed, cfg, quant)
    return x + y, margin


def head_logits(params: dict, x, cfg: dict, quant: Optional[Callable] = None):
    """Final norm and the untied head over the rows ``x`` (T, d)."""
    x = rms_norm(x, params["norm"].astype(jnp.float32), sizes(cfg)["eps"])
    return _mm(x, params["head"].astype(jnp.float32), quant)


def logits(params: dict, tokens, cfg: dict, quant: Optional[Callable] = None,
           window: bool = True):
    """``(logits (T, V), margin (T,))`` of ONE sequence ``tokens`` (T,):
    ``margin`` is the smallest router margin over the layers, per
    position."""
    x = embed(params, tokens)
    margin = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
    for i in range(sizes(cfg)["L"]):
        x, m = layer_forward(params["layers"][str(i)], x, cfg, i, quant, window)
        margin = jnp.minimum(margin, m)
    return head_logits(params, x, cfg, quant), margin


# -- operations (this chip's share) -------------------------------------------

def matmul_params_per_token(cfg: dict, pairs_per_token: float) -> float:
    """Parameters one token multiplies on this chip: every layer's
    attention (q, k, v, the gate, o) and feed-forward — the dense one, or
    the router, the shared expert and ``pairs_per_token`` held experts —
    and the sliced head (the norms are not matrix multiplications)."""
    z = sizes(cfg)
    d, head = z["d"], z["head"]
    total = d * z["V"]
    for i in range(z["L"]):
        hq = heads(cfg, i) * head
        total += 2 * d * hq + 2 * d * z["Hkv"] * head + d * heads(cfg, i)
        if is_dense(cfg, i):
            total += 3 * d * z["dense"]
        else:
            total += d * z["E"] + 3 * d * z["shared"] + pairs_per_token * 3 * d * z["expert"]
    return total


def attended_rows(cfg: dict, i: int, position: int) -> int:
    """The keys a query at ``position`` reads in layer ``i``: ``position +
    1``, at most ``sliding_window`` in a sliding layer."""
    rows = position + 1
    if kind(cfg, i) == "sliding_attention":
        rows = min(rows, sizes(cfg)["window"])
    return rows


def serve_flops(cfg: dict, positions, pairs_per_token: Optional[float] = None) -> float:
    """Forward operations this chip needs to process one token at each of
    ``positions``: twice the parameters it multiplies (of the routed
    experts only the pairs that fall to held ones; ``pairs_per_token``
    defaults to even routing, ``k * held / E``), and in each layer
    attention over the rows :func:`attended_rows` gives (``4 * H_i *
    head`` a row: scores and values, two operations a multiply-add)."""
    z = sizes(cfg)
    if pairs_per_token is None:
        pairs_per_token = z["k"] * z["held"] / z["E"]
    positions = [int(p) for p in positions]
    attention = 0.0
    for i in range(z["L"]):
        w = z["window"] if kind(cfg, i) == "sliding_attention" else None
        rows = sum(p + 1 if w is None else min(p + 1, w) for p in positions)
        attention += 4.0 * heads(cfg, i) * z["head"] * rows
    return 2.0 * matmul_params_per_token(cfg, pairs_per_token) * len(positions) + attention
