"""Plain Qwen3-Next language model in ``jax.numpy``
(``Qwen3-Next-80B-A3B-Instruct``): weights from a seed and the forward pass
— float32, ``"highest"`` matmul precision, the delta rule as a literal
``lax.scan`` over tokens, no kernels, no cache, no batching, nothing
imported from ``rocket_tpu``.

Follows ``modeling_qwen3_next.py`` beside the source's ``config.json``
(recalled from memory: each point that could not be checked is under
``assumed`` in the configuration file) and Gated Delta Networks, arXiv
2412.06464:

* **Layer order.** Layer ``i`` is full attention where ``(i + 1) %
  full_attention_interval == 0`` (layers 3, 7, ...), a Gated DeltaNet
  elsewhere; every layer's feed-forward is routed (``decoder_sparse_step``
  1, ``mlp_only_layers`` empty). ``x = x + mixer(N(x)); x = x + ffn(N'(x))``
  with ``N`` the zero-centred RMSNorm ``x * rsqrt(mean(x^2) + eps) * (1 +
  w)``; a final ``N``; an untied head.
* **Gated attention.** Bias-free ``q`` and ``gate`` (heads x head each),
  ``k``, ``v`` (kv heads x head), ``o``; ``q`` and ``k`` through a
  zero-centred RMSNorm over the head's lanes (one weight for all heads);
  rotary (``rope_theta``, rotate-half) over the first
  ``partial_rotary_factor`` of each head's lanes, the rest untouched;
  causal softmax at scale ``head^-0.5``; query head ``j`` reads kv head ``j
  // (heads / kv heads)``; ``out = o(attention * sigmoid(gate))``.
* **Gated DeltaNet**, per token ``t``: ``q, k`` (key heads x 128), ``v, z``
  (value heads x 128), ``b, a`` (value heads); ``[q | k | v] =
  silu(conv1d([q | k | v]))`` — depthwise, causal, 4 taps, no bias;
  ``beta = sigmoid(b)``; ``g = -exp(A_log) * softplus(a + dt_bias)``; ``q =
  q / |q| * 128^-0.5``, ``k = k / |k|`` per head; key head ``j`` serves
  value heads ``2j`` and ``2j + 1``. Per value head, ``S`` (key x value):
  ``S = exp(g_t) S``; ``r = S^T k_t``; ``S = S + k_t (x) (beta_t (v_t -
  r))``; ``o_t = S^T q_t``; then ``o = RMSNorm(o) * w * silu(z)`` per head
  (plain weight) and ``out = out_proj(o)``.
* **Routed feed-forward.** ``p = softmax(W_r x)`` over all
  ``num_experts_published`` experts; the ``num_experts_per_tok`` largest;
  ``w = p_chosen / sum(p_chosen)``; ``y = sum_{chosen and held} w_i E_i(x)
  + sigmoid(w_sg . x) E_shared(x)``, ``E(x) = W_down(silu(W_gate x) * W_up
  x)``.

Departures from the source, each on purpose:

* **The chip's share.** Only ``config["num_experts"]`` experts are held
  (``experts_held_offset`` on, of the ``num_experts_published`` the router
  scores); what the absent experts would add is left out and the partial
  sum goes on. ``vocab_size`` rows of the vocabulary are kept.
  ``experts_held=(offset, count)`` of :func:`expert_layer` lets a test ask
  for any other share of the same weights, ``shared=False`` for the routed
  part alone.
* **Column order.** The source stores ``in_proj_qkvz`` and ``in_proj_ba``
  interleaved by key-head group, and ``q_proj`` as (head, [query | gate]);
  each is a fixed permutation of columns that random weights absorb: here
  every part is a matrix of its own, head by head.
* No multi-token-prediction module: the configuration file says why.

Attention runs in blocks of queries (a ``lax.map``) so that 16,384
positions fit; a held expert runs on the tokens that chose it (gathered up
to a capacity of an eighth of the sequence; on every token where more
did), which is the same sum as running every expert on every token at a
fiftieth of the operations.

``quant`` (the controls): a function applied to BOTH operands of every
matrix multiplication — :func:`fp8` rounds them to float8 e4m3 with a
per-tensor scale, the nearest precision below the bfloat16 the
configuration states for the weights. ``state_dtype``: the precision in
which ``S`` is CARRIED from token to token (the configuration states
float32; ``bfloat16`` is the control below it).
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

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
    head = cfg["head_dim"]
    return {
        "d": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
        "V": cfg["vocab_size"], "H": cfg["num_attention_heads"],
        "Hkv": cfg["num_key_value_heads"], "head": head,
        "rot": int(head * cfg["partial_rotary_factor"]),
        "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
        "interval": cfg["full_attention_interval"],
        "Hk": cfg["linear_num_key_heads"], "Hv": cfg["linear_num_value_heads"],
        "dk": cfg["linear_key_head_dim"], "dv": cfg["linear_value_head_dim"],
        "K": cfg["linear_conv_kernel_dim"],
        "E": int(cfg.get("num_experts_published", cfg["num_experts"])),
        "held": cfg["num_experts"],
        "offset": int(cfg.get("experts_held_offset", 0)),
        "k": cfg["num_experts_per_tok"], "expert": cfg["moe_intermediate_size"],
        "shared": cfg["shared_expert_intermediate_size"],
    }


def is_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


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


def layer_params(k, cfg: dict, attention: bool, dtype=jnp.float32,
                 all_experts: bool = False) -> dict:
    """One layer's weights from its key ``k`` (traceable). Scales are the
    configuration file's ``assumed.weights``."""
    z = sizes(cfg)
    d = z["d"]
    resid = 1.0 / math.sqrt(2 * z["L"])
    ks = jax.random.split(k, 24)
    # A zero-centred norm's weight is applied as 1 + w.
    centred = lambda key, n: _normal(key, (n,), 0.05, dtype)
    out = {"ln1": centred(ks[0], d), "ln2": centred(ks[1], d)}
    if attention:
        hq, hkv = z["H"] * z["head"], z["Hkv"] * z["head"]
        out["attn"] = {
            "w_q": _normal(ks[2], (d, hq), d ** -0.5, dtype),
            "w_gate": _normal(ks[3], (d, hq), d ** -0.5, dtype),
            "w_k": _normal(ks[4], (d, hkv), d ** -0.5, dtype),
            "w_v": _normal(ks[5], (d, hkv), d ** -0.5, dtype),
            "w_o": _normal(ks[6], (hq, d), hq ** -0.5 * resid, dtype),
            "q_norm": centred(ks[7], z["head"]),
            "k_norm": centred(ks[8], z["head"]),
        }
    else:
        kd, vd = z["Hk"] * z["dk"], z["Hv"] * z["dv"]
        # A head's decay at a = 0 is exp(-rate): rates log-uniform in
        # [1e-3, 0.5] put it between 0.6 and 0.999, fast and slow heads.
        rate = jnp.exp(jax.random.uniform(ks[14], (z["Hv"],), jnp.float32)
                       * (math.log(0.5) - math.log(1e-3)) + math.log(1e-3))
        out["gdn"] = {
            "w_q": _normal(ks[2], (d, kd), d ** -0.5, dtype),
            "w_k": _normal(ks[3], (d, kd), d ** -0.5, dtype),
            "w_v": _normal(ks[4], (d, vd), d ** -0.5, dtype),
            "w_z": _normal(ks[5], (d, vd), d ** -0.5, dtype),
            "w_b": _normal(ks[6], (d, z["Hv"]), d ** -0.5, dtype),
            "w_a": _normal(ks[7], (d, z["Hv"]), d ** -0.5, dtype),
            "conv_w": _normal(ks[8], (z["K"], 2 * kd + vd), z["K"] ** -0.5, dtype),
            # softplus(dt_bias) = 1 at the centre.
            "dt_bias": (math.log(math.e - 1.0) + 0.1 * jax.random.normal(
                ks[13], (z["Hv"],), jnp.float32)).astype(dtype),
            "a_log": jnp.log(rate).astype(dtype),
            "norm": (1.0 + 0.05 * jax.random.normal(
                ks[15], (z["dv"],), jnp.float32)).astype(dtype),
            "w_out": _normal(ks[16], (vd, d), vd ** -0.5 * resid, dtype),
        }
    # Every published expert has a key of its own, so a share holds the
    # same numbers whichever other experts are made beside it (a loop, not
    # a batch: a batched draw is another draw).
    ids = jnp.arange(z["E"]) if all_experts else z["offset"] + jnp.arange(z["held"])
    experts = jax.lax.map(
        lambda e: _expert(jax.random.fold_in(ks[17], e), d, z["expert"], resid, dtype),
        ids)
    a, b, c = jax.random.split(ks[18], 3)
    out["moe"] = {
        "w_r": _normal(ks[19], (d, z["E"]), d ** -0.5, dtype),
        "experts": experts,
        "shared": {
            "w_gate": _normal(a, (d, z["shared"]), d ** -0.5, dtype),
            "w_up": _normal(b, (d, z["shared"]), d ** -0.5, dtype),
            "w_down": _normal(c, (z["shared"], d), z["shared"] ** -0.5 * resid, dtype),
            "w_sg": _normal(ks[20], (d, 1), d ** -0.5, dtype),
        },
    }
    return out


def make_params(key, cfg: dict, dtype=jnp.float32, *, all_experts: bool = False,
                layer_jit: bool = False) -> dict:
    """Weights from ``key`` in ``dtype``: ``embed``, ``head`` (untied),
    ``norm``, one subtree per layer (``layers/<i>``), the held experts
    stacked. ``all_experts`` makes all the published experts (the test of
    the shares); the held ones are then ``[offset, offset + held)`` of
    them, the same numbers. ``layer_jit`` (call it eagerly then) makes each
    layer in a jitted call of its own, so that one layer's float32
    temporaries are alive at a time."""
    z = sizes(cfg)
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)

    def top(k_embed, k_head, k_norm):
        return {
            "embed": _normal(k_embed, (z["V"], z["d"]), 1.0, dtype),
            "head": _normal(k_head, (z["d"], z["V"]), z["d"] ** -0.5, dtype),
            "norm": _normal(k_norm, (z["d"],), 0.05, dtype),
        }

    def layer(k, attention):
        return layer_params(k, cfg, attention, dtype, all_experts)

    if layer_jit:
        top, layer = jax.jit(top), jax.jit(layer, static_argnums=1)
    out = top(k_embed, k_head, k_norm)
    out["layers"] = {
        str(i): layer(jax.random.fold_in(k_layers, i), is_attention(cfg, i))
        for i in range(z["L"])
    }
    return out


def program_params(params: dict, cfg: dict) -> dict:
    """The same weights in the PROGRAM's layout (``TransformerLM`` with
    Gated DeltaNet state layers, gated attention and routed experts): a
    renaming, and the projections of one input side by side as the
    program's one matrix (``[q | gate | k | v]``; ``[q | k | v | z]``;
    ``[b | a]``)."""
    side = lambda *ws: jnp.concatenate(ws, axis=1)
    blocks = {}
    for i, lp in params["layers"].items():
        m = lp["moe"]
        block = {
            "ln1": {"scale": lp["ln1"]}, "ln2": {"scale": lp["ln2"]},
            "moe": {"router": {"w": m["w_r"]}, "experts": m["experts"],
                    "shared": m["shared"]},
        }
        if "attn" in lp:
            a = lp["attn"]
            block["attn"] = {
                "qkv": {"w": side(a["w_q"], a["w_gate"], a["w_k"], a["w_v"])},
                "proj": {"w": a["w_o"]},
                "q_norm": {"scale": a["q_norm"]}, "k_norm": {"scale": a["k_norm"]},
            }
        else:
            g = lp["gdn"]
            block["mixer"] = {
                "in_proj_qkvz": {"w": side(g["w_q"], g["w_k"], g["w_v"], g["w_z"])},
                "in_proj_ba": {"w": side(g["w_b"], g["w_a"])},
                "conv": {"w": g["conv_w"]},
                "dt_bias": g["dt_bias"], "a_log": g["a_log"],
                "norm": {"scale": g["norm"]}, "out_proj": {"w": g["w_out"]},
            }
        blocks[i] = block
    return {
        "wte": {"table": params["embed"]}, "ln_f": {"scale": params["norm"]},
        "head": {"w": params["head"]}, "blocks": blocks,
    }


# -- the forward pass ---------------------------------------------------------

def rms_norm(x, w, eps, centred: bool = True):
    """``x * rsqrt(mean(x^2) + eps)`` times ``1 + w`` (the zero-centred
    form, the model's own everywhere but inside the DeltaNet) or ``w``."""
    scale = 1.0 + w if centred else w
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rope(x, cfg: dict):
    """Rotate-half rotary embedding over the first ``rot`` lanes of each
    head of ``x`` (T, heads, head), positions ``0 .. T``."""
    z = sizes(cfg)
    rot = z["rot"]
    half = rot // 2
    freqs = z["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], axis=-1)


def attention(p, x, cfg: dict, quant=None, *, query_block: int = 256):
    """Causal gated grouped-query attention of ONE sequence ``x`` (T, d),
    in blocks of queries (a ``lax.map``) so that 16,384 positions of 16
    heads fit; every key is seen by every block."""
    z = sizes(cfg)
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    q = _mm(x, f32(p["w_q"]), quant).reshape(t, z["H"], z["head"])
    gate = _mm(x, f32(p["w_gate"]), quant)
    k = _mm(x, f32(p["w_k"]), quant).reshape(t, z["Hkv"], z["head"])
    v = _mm(x, f32(p["w_v"]), quant).reshape(t, z["Hkv"], z["head"])
    q = rope(rms_norm(q, f32(p["q_norm"]), z["eps"]), cfg)
    k = rope(rms_norm(k, f32(p["k_norm"]), z["eps"]), cfg)
    group = z["H"] // z["Hkv"]
    kq, vq = (k, v) if quant is None else (quant(k), quant(v))
    block = min(query_block, t)
    if t % block:
        raise ValueError(f"attention: {t} positions are not whole blocks of {block}")

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        qb = (qb if quant is None else quant(qb)).reshape(
            block, z["Hkv"], group, z["head"])
        s = jnp.einsum("qkgd,tkd->kgqt", qb, kq, precision=HIGHEST) * z["head"] ** -0.5
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        w = w if quant is None else quant(w)
        return jnp.einsum("kgqt,tkd->qkgd", w, vq, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, z["H"] * z["head"])
    return _mm(out * jax.nn.sigmoid(gate), f32(p["w_o"]), quant)


def conv1d(u, w):
    """Depthwise causal convolution along time: ``u`` (T, C), ``w`` (K, C),
    tap ``K - 1`` on the current token, zeros before the first; no bias."""
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u], axis=0)
    return sum(w[k] * padded[k:k + u.shape[0]] for k in range(taps))


def l2_norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_rule(q, k, v, g, beta, state_dtype=jnp.float32, length=None):
    """The gated delta rule, token by token: ``q``, ``k`` (T, Hv, dk);
    ``v`` (T, Hv, dv); ``g``, ``beta`` (T, Hv). Returns ``(o (T, Hv, dv),
    S (Hv, dk, dv))``: the state after the first ``length`` tokens (all
    ``T`` where it is None: the rows past ``length`` are padding, whose
    ``o`` nobody reads). ``S`` is carried in ``state_dtype``."""

    def step(s, xs):
        i, qt, kt, vt, gt, bt = xs
        s2 = jnp.exp(gt)[:, None, None] * s.astype(jnp.float32)
        r = jnp.einsum("hkv,hk->hv", s2, kt, precision=HIGHEST)
        s2 = s2 + kt[:, :, None] * (bt[:, None] * (vt - r))[:, None, :]
        s2 = s2.astype(state_dtype)
        if length is not None:
            s2 = jnp.where(i < length, s2, s)
        return s2, jnp.einsum("hkv,hk->hv", s2.astype(jnp.float32), qt,
                              precision=HIGHEST)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), state_dtype)
    s, o = jax.lax.scan(step, s0, (jnp.arange(q.shape[0]), q, k, v, g, beta))
    return o, s


def gated_delta_net(p, x, cfg: dict, quant=None, state_dtype=jnp.float32,
                    length=None):
    """The Gated DeltaNet mixer of ONE sequence ``x`` (T, d): ``(out, S)``,
    the state as :func:`delta_rule` returns it."""
    z = sizes(cfg)
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    kd = z["Hk"] * z["dk"]
    mixed = jnp.concatenate(
        [_mm(x, f32(p[w]), quant) for w in ("w_q", "w_k", "w_v")], axis=1)
    gate = _mm(x, f32(p["w_z"]), quant).reshape(t, z["Hv"], z["dv"])
    beta = jax.nn.sigmoid(_mm(x, f32(p["w_b"]), quant))
    g = -jnp.exp(f32(p["a_log"])) * jax.nn.softplus(
        _mm(x, f32(p["w_a"]), quant) + f32(p["dt_bias"]))
    mixed = jax.nn.silu(conv1d(mixed, f32(p["conv_w"])))
    per_value_head = lambda a: jnp.repeat(
        a.reshape(t, z["Hk"], z["dk"]), z["Hv"] // z["Hk"], axis=1)
    q = l2_norm(per_value_head(mixed[:, :kd])) * z["dk"] ** -0.5
    k = l2_norm(per_value_head(mixed[:, kd:2 * kd]))
    v = mixed[:, 2 * kd:].reshape(t, z["Hv"], z["dv"])
    o, s = delta_rule(q, k, v, g, beta, state_dtype, length)
    o = rms_norm(o, f32(p["norm"]), z["eps"], centred=False) * jax.nn.silu(gate)
    return _mm(o.reshape(t, -1), f32(p["w_out"]), quant), s


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
    router of the module's docstring, and per token how near the choice is
    to another one THAT THIS CHIP WOULD FEEL: the smallest distance, in
    router logits, of a held expert from the boundary it would have to
    cross (a chosen one from the (k+1)-th best, one not chosen from the
    k-th best). Two absent experts that change places leave this chip's
    sum as it was but for the weights' common denominator, which moves by
    less than their distance."""
    z = sizes(cfg)
    t = x.shape[0]
    offset, count = experts_held or (z["offset"], z["held"])
    logits = _mm(x, p["w_r"].astype(jnp.float32), quant)
    probs = jax.nn.softmax(logits, axis=-1)
    top, experts = jax.lax.top_k(logits, z["k"] + 1)
    experts = experts[:, :z["k"]]
    w = jnp.take_along_axis(probs, experts, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    held = (jnp.arange(z["E"]) >= offset) & (jnp.arange(z["E"]) < offset + count)
    chosen = jnp.zeros((t, z["E"]), bool).at[jnp.arange(t)[:, None], experts].set(True)
    distance = jnp.where(chosen, logits - top[:, z["k"]:],
                         top[:, z["k"] - 1:z["k"]] - logits)
    margin = jnp.min(jnp.where(held[None, :], distance, jnp.inf), axis=1)
    return w, experts, margin


def expert_layer(p, x, cfg: dict, quant=None, *, experts_held=None,
                 shared: bool = True):
    """``(y (T, d), margin (T,))``: the held experts' part of the routed
    sum plus (``shared``) the gated shared expert. Each held expert runs on
    the tokens that chose it — gathered, up to a capacity of an eighth of
    the sequence; on every token, weighted by 0 where it was not chosen,
    if more did: the same sum either way. ``p["experts"]`` holds the
    experts ``[offset, offset + count)`` of ``experts_held`` (default: the
    configuration's share), stacked."""
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
        sh = p["shared"]
        y = y + jax.nn.sigmoid(_mm(x, sh["w_sg"].astype(jnp.float32), quant)) \
            * swiglu(sh, x, quant)
    return y, margin


def embed(params: dict, tokens):
    return params["embed"].astype(jnp.float32)[tokens]


def layer_forward(lp: dict, x, cfg: dict, quant: Optional[Callable] = None,
                  state_dtype=jnp.float32, state_after=None):
    """One pre-norm residual block on ONE sequence ``x`` (T, d): ``(x',
    margin (T,), S)`` — ``margin`` the router's (:func:`route`), ``S`` the
    state a DeltaNet layer holds after ``state_after`` tokens (all of them
    where it is None) and None for an attention layer. A driver that jits
    this once per kind of layer keeps one layer's float32 temporaries
    alive at a time."""
    z = sizes(cfg)
    f32 = lambda a: a.astype(jnp.float32)
    normed, state = rms_norm(x, f32(lp["ln1"]), z["eps"]), None
    if "attn" in lp:
        x = x + attention(lp["attn"], normed, cfg, quant)
    else:
        mixed, state = gated_delta_net(
            lp["gdn"], normed, cfg, quant, state_dtype, state_after)
        x = x + mixed
    y, margin = expert_layer(lp["moe"], rms_norm(x, f32(lp["ln2"]), z["eps"]),
                             cfg, quant)
    return x + y, margin, state


def head_logits(params: dict, x, cfg: dict, quant: Optional[Callable] = None):
    """Final norm and the untied head over the rows ``x`` (T, d)."""
    x = rms_norm(x, params["norm"].astype(jnp.float32), sizes(cfg)["eps"])
    return _mm(x, params["head"].astype(jnp.float32), quant)


def logits(params: dict, tokens, cfg: dict, quant: Optional[Callable] = None,
           state_dtype=jnp.float32, layer_fn: Optional[Callable] = None):
    """``(logits (T, V), margin (T,))`` of ONE sequence ``tokens`` (T,):
    ``margin`` is the smallest router margin over the layers, per
    position. ``layer_fn`` replaces :func:`layer_forward` (a jitted one)."""
    layer_fn = layer_fn or (
        lambda lp, x: layer_forward(lp, x, cfg, quant, state_dtype))
    x = embed(params, tokens)
    margin = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
    for i in range(sizes(cfg)["L"]):
        x, m, _ = layer_fn(params["layers"][str(i)], x)
        margin = jnp.minimum(margin, m)
    return head_logits(params, x, cfg, quant), margin


# -- operations (this chip's share) -------------------------------------------

def matmul_params_per_token(cfg: dict, pairs_per_token: float) -> float:
    """Parameters one token multiplies on this chip: the mixer of every
    layer, the router, the shared expert and its gate, ``pairs_per_token``
    held experts a layer, the sliced head (the convolution, the norms and
    the delta rule are not matrix multiplications)."""
    z = sizes(cfg)
    d = z["d"]
    attn = d * z["H"] * z["head"] * 2 + 2 * d * z["Hkv"] * z["head"] \
        + z["H"] * z["head"] * d
    kd, vd = z["Hk"] * z["dk"], z["Hv"] * z["dv"]
    gdn = d * (2 * kd + 2 * vd) + d * 2 * z["Hv"] + vd * d
    mixers = sum(attn if is_attention(cfg, i) else gdn for i in range(z["L"]))
    ffn = d * z["E"] + 3 * d * z["shared"] + d + pairs_per_token * 3 * d * z["expert"]
    return mixers + z["L"] * ffn + d * z["V"]


def rule_flops_per_token(cfg: dict) -> float:
    """Operations of the delta rule for one token, over the DeltaNet
    layers: per value head and element of ``S`` the decay, a multiply and
    an add into ``r``, a multiply and an add into ``S``, a multiply and an
    add into ``o`` (7)."""
    z = sizes(cfg)
    layers = sum(not is_attention(cfg, i) for i in range(z["L"]))
    return float(layers * 7 * z["Hv"] * z["dk"] * z["dv"])


def serve_flops(cfg: dict, positions, pairs_per_token: Optional[float] = None) -> float:
    """Forward operations this chip needs to process one token at each of
    ``positions``: twice the parameters it multiplies (of the routed
    experts only the pairs that fall to held ones; ``pairs_per_token``
    defaults to even routing, ``k * held / E``), attention over the
    ``position + 1`` live rows in the attention layers (``4 * H * head`` a
    row a layer: scores and values, two operations a multiply-add) and the
    delta rule's."""
    z = sizes(cfg)
    if pairs_per_token is None:
        pairs_per_token = z["k"] * z["held"] / z["E"]
    positions = [int(p) for p in positions]
    attended = sum(positions) + len(positions)
    attn_layers = sum(is_attention(cfg, i) for i in range(z["L"]))
    return (
        (2.0 * matmul_params_per_token(cfg, pairs_per_token)
         + rule_flops_per_token(cfg)) * len(positions)
        + 4.0 * attn_layers * z["H"] * z["head"] * attended
    )
