"""Plain Jamba language model in ``jax.numpy`` (``AI21-Jamba2-3B``): weights
from a seed and the forward pass — float32, ``"highest"`` matmul precision,
a literal ``lax.scan`` over tokens for the recurrence, no kernels, no cache,
no batching, nothing imported from ``rocket_tpu``.

Follows ``modeling_jamba.py`` beside the source's ``config.json`` (recalled
from memory: each point that could not be checked is under ``assumed`` in
the configuration file) and Mamba, arXiv 2312.00752 §3:

* **Layer order.** Layer ``i`` is attention where ``i % attn_layer_period
  == attn_layer_offset`` (14, 7: layers 7 and 21 of 28), a Mamba-1 mixer
  elsewhere. ``num_experts`` 1 makes every feed-forward the plain gated
  MLP. Every layer: ``x = x + mixer(RMSNorm(x)); x = x + down(silu(gate(h))
  * up(h))`` with ``h = RMSNorm(x)``; final RMSNorm; logits through the
  tied embedding. No positional embedding and no rotation anywhere: the
  mixers give the order.
* **Attention.** Bias-free ``q`` (heads x head), ``k``, ``v`` (kv heads x
  head), ``o``; causal softmax at scale ``head^-0.5``; query head ``j``
  reads kv head ``j // (heads / kv heads)``.
* **Mamba-1 mixer**, per token ``t``: ``[u, z] = in_proj(x)``; ``u =
  silu(conv1d(u))`` — depthwise, causal, ``mamba_d_conv`` taps, with bias;
  ``[dt, B, C] = x_proj(u)``; ``dt``, ``B``, ``C`` each through an RMSNorm
  of its own (Jamba's addition); ``delta = softplus(dt_proj(dt))`` (with
  bias); ``A = -exp(a_log)``; ``h_t = exp(delta_t (x) A) * h_{t-1} +
  (delta_t * u_t) (x) B_t``; ``y_t = h_t . C_t + D * u_t``; ``out =
  out_proj(y * silu(z))``.

``quant`` (the controls): a function applied to BOTH operands of every
matrix multiplication — :func:`fp8` rounds them to float8 e4m3 with a
per-tensor scale, the nearest precision below the bfloat16 the
configuration states for the weights. ``state_dtype``: the precision in
which ``h`` is CARRIED from token to token (the configuration states
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
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "d": d, "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
        "H": heads, "Hkv": cfg["num_key_value_heads"], "head": d // heads,
        "inter": cfg["intermediate_size"],
        "Di": cfg["mamba_expand"] * d, "N": cfg["mamba_d_state"],
        "R": cfg["mamba_dt_rank"], "K": cfg["mamba_d_conv"],
        "period": cfg["attn_layer_period"], "offset": cfg["attn_layer_offset"],
        "eps": cfg["rms_norm_eps"],
    }


def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


# -- weights ----------------------------------------------------------------

def _normal(k, shape, s, dtype):
    return (s * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def _norm_scale(k, n, dtype):
    return (1.0 + 0.05 * jax.random.normal(k, (n,), jnp.float32)).astype(dtype)


def layer_params(k, cfg: dict, attention: bool, dtype=jnp.float32) -> dict:
    """One layer's weights from its key ``k`` (traceable). Scales are the
    configuration file's ``assumed.weights``."""
    z = sizes(cfg)
    d, di, n, r = z["d"], z["Di"], z["N"], z["R"]
    resid = 1.0 / math.sqrt(2 * z["L"])
    ks = jax.random.split(k, 17)
    out = {
        "ln1": _norm_scale(ks[0], d, dtype), "ln2": _norm_scale(ks[1], d, dtype),
        "mlp": {
            "w_gate": _normal(ks[2], (d, z["inter"]), d ** -0.5, dtype),
            "w_up": _normal(ks[3], (d, z["inter"]), d ** -0.5, dtype),
            "w_down": _normal(ks[4], (z["inter"], d), z["inter"] ** -0.5 * resid, dtype),
        },
    }
    if attention:
        hq, hkv = z["H"] * z["head"], z["Hkv"] * z["head"]
        out["attn"] = {
            "w_q": _normal(ks[5], (d, hq), d ** -0.5, dtype),
            "w_k": _normal(ks[6], (d, hkv), d ** -0.5, dtype),
            "w_v": _normal(ks[7], (d, hkv), d ** -0.5, dtype),
            "w_o": _normal(ks[8], (hq, d), hq ** -0.5 * resid, dtype),
        }
        return out
    # The step: softplus^-1 of a value log-uniform in [1e-3, 1e-1] (Mamba's
    # own initialisation), so that delta * A spans fast and slow channels.
    dt = jnp.exp(jax.random.uniform(ks[12], (di,), jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    out["mamba"] = {
        "w_in": _normal(ks[5], (d, 2 * di), d ** -0.5, dtype),
        "conv_w": _normal(ks[6], (z["K"], di), z["K"] ** -0.5, dtype),
        "conv_b": _normal(ks[7], (di,), 0.1, dtype),
        "w_x": _normal(ks[8], (di, r + 2 * n), di ** -0.5, dtype),
        "dt_norm": _norm_scale(ks[9], r, dtype),
        "b_norm": _norm_scale(ks[10], n, dtype),
        "c_norm": _norm_scale(ks[11], n, dtype),
        "w_dt": _normal(ks[13], (r, di), r ** -0.5, dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        # A = -exp(a_log): -(1..N) for every channel, spread a little.
        "a_log": (jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None, :]
                  + 0.1 * jax.random.normal(ks[14], (di, n), jnp.float32)).astype(dtype),
        "d_skip": _norm_scale(ks[15], di, dtype),
        "w_out": _normal(ks[16], (di, d), di ** -0.5 * resid, dtype),
    }
    return out


def make_params(key, cfg: dict, dtype=jnp.float32, *, layer_jit: bool = False) -> dict:
    """Weights from ``key`` in ``dtype``: ``embed`` (tied head), ``norm``,
    one subtree per layer (``layers/<i>``). ``layer_jit`` (call it eagerly
    then) makes each layer in a jitted call of its own, so that one layer's
    float32 temporaries are alive at a time."""
    z = sizes(cfg)
    k_embed, k_norm, k_layers = jax.random.split(key, 3)

    def top(k_embed, k_norm):
        # Embeddings normal(d^-0.5): tied, so the logits of a unit-norm
        # hidden state are of unit spread.
        return {"embed": _normal(k_embed, (z["V"], z["d"]), z["d"] ** -0.5, dtype),
                "norm": _norm_scale(k_norm, z["d"], dtype)}

    def layer(k, attention):
        return layer_params(k, cfg, attention, dtype)

    if layer_jit:
        top, layer = jax.jit(top), jax.jit(layer, static_argnums=1)
    out = top(k_embed, k_norm)
    out["layers"] = {
        str(i): layer(jax.random.fold_in(k_layers, i), is_attention(cfg, i))
        for i in range(z["L"])
    }
    return out


def program_params(params: dict, cfg: dict) -> dict:
    """The same weights in the PROGRAM's layout (``TransformerLM`` with
    state-space layers). A renaming, but for two arrays: ``q``, ``k``,
    ``v`` side by side as the program's one ``qkv`` matrix, and ``a_log``
    transposed to the program's ``(d_state, d_inner)`` (``d_inner`` on the
    lane axis, like the state it multiplies)."""
    blocks = {}
    for i, lp in params["layers"].items():
        f = lp["mlp"]
        block = {
            "ln1": {"scale": lp["ln1"]}, "ln2": {"scale": lp["ln2"]},
            "mlp": {"fc_gate": {"w": f["w_gate"]}, "fc_in": {"w": f["w_up"]},
                    "fc_out": {"w": f["w_down"]}},
        }
        if "attn" in lp:
            a = lp["attn"]
            block["attn"] = {
                "qkv": {"w": jnp.concatenate([a["w_q"], a["w_k"], a["w_v"]], axis=1)},
                "proj": {"w": a["w_o"]},
            }
        else:
            m = lp["mamba"]
            block["mixer"] = {
                "in_proj": {"w": m["w_in"]},
                "conv": {"w": m["conv_w"], "b": m["conv_b"]},
                "x_proj": {"w": m["w_x"]},
                "dt_norm": {"scale": m["dt_norm"]}, "b_norm": {"scale": m["b_norm"]},
                "c_norm": {"scale": m["c_norm"]},
                "dt_proj": {"w": m["w_dt"], "b": m["dt_bias"]},
                "a_log": m["a_log"].T, "d": m["d_skip"],
                "out_proj": {"w": m["w_out"]},
            }
        blocks[i] = block
    return {"wte": {"table": params["embed"]}, "ln_f": {"scale": params["norm"]},
            "blocks": blocks}


# -- the forward pass ---------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def attention(p, x, cfg: dict, quant=None, *, query_block: int = 512):
    """Causal grouped-query attention of ONE sequence ``x`` (T, d), in
    blocks of queries (a ``lax.map``) so that 4096 positions of 20 heads
    fit; every key is seen by every block."""
    z = sizes(cfg)
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    q = _mm(x, f32(p["w_q"]), quant).reshape(t, z["H"], z["head"])
    k = _mm(x, f32(p["w_k"]), quant).reshape(t, z["Hkv"], z["head"])
    v = _mm(x, f32(p["w_v"]), quant).reshape(t, z["Hkv"], z["head"])
    group = z["H"] // z["Hkv"]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    kq, vq = (k, v) if quant is None else (quant(k), quant(v))
    block = min(query_block, t)
    if t % block:
        raise ValueError(f"attention: {t} positions are not whole blocks of {block}")

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        qb = qb if quant is None else quant(qb)
        s = jnp.einsum("qhd,khd->hqk", qb, kq, precision=HIGHEST) * z["head"] ** -0.5
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        w = w if quant is None else quant(w)
        return jnp.einsum("hqk,khd->qhd", w, vq, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, z["H"] * z["head"])
    return _mm(out, f32(p["w_o"]), quant)


def conv1d(u, w, b):
    """Depthwise causal convolution along time: ``u`` (T, Di), ``w`` (K,
    Di), tap ``K - 1`` on the current token, zeros before the first."""
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u], axis=0)
    return sum(w[k] * padded[k:k + u.shape[0]] for k in range(taps)) + b


def selective_scan(delta, u, b, c, a, state_dtype=jnp.float32, length=None):
    """The recurrence, token by token: ``delta``, ``u`` (T, Di); ``b``,
    ``c`` (T, N); ``a`` (Di, N). Returns ``(y, h)``: ``y`` (T, Di) without
    the skip, and the state ``h`` (Di, N) after the first ``length`` tokens
    (all ``T`` where it is None: the rows past ``length`` are padding,
    whose ``y`` nobody reads). ``h`` is carried in ``state_dtype``."""

    def step(h, xs):
        i, dt, ut, bt, ct = xs
        h2 = jnp.exp(dt[:, None] * a) * h.astype(jnp.float32) \
            + (dt * ut)[:, None] * bt[None, :]
        h2 = h2.astype(state_dtype)
        if length is not None:
            h2 = jnp.where(i < length, h2, h)
        return h2, jnp.sum(h2.astype(jnp.float32) * ct[None, :], axis=1)

    h0 = jnp.zeros(a.shape, state_dtype)
    h, y = jax.lax.scan(step, h0, (jnp.arange(delta.shape[0]), delta, u, b, c))
    return y, h


def mamba(p, x, cfg: dict, quant=None, state_dtype=jnp.float32, length=None):
    """The Mamba-1 mixer of ONE sequence ``x`` (T, d): ``(out, h)``, the
    state as :func:`selective_scan` returns it."""
    z = sizes(cfg)
    f32 = lambda a: a.astype(jnp.float32)
    di, n, r = z["Di"], z["N"], z["R"]
    uz = _mm(x, f32(p["w_in"]), quant)
    u, gate = uz[:, :di], uz[:, di:]
    u = jax.nn.silu(conv1d(u, f32(p["conv_w"]), f32(p["conv_b"])))
    dbc = _mm(u, f32(p["w_x"]), quant)
    dt = rms_norm(dbc[:, :r], f32(p["dt_norm"]), z["eps"])
    b = rms_norm(dbc[:, r:r + n], f32(p["b_norm"]), z["eps"])
    c = rms_norm(dbc[:, r + n:], f32(p["c_norm"]), z["eps"])
    delta = jax.nn.softplus(_mm(dt, f32(p["w_dt"]), quant) + f32(p["dt_bias"]))
    y, h = selective_scan(delta, u, b, c, -jnp.exp(f32(p["a_log"])), state_dtype,
                          length)
    y = y + f32(p["d_skip"]) * u
    return _mm(y * jax.nn.silu(gate), f32(p["w_out"]), quant), h


def mlp(f, x, quant=None):
    f32 = lambda a: a.astype(jnp.float32)
    hidden = jax.nn.silu(_mm(x, f32(f["w_gate"]), quant)) * _mm(x, f32(f["w_up"]), quant)
    return _mm(hidden, f32(f["w_down"]), quant)


def embed(params: dict, tokens):
    return params["embed"].astype(jnp.float32)[tokens]


def layer_forward(lp: dict, x, cfg: dict, quant: Optional[Callable] = None,
                  state_dtype=jnp.float32, state_after=None):
    """One pre-norm residual block on ONE sequence ``x`` (T, d). A driver
    that jits this once per kind of layer keeps one layer's float32
    temporaries alive at a time. With ``state_after`` (a count of tokens)
    it returns ``(x, h)``: beside the rows, the state a Mamba layer holds
    after that many tokens (Di, N); None for an attention layer."""
    z = sizes(cfg)
    f32 = lambda a: a.astype(jnp.float32)
    normed, state = rms_norm(x, f32(lp["ln1"]), z["eps"]), None
    if "attn" in lp:
        x = x + attention(lp["attn"], normed, cfg, quant)
    else:
        mixed, state = mamba(lp["mamba"], normed, cfg, quant, state_dtype, state_after)
        x = x + mixed
    x = x + mlp(lp["mlp"], rms_norm(x, f32(lp["ln2"]), z["eps"]), quant)
    return x if state_after is None else (x, state)


def head_logits(params: dict, x, cfg: dict, quant: Optional[Callable] = None):
    """Final norm and the tied head over the rows ``x`` (T, d)."""
    x = rms_norm(x, params["norm"].astype(jnp.float32), sizes(cfg)["eps"])
    return _mm(x, params["embed"].astype(jnp.float32).T, quant)


def logits(params: dict, tokens, cfg: dict, quant: Optional[Callable] = None,
           state_dtype=jnp.float32, layer_fn: Optional[Callable] = None):
    """Logits (T, V) of ONE sequence ``tokens`` (T,). ``layer_fn`` replaces
    :func:`layer_forward` (a jitted one)."""
    layer_fn = layer_fn or (
        lambda lp, x: layer_forward(lp, x, cfg, quant, state_dtype))
    x = embed(params, tokens)
    for i in range(sizes(cfg)["L"]):
        x = layer_fn(params["layers"][str(i)], x)
    return head_logits(params, x, cfg, quant)


# -- operations ---------------------------------------------------------------

def matmul_params_per_token(cfg: dict) -> float:
    """Parameters one token multiplies: every matrix of every layer and the
    tied head (the convolution, the norms and the recurrence are not
    matrix multiplications)."""
    z = sizes(cfg)
    d, di = z["d"], z["Di"]
    attn = 2 * d * z["H"] * z["head"] + 2 * d * z["Hkv"] * z["head"]
    mixer = d * 2 * di + di * (z["R"] + 2 * z["N"]) + z["R"] * di + di * d
    layers = sum(attn if is_attention(cfg, i) else mixer for i in range(z["L"]))
    return layers + z["L"] * 3 * d * z["inter"] + d * z["V"]


def recurrence_flops_per_token(cfg: dict) -> float:
    """Operations of the recurrence and the convolution for one token, over
    the state layers: per channel and state ``delta * A``, its exponential,
    two multiplies and an add into ``h``, a multiply and an add into ``y``
    (7), per channel the ``d_conv`` taps (2 each) and ``delta * u``, the
    skip and the gate (4)."""
    z = sizes(cfg)
    layers = sum(not is_attention(cfg, i) for i in range(z["L"]))
    return float(layers * z["Di"] * (7 * z["N"] + 2 * z["K"] + 4))


def serve_flops(cfg: dict, positions) -> float:
    """Forward operations to process one token at each of ``positions``:
    twice the parameters it multiplies, attention over the ``position +
    1`` live rows in the attention layers (``4 * d`` a row a layer: scores
    and values, two operations a multiply-add) and the recurrence's."""
    z = sizes(cfg)
    positions = [int(p) for p in positions]
    attended = sum(positions) + len(positions)
    attn_layers = sum(is_attention(cfg, i) for i in range(z["L"]))
    return (
        (2.0 * matmul_params_per_token(cfg) + recurrence_flops_per_token(cfg))
        * len(positions)
        + 4.0 * attn_layers * z["H"] * z["head"] * attended
    )
