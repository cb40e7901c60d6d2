"""Plain DeepSeek-V3-style language model in ``jax.numpy`` (the language
model of ``dots.vlm1.inst``): weights from a seed and the forward pass —
float32, ``"highest"`` matmul precision, no kernels, no cache, no batching,
nothing imported from ``rocket_tpu``.

Follows the published architecture (DeepSeek-V2, arXiv 2405.04434 §2.1 for
the latent attention; DeepSeek-V3, arXiv 2412.19437 §2.1.2 for the router;
``modeling_deepseek.py`` beside the source's ``config.json``):

* **MLA**, per layer, for hidden ``h_t``: ``c_q = RMSNorm(W_dq h_t)``;
  ``[q_nope | q_rope] = W_uq c_q`` per head; ``[c_kv | k_r] = W_dkv h_t``;
  ``c_kv = RMSNorm(c_kv)``; ``k_rope = RoPE(k_r)``, ONE per token shared by
  all heads; ``[k_nope | v] = W_ukv c_kv`` per head; ``score = (q_nope.k_nope
  + RoPE(q_rope).k_rope) * (nope + rope)^-0.5 * m^2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``; causal softmax; ``o = W_o
  concat_heads(softmax . v)``. Always the non-absorbed form, on the whole
  sequence.
* **YaRN**: ``inv_freq`` blends ``theta^(-2i/d)`` and the same over
  ``factor`` by the linear ramp between the correction dimensions of
  ``beta_fast`` and ``beta_slow`` over ``original_max_position_embeddings``;
  cos and sin are scaled by ``mscale``'s ratio to ``mscale_all_dim``'s.
* **Router**: ``s = sigmoid(W_g x)``; ``s' = s + b`` (selection only); a
  group's score is the sum of its two best ``s'``; the best ``topk_group``
  groups stay; the ``num_experts_per_tok`` best ``s'`` within them are
  chosen; ``w_i = s_i / (sum_chosen s + 1e-20) * routed_scaling_factor``.
* **Expert layer**: ``y = sum_{i chosen and held} w_i E_i(x) + E_shared(x)``,
  ``E(x) = W_down(silu(W_gate x) * W_up x)``; the first
  ``first_k_dense_replace`` layers are the same SwiGLU at
  ``intermediate_size``. Pre-norm residual blocks, final RMSNorm, untied
  head.

Departures from the source, each on purpose:

* **The chip's share.** Only ``config["n_routed_experts"]`` experts are
  held (``experts_held_offset`` on, of the ``n_routed_experts_published``
  the router scores); what the absent experts would add is left out and
  the partial sum goes on. ``vocab_size`` rows of the vocabulary are kept.
  ``experts_held=(offset, count)`` of :func:`expert_layer` lets a test ask for
  any other share of the same weights, and ``shared=False`` for the routed
  part alone.
* **Rotary layout.** The source de-interleaves the rotary dimensions
  (pairs ``(2i, 2i+1)``) before its rotate-half; that is a fixed
  permutation of ``W_uq``'s and ``W_dkv``'s rotary columns, which random
  weights absorb: here the halves are ``[0, d/2)`` and ``[d/2, d)``.
* **Groups not kept** are masked with ``-inf`` (the source fills 0.0, which
  a negative biased score could lose to).
* No vision tower and no multi-token-prediction head: the configuration
  file says why.

Attention runs in blocks of heads and of queries (two ``lax.map`` loops),
so that a sequence of 8192 positions fits beside 11 GB of weights and the
compiler sees one block; every other operation takes the whole sequence.

``quant`` (the control): a function applied to BOTH operands of every
matrix multiplication of the forward pass (the router's too) —
:func:`fp8` rounds them to float8 e4m3 with a per-tensor scale, the
nearest precision below the bfloat16 the configuration states.
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
    published = int(cfg.get("n_routed_experts_published", cfg["n_routed_experts"]))
    return {
        "d": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"], "V": cfg["vocab_size"],
        "H": cfg["num_attention_heads"], "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "inter": cfg["intermediate_size"], "expert": cfg["moe_intermediate_size"],
        "E": published, "held": cfg["n_routed_experts"],
        "offset": int(cfg.get("experts_held_offset", 0)),
        "k": cfg["num_experts_per_tok"], "groups": cfg["n_group"],
        "kept": cfg["topk_group"],
        "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "eps": cfg["rms_norm_eps"], "scaling": cfg["routed_scaling_factor"],
    }


# -- weights ----------------------------------------------------------------

def _normal(k, shape, s, dtype):
    return (s * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def _norm_scale(k, n, dtype):
    return (1.0 + 0.05 * jax.random.normal(k, (n,), jnp.float32)).astype(dtype)


def _ffn(k, d, width, resid, dtype):
    a, b, c = jax.random.split(k, 3)
    return {
        "w_gate": _normal(a, (d, width), d ** -0.5, dtype),
        "w_up": _normal(b, (d, width), d ** -0.5, dtype),
        "w_down": _normal(c, (width, d), width ** -0.5 * resid, dtype),
    }


def _expert(k, d, width, resid, dtype):
    """A routed expert: ``W_gate`` and ``W_up`` are the two halves of ONE
    stored matrix ``w_gate_up`` (d, 2 * width), the layout the program's
    grouped matmul reads; :func:`swiglu` takes them apart again."""
    a, c = jax.random.split(k)
    return {
        "w_gate_up": _normal(a, (d, 2 * width), d ** -0.5, dtype),
        "w_down": _normal(c, (width, d), width ** -0.5 * resid, dtype),
    }


def _router_bias(k, experts: int, share: int, std: float, dtype):
    """``e_score_correction_bias``: every chip's share of ``share``
    experts holds the SAME values, the ``share`` mid-quantiles of
    normal(``std``), each share in an order of its own drawn from ``k``.
    The seed decides WHICH experts the bias favours, not how much of the
    routing a chip's share attracts: a trained bias balances the chips'
    loads, and a bias drawn freely moved this chip's pairs a token, and
    with them every timing, by several percent from seed to seed."""
    values = std * jax.scipy.special.ndtri((jnp.arange(share) + 0.5) / share)
    keys = jax.random.split(k, experts // share)
    return jnp.concatenate(
        [jax.random.permutation(key, values) for key in keys]).astype(dtype)


def layer_params(k, cfg: dict, dense: bool, dtype=jnp.float32,
                 all_experts: bool = False) -> dict:
    """One layer's weights from its key ``k`` (traceable)."""
    z = sizes(cfg)
    d = z["d"]
    resid = 1.0 / math.sqrt(2 * z["L"])
    bias_std = float(cfg.get("assumed", {}).get("router_bias_std", 0.1))
    ks = jax.random.split(k, 12)
    hq = z["H"] * (z["nope"] + z["rope"])
    out = {
        "ln1": _norm_scale(ks[0], d, dtype), "ln2": _norm_scale(ks[1], d, dtype),
        "attn": {
            "w_dq": _normal(ks[2], (d, z["q_rank"]), d ** -0.5, dtype),
            "q_norm": _norm_scale(ks[3], z["q_rank"], dtype),
            "w_uq": _normal(ks[4], (z["q_rank"], hq), z["q_rank"] ** -0.5, dtype),
            "w_dkv": _normal(ks[5], (d, z["kv_rank"] + z["rope"]), d ** -0.5, dtype),
            "kv_norm": _norm_scale(ks[6], z["kv_rank"], dtype),
            "w_ukv": _normal(ks[7], (z["kv_rank"], z["H"] * (z["nope"] + z["v"])),
                             z["kv_rank"] ** -0.5, dtype),
            "w_o": _normal(ks[8], (z["H"] * z["v"], d),
                           (z["H"] * z["v"]) ** -0.5 * resid, dtype),
        },
    }
    if dense:
        out["mlp"] = _ffn(ks[9], d, z["inter"], resid, dtype)
        return out
    a, b = jax.random.split(ks[9])
    # Every published expert has a key of its own, so a share holds the
    # same numbers whichever other experts are made beside it.
    ids = range(z["E"]) if all_experts else range(z["offset"], z["offset"] + z["held"])
    made = [_expert(jax.random.fold_in(ks[10], e), d, z["expert"], resid, dtype) for e in ids]
    out["moe"] = {
        "w_g": _normal(a, (d, z["E"]), d ** -0.5, dtype),
        "bias": _router_bias(b, z["E"], z["held"], bias_std, dtype),
        "experts": jax.tree.map(lambda *xs: jnp.stack(xs), *made),
        "shared": _ffn(ks[11], d, z["shared"], resid, dtype),
    }
    return out


def make_params(key, cfg: dict, dtype=jnp.float32, *, all_experts: bool = False,
                layer_jit: bool = False) -> dict:
    """Weights from ``key`` in ``dtype``: one subtree per layer
    (``layers/<i>``), the held experts stacked. Scales (``assumed`` in the
    configuration file): embeddings normal(1); every matrix
    normal(fan_in^-0.5), the two residual outputs (``w_o``, every
    ``w_down``) over ``sqrt(2 L)``; norm scales 1 + normal(0.05);
    ``e_score_correction_bias`` the mid-quantiles of
    normal(``router_bias_std``) within every share (:func:`_router_bias`).
    ``all_experts`` makes all the published experts (the test of the
    shares); the held ones are then ``[offset, offset + held)`` of them,
    the same numbers. Traceable as a whole; ``layer_jit`` (call it eagerly
    then) makes each layer in a jitted call of its own, so that one
    layer's float32 temporaries are alive at a time — 11 GB of weights
    leave little room on a chip of 16."""
    z = sizes(cfg)
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)

    def top(k_embed, k_head, k_norm):
        return {
            "embed": _normal(k_embed, (z["V"], z["d"]), 1.0, dtype),
            "head": _normal(k_head, (z["d"], z["V"]), z["d"] ** -0.5, dtype),
            "norm": _norm_scale(k_norm, z["d"], dtype),
        }

    def layer(k, dense):
        return layer_params(k, cfg, dense, dtype, all_experts)

    if layer_jit:
        top, layer = jax.jit(top), jax.jit(layer, static_argnums=1)
    out = top(k_embed, k_head, k_norm)
    out["layers"] = {
        str(i): layer(jax.random.fold_in(k_layers, i), i < z["dense"])
        for i in range(z["L"])
    }
    return out


def program_params(params: dict, cfg: dict) -> dict:
    """The same weights in the PROGRAM's layout (``TransformerLM`` with
    latent attention and routed experts): a renaming, no array is
    touched."""
    def ffn(f):
        return {"fc_gate": {"w": f["w_gate"]}, "fc_in": {"w": f["w_up"]},
                "fc_out": {"w": f["w_down"]}}

    blocks = {}
    for i, lp in params["layers"].items():
        a = lp["attn"]
        block = {
            "ln1": {"scale": lp["ln1"]}, "ln2": {"scale": lp["ln2"]},
            "attn": {
                "q_a": {"w": a["w_dq"]}, "q_norm": {"scale": a["q_norm"]},
                "q_b": {"w": a["w_uq"]}, "kv_a": {"w": a["w_dkv"]},
                "kv_norm": {"scale": a["kv_norm"]}, "kv_b": {"w": a["w_ukv"]},
                "proj": {"w": a["w_o"]},
            },
        }
        if "mlp" in lp:
            block["mlp"] = ffn(lp["mlp"])
        else:
            m = lp["moe"]
            block["moe"] = {
                "router": {"w": m["w_g"], "bias": m["bias"]},
                "experts": m["experts"], "shared": m["shared"],
            }
        blocks[i] = block
    return {
        "wte": {"table": params["embed"]}, "ln_f": {"scale": params["norm"]},
        "head": {"w": params["head"]}, "blocks": blocks,
    }


# -- YaRN -------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The rotary frequencies (``qk_rope_head_dim // 2`` of them), float32."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    half = dim // 2
    plain = base ** (-np.arange(half, dtype=np.float64) / half)
    rs = cfg.get("rope_scaling")
    if not rs:
        return plain.astype(np.float32)
    original = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0, 1)
    return (plain / rs["factor"] * ramp + plain * (1 - ramp)).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    scale = float(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs:
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope(x, positions, cfg: dict):
    """Rotate-half RoPE of ``x`` (T, ..., rope) at ``positions`` (T,)."""
    rs = cfg.get("rope_scaling")
    ratio = 1.0
    if rs:
        ratio = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(
            rs["factor"], rs["mscale_all_dim"])
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (angles.shape[1],)
    cos, sin = (jnp.cos(angles) * ratio).reshape(shape), (jnp.sin(angles) * ratio).reshape(shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# -- the forward pass ---------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def attention(p, x, cfg: dict, quant=None, *, head_block: int = 16,
              query_block: int = 512):
    """Latent attention of one sequence ``x`` (T, d), non-absorbed, in
    blocks of ``head_block`` heads and ``query_block`` queries: a block's
    scores are (heads, queries, T) float32, every key present and those
    after the query masked. The blocks are the steps of two ``lax.map``
    loops, so the program is compiled once per block and not once per
    block and prefix."""
    z = sizes(cfg)
    t = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(t)
    c_q = rms_norm(_mm(x, f32(p["w_dq"]), quant), f32(p["q_norm"]), z["eps"])
    q = _mm(c_q, f32(p["w_uq"]), quant).reshape(t, z["H"], z["nope"] + z["rope"])
    q = jnp.concatenate([q[..., :z["nope"]], rope(q[..., z["nope"]:], pos, cfg)], axis=-1)
    kv = _mm(x, f32(p["w_dkv"]), quant)
    c_kv = rms_norm(kv[:, :z["kv_rank"]], f32(p["kv_norm"]), z["eps"])
    k_rope = rope(kv[:, z["kv_rank"]:], pos, cfg)                   # (T, rope)
    scale = softmax_scale(cfg)
    qn = (lambda a: a) if quant is None else quant
    hb = math.gcd(head_block, z["H"])
    qb = min(query_block, t)
    nq = -(-t // qb)
    # Queries past the end (the padding of the last block) see every key
    # and are cut off again below.
    q = jnp.pad(q, ((0, nq * qb - t), (0, 0), (0, 0)))
    q = q.reshape(nq, qb, z["H"] // hb, hb, -1).transpose(2, 0, 1, 3, 4)
    w_ukv = f32(p["w_ukv"]).reshape(z["kv_rank"], z["H"] // hb, hb, z["nope"] + z["v"])
    q_pos = jnp.arange(nq * qb).reshape(nq, qb)

    def head_block_out(block):
        w_blk, q_blk = block            # (kv_rank, hb, nope + v), (nq, qb, hb, nope + rope)
        up = jnp.einsum("tk,khe->the", qn(c_kv), qn(w_blk), precision=HIGHEST)
        k_nope, v = up[..., :z["nope"]], up[..., z["nope"]:]

        def query_block_out(rows):
            q_rows, at = rows           # (qb, hb, nope + rope), (qb,)
            score = (
                jnp.einsum("qhn,thn->hqt", qn(q_rows[..., :z["nope"]]), qn(k_nope),
                           precision=HIGHEST)
                + jnp.einsum("qhr,tr->hqt", qn(q_rows[..., z["nope"]:]), qn(k_rope),
                             precision=HIGHEST)
            ) * scale
            seen = pos[None, :] <= at[:, None]
            w = jax.nn.softmax(jnp.where(seen[None], score, -jnp.inf), axis=-1)
            return jnp.einsum("hqt,thv->qhv", qn(w), qn(v), precision=HIGHEST)

        out = jax.lax.map(query_block_out, (q_blk, q_pos))          # (nq, qb, hb, v)
        return out.reshape(nq * qb, hb, z["v"])[:t]

    heads = jax.lax.map(head_block_out, (w_ukv.transpose(1, 0, 2, 3), q))
    out = heads.transpose(1, 0, 2, 3).reshape(t, z["H"] * z["v"])
    return _mm(out, f32(p["w_o"]), quant)


def swiglu(f, x, quant=None):
    """``W_down(silu(W_gate x) * W_up x)``; a routed expert stores gate
    and up as the halves of ``w_gate_up``."""
    f32 = lambda a: a.astype(jnp.float32)
    if "w_gate_up" in f:
        width = f["w_gate_up"].shape[-1] // 2
        w_gate, w_up = f["w_gate_up"][..., :width], f["w_gate_up"][..., width:]
    else:
        w_gate, w_up = f["w_gate"], f["w_up"]
    hid = jax.nn.silu(_mm(x, f32(w_gate), quant)) * _mm(x, f32(w_up), quant)
    return _mm(hid, f32(f["w_down"]), quant)


def route(p, x, cfg: dict, quant=None, *, experts_held=None):
    """``(weights (T, k), experts (T, k), margin (T,))``: the router of
    the module's docstring, and per token how near the choice is to
    another one THAT THIS CHIP WOULD FEEL: the smallest distance of a held
    expert's biased score from the boundary it would have to cross (a
    chosen one from the (k+1)-th best, one not chosen from the k-th best,
    among the groups kept), and of a held expert's group from the boundary
    between the groups kept and not. Two absent experts that change places
    leave this chip's sum as it was (the weights move by less than their
    distance), so they do not count."""
    z = sizes(cfg)
    t = x.shape[0]
    offset, count = experts_held or (z["offset"], z["held"])
    s = jax.nn.sigmoid(_mm(x, p["w_g"].astype(jnp.float32), quant))
    biased = s + p["bias"].astype(jnp.float32)[None, :]
    per = z["E"] // z["groups"]
    grouped = biased.reshape(t, z["groups"], per)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    ranked, keep = jax.lax.top_k(group_score, min(z["kept"] + 1, z["groups"]))
    keep = keep[:, :z["kept"]]
    kept = jnp.zeros((t, z["groups"]), bool).at[jnp.arange(t)[:, None], keep].set(True)
    masked = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(t, z["E"])
    top, experts = jax.lax.top_k(masked, z["k"] + 1)
    experts = experts[:, :z["k"]]
    w = jnp.take_along_axis(s, experts, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)

    held = (jnp.arange(z["E"]) >= offset) & (jnp.arange(z["E"]) < offset + count)
    chosen = jnp.zeros((t, z["E"]), bool).at[jnp.arange(t)[:, None], experts].set(True)
    distance = jnp.where(chosen, masked - top[:, z["k"]:], top[:, z["k"] - 1:z["k"]] - masked)
    margin = jnp.min(jnp.where(held[None, :], distance, jnp.inf), axis=1)
    if z["kept"] < z["groups"]:
        held_group = jnp.any(held.reshape(z["groups"], per), axis=1)
        group_distance = jnp.where(
            kept, group_score - ranked[:, z["kept"]:], ranked[:, z["kept"] - 1:z["kept"]] - group_score)
        margin = jnp.minimum(margin, jnp.min(
            jnp.where(held_group[None, :], group_distance, jnp.inf), axis=1))
    return w * z["scaling"], experts, margin


def expert_layer(p, x, cfg: dict, quant=None, *, experts_held=None,
                 shared: bool = True):
    """``(y (T, d), margin (T,))``: the held experts' part of the routed
    sum plus (``shared``) the shared expert. Every held expert runs on
    every token and is weighted by the router's weight or by 0: plain, and
    the same numbers as a dispatch. ``p["experts"]`` holds the experts
    ``[offset, offset + count)`` of ``experts_held`` (default: the
    configuration's share), stacked."""
    z = sizes(cfg)
    offset, count = experts_held or (z["offset"], z["held"])
    w, experts, margin = route(p, x, cfg, quant, experts_held=(offset, count))
    y = jnp.zeros_like(x)

    def one(y, xs):
        f, e = xs
        weight = jnp.sum(jnp.where(experts == e, w, 0.0), axis=1)       # (T,)
        return y + weight[:, None] * swiglu(f, x, quant), None

    y, _ = jax.lax.scan(one, y, (p["experts"], offset + jnp.arange(count)))
    if shared:
        y = y + swiglu(p["shared"], x, quant)
    return y, margin


def embed(params: dict, tokens):
    return params["embed"].astype(jnp.float32)[tokens]


def layer_forward(lp: dict, x, cfg: dict, quant: Optional[Callable] = None):
    """One pre-norm residual block on ONE sequence ``x`` (T, d): ``(x',
    margin (T,))``, ``margin`` the router's (:func:`route`; ``inf`` in a
    dense layer). A driver that jits this once per kind of layer keeps one
    layer's float32 temporaries alive at a time."""
    z = sizes(cfg)
    f32 = lambda a: a.astype(jnp.float32)
    x = x + attention(lp["attn"], rms_norm(x, f32(lp["ln1"]), z["eps"]), cfg, quant)
    h = rms_norm(x, f32(lp["ln2"]), z["eps"])
    if "mlp" in lp:
        return x + swiglu(lp["mlp"], h, quant), jnp.full((x.shape[0],), jnp.inf)
    y, margin = expert_layer(lp["moe"], h, cfg, quant)
    return x + y, margin


def head_logits(params: dict, x, cfg: dict, quant: Optional[Callable] = None):
    """Final norm and the untied head over the rows ``x`` (T, d)."""
    x = rms_norm(x, params["norm"].astype(jnp.float32), sizes(cfg)["eps"])
    return _mm(x, params["head"].astype(jnp.float32), quant)


def logits(params: dict, tokens, cfg: dict, quant: Optional[Callable] = None,
           layer_fn: Optional[Callable] = None):
    """``(logits (T, V), margin (T,))`` of ONE sequence ``tokens`` (T,):
    ``margin`` is the smallest router margin over the routed layers, per
    position. ``layer_fn`` replaces :func:`layer_forward` (a jitted one)."""
    layer_fn = layer_fn or (lambda lp, x: layer_forward(lp, x, cfg, quant))
    x = embed(params, tokens)
    margin = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
    for i in range(sizes(cfg)["L"]):
        x, m = layer_fn(params["layers"][str(i)], x)
        margin = jnp.minimum(margin, m)
    return head_logits(params, x, cfg, quant), margin


# -- operations (this chip's share) -------------------------------------------

def matmul_params_per_token(cfg: dict, pairs_per_token: float) -> float:
    """Parameters one token multiplies on this chip: attention in every
    layer, the dense FFN or the shared expert, ``pairs_per_token`` held
    experts a routed layer, the router, the sliced head."""
    z = sizes(cfg)
    d = z["d"]
    attn = (d * z["q_rank"] + z["q_rank"] * z["H"] * (z["nope"] + z["rope"])
            + d * (z["kv_rank"] + z["rope"])
            + z["kv_rank"] * z["H"] * (z["nope"] + z["v"]) + z["H"] * z["v"] * d)
    routed = z["L"] - z["dense"]
    return (
        z["L"] * attn + z["dense"] * 3 * d * z["inter"]
        + routed * (3 * d * z["shared"] + d * z["E"]
                    + pairs_per_token * 3 * d * z["expert"])
        + d * z["V"]
    )


def serve_flops(cfg: dict, positions, pairs_per_token: Optional[float] = None) -> float:
    """Forward operations this chip needs to process one token at each of
    ``positions``: twice the parameters it multiplies, plus attention over
    the ``position + 1`` live rows in the non-absorbed form (``H * (nope +
    rope + v)`` multiply-adds a row a layer). ``pairs_per_token`` defaults
    to even routing: ``k * held / E``."""
    z = sizes(cfg)
    if pairs_per_token is None:
        pairs_per_token = z["k"] * z["held"] / z["E"]
    positions = [int(p) for p in positions]
    attended = sum(positions) + len(positions)
    per_row = 2.0 * z["L"] * z["H"] * (z["nope"] + z["rope"] + z["v"])
    return (2.0 * matmul_params_per_token(cfg, pairs_per_token) * len(positions)
            + per_row * attended)
