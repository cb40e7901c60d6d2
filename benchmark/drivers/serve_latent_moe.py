"""The serving driver for a latent-attention, routed-expert configuration
(``reference/deepseek_v3_lm.py``): the open loop, the window, the lead-in,
the end-to-end arithmetic and the result's keys are ``drivers/serve.py``'s —
its :func:`~benchmark.drivers.serve.run` runs here unchanged, with the three
things a GPT-2 names by import (the engine's model and weights, the
operation count, the reference's comparison) taken from this
configuration's own reference module.

``correct`` compares, like ``serve.py``, the widest gap by which a served
token's reference logit lies below the reference's best — over the SAMPLE of
the finished requests that ``serve.run`` picks by the cell's ``compare``
rule (``common.pick_sample``: every ``every``-th in submit order and always
the longest; the float32 reference of a 6,000-token request takes
seconds), and leaving out the positions at
which the reference's own router was within ``margin`` of choosing another
expert (a bfloat16 score that near the boundary picks differently, and the
logits then differ by more than rounding). Their share is a number of
its own, ``routing_ambiguous_share``, with its limit. A flip that the
margin does not foresee still happens now and then, in a sound run as in
one of lower precision, and sets the widest gap of either; so the mean gap
over the same positions, ``token_gap_mean``, is the number that tells the
stated precision from the one below it.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import common, serve
from benchmark.drivers.serve import GRACE_S, Loop, _decode_contexts, warm_up  # noqa: F401
from benchmark.reference import deepseek_v3_lm as ref

#: Sequences are padded to a multiple of this (or of the configuration's
#: positions, if fewer) before the reference runs: one compile per bucket,
#: not per length — two buckets at 8192 positions.
PAD = 4096


def transformer_config(config: dict):
    """The program's ``TransformerConfig`` for a configuration file of this
    family: every size is the file's."""
    from rocket_tpu.models.transformer import TransformerConfig
    from rocket_tpu.nn.attention import LatentAttentionConfig, YarnScaling
    from rocket_tpu.nn.moe import RoutedExpertsConfig

    z = ref.sizes(config)
    rs = config.get("rope_scaling")
    yarn = None
    if rs:
        yarn = YarnScaling(
            factor=rs["factor"],
            original_max_position_embeddings=rs["original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"],
        )
    return TransformerConfig(
        vocab_size=z["V"], max_seq_len=config["max_position_embeddings"],
        dim=z["d"], num_layers=z["L"], num_heads=z["H"], dropout=0.0,
        tied_embeddings=bool(config["tie_word_embeddings"]),
        activation_dtype="bfloat16", pos_embedding="rope",
        rope_base=float(config["rope_theta"]), norm="rmsnorm",
        norm_eps=z["eps"], mlp="swiglu", mlp_hidden=z["inter"], mlp_bias=False,
        latent_attention=LatentAttentionConfig(
            q_lora_rank=z["q_rank"], kv_lora_rank=z["kv_rank"],
            qk_nope_head_dim=z["nope"], qk_rope_head_dim=z["rope"],
            v_head_dim=z["v"], yarn=yarn,
        ),
        routed_experts=RoutedExpertsConfig(
            num_experts=z["E"], top_k=z["k"], hidden=z["expert"],
            n_group=z["groups"], topk_group=z["kept"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scaling_factor=z["scaling"], shared_hidden=z["shared"],
            experts_held=(z["offset"], z["held"]),
        ),
        first_dense_layers=z["dense"],
    )


def make_weights(config: dict, seed: int):
    """The reference's weights for ``seed``, bfloat16, on the device, a
    layer at a time."""
    return ref.make_params(ref.seed_key(seed), config, jnp.bfloat16, layer_jit=True)


def build_engine(cell: dict, config: dict, seed: int):
    from rocket_tpu.models.transformer import TransformerLM
    from rocket_tpu.serve import ServeConfig, ServeEngine

    model = TransformerLM(transformer_config(config))
    params = ref.program_params(make_weights(config, seed), config)
    e = cell["engine"]
    return ServeEngine(model, params, ServeConfig(
        max_slots=e["max_slots"], block_len=e["block_len"],
        prefill_chunk=e["prefill_chunk"],
        decode_waves_per_dispatch=e["decode_waves_per_dispatch"],
    ))


def reference_numbers(config: dict, seed: int, sample: list, *, span: int,
                      quant=None, control: bool = False) -> list:
    """For each ``(prompt, served tokens)`` of ``sample``, per answer
    position: ``(gap, margin)`` arrays — the gap by which the served
    token's reference logit lies below the reference's best, and the
    reference router's smallest margin there (``ref.route``) over the
    routed layers. ``control=True`` reads instead the gap of the token that
    ``quant``'s lower precision puts first."""
    params = make_weights(config, seed)
    layer = jax.jit(functools.partial(ref.layer_forward, cfg=config))
    low_layer = jax.jit(functools.partial(ref.layer_forward, cfg=config, quant=quant))
    embed = jax.jit(ref.embed)

    def head_fn(quant):
        return jax.jit(lambda params, x, start: ref.head_logits(
            params, jax.lax.dynamic_slice_in_dim(x, start, span, axis=0), config, quant))

    head, low_head = head_fn(None), head_fn(quant)

    def forward(tokens, layer_fn):
        x = embed(params, tokens)
        worst = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
        for i in range(config["num_hidden_layers"]):
            x, m = layer_fn(params["layers"][str(i)], x)
            worst = jnp.minimum(worst, m)
        return x, worst

    out = []
    for prompt, served in sample:
        p, a = len(prompt), len(served)
        bucket = min(PAD, config["max_position_embeddings"])
        pad = -(-(p + a) // bucket) * bucket
        padded = np.zeros((pad,), np.int32)
        padded[:p + a] = np.concatenate([prompt, served])
        tokens = jnp.asarray(padded)
        # Position p-1+j predicts answer token j.
        start = min(p - 1, pad - span)
        x, worst = forward(tokens, layer)
        logits = head(params, x, start)
        picked = None
        if control:
            picked = jnp.argmax(low_head(params, forward(tokens, low_layer)[0], start), -1)
        logits, worst, picked = jax.device_get((logits, worst, picked))
        off = p - 1 - start
        logits = logits[off:off + a]
        chosen = picked[off:off + a] if control else served
        out.append((logits.max(axis=-1) - logits[np.arange(a), chosen],
                    worst[p - 1:p - 1 + a]))
    return out


def summarise(per_request: list, margin: float) -> dict:
    """The cell's numbers from :func:`reference_numbers`: the widest and
    the mean gap over the positions whose router margin is at least
    ``margin``, and the share of positions left out. (The widest gap is a
    routing flip's, in a sound run as in a lower precision; the mean tells
    the precisions apart.)"""
    kept = np.concatenate([gap[m >= margin] for gap, m in per_request] or [np.zeros(0)])
    positions = sum(len(gap) for gap, _ in per_request)
    return {
        "token_gap_max": float(kept.max()) if len(kept) else None,
        "token_gap_mean": float(kept.mean()) if len(kept) else None,
        "routing_ambiguous_share": (positions - len(kept)) / max(positions, 1),
    }


@contextlib.contextmanager
def _as_serve(**names):
    """``drivers/serve.py`` with some of its module-level names replaced
    for the length of one ``serve.run``."""
    saved = {k: getattr(serve, k) for k in names}
    try:
        for k, v in names.items():
            setattr(serve, k, v)
        yield
    finally:
        for k, v in saved.items():
            setattr(serve, k, v)


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace_dir=None,
        setup_clock=None) -> dict:
    """One run of a serving cell of this family. ``trace_dir`` set = a
    traced run. ``serve.run`` does everything; its reference step lands in
    :func:`reference_numbers` through the cell's ``compare`` rule."""
    rule = cell["compare"]
    compared = {}
    before = {id(a) for a in jax.live_arrays()}

    def reference_gaps(config, seed, sample, *, span):
        # serve.run has let go of the engine, but the engine's jitted
        # programs and their trace counters hold each other, and a traced
        # run's frame still holds the loop: what this run put on the
        # device (13 GB) is deleted before the reference's 11 arrive.
        gc.collect()
        for a in jax.live_arrays():
            if id(a) not in before:
                a.delete()
        compared["per_request"] = reference_numbers(config, seed, sample, span=span)
        compared["tokens"] = int(sum(len(served) for _, served in sample))
        compared["requests"] = len(sample)
        return [0.0]        # serve.run's own number is replaced below

    t = time.perf_counter()
    with _as_serve(
        build_engine=build_engine, reference_gaps=reference_gaps,
        counts=types.SimpleNamespace(serve_flops=ref.serve_flops),
    ):
        result = serve.run(cell, config, seed=seed, seconds=seconds,
                           trace_dir=trace_dir, setup_clock=setup_clock)
    if compared:
        result["numbers"] = summarise(compared["per_request"], float(rule["margin"]))
        result["reference"] = compared["per_request"]
        result["compared_tokens"] = compared["tokens"]
        result["compared_requests"] = compared["requests"]
    common.note(setup_clock or common.Clock(t), (
        f"compared {compared.get('tokens')} tokens of {compared.get('requests')} "
        f"requests; numbers {result.get('numbers')}"))
    return result
