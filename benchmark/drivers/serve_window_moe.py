"""The serving driver for a model whose attention layers are of two kinds —
full ones on the paged pool and sliding-window ones on a ring a slot — with
a leading dense layer and a routed feed-forward behind it
(``reference/laguna_lm.py``): the open loop, the window, the lead-in, the
end-to-end arithmetic and the result's keys are ``drivers/serve.py``'s —
its :func:`~benchmark.drivers.serve.run` runs here unchanged, with the
three things a GPT-2 names by import (the engine's model and weights, the
operation count, the reference's comparison) taken from this
configuration's own reference module, as ``drivers/serve_latent_moe.py``
does for its family.

``correct`` compares, like the other serve drivers, the gap by which a
served token's reference logit lies below the reference's best — over the
SAMPLE of the finished requests that ``serve.run`` picks by the cell's
``compare`` rule (``common.pick_sample``), prefill through chunks and then
decode through pool and rings against the reference's full forward of the
whole sequence — leaving out the positions at which the reference's own
router was within ``margin`` (in router logits) of a choice this chip would
feel: the widest gap (``token_gap_max``), the mean (``token_gap_mean``)
and the share left out (``routing_ambiguous_share``). A ring row written
to the wrong place, read stale or read outside the window moves every later
token of its request, so it shows in both gaps.
"""

from __future__ import annotations

import functools
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import common, serve
from benchmark.drivers.serve import GRACE_S, Loop, warm_up  # noqa: F401
from benchmark.drivers.serve_hybrid_ssm import free_since
from benchmark.drivers.serve_latent_moe import _as_serve, summarise  # noqa: F401
from benchmark.reference import laguna_lm as ref

#: Sequences are padded to a multiple of this before the reference runs:
#: one compile per bucket, not per length — four buckets at 16,384.
PAD = 4096


def transformer_config(config: dict):
    """The program's ``TransformerConfig`` for a configuration file of this
    family: every size is the file's; each kind of layer named in
    ``layer_types`` gets its heads, rotary and window."""
    from rocket_tpu.models.transformer import AttentionKind, TransformerConfig
    from rocket_tpu.nn.attention import YarnScaling
    from rocket_tpu.nn.moe import RoutedExpertsConfig

    z = ref.sizes(config)
    layers = range(z["L"])
    kinds = {}
    for name in sorted({ref.kind(config, i) for i in layers}):
        heads = {ref.heads(config, i) for i in layers if ref.kind(config, i) == name}
        if len(heads) != 1:
            raise ValueError(f"{name}: one head count a kind of layer, not {heads}")
        rp = config["rope_parameters"][name]
        yarn = None
        if rp.get("rope_type", "default") == "yarn":
            yarn = YarnScaling(
                factor=rp["factor"],
                original_max_position_embeddings=rp["original_max_position_embeddings"],
                beta_fast=rp["beta_fast"], beta_slow=rp["beta_slow"],
            )
            stated = rp.get("attention_factor", yarn.trig_scale)
            if abs(stated - yarn.trig_scale) > 1e-9:
                raise ValueError(f"{name}: attention_factor {stated} is not "
                                 f"0.1 ln(factor) + 1 = {yarn.trig_scale}")
        kinds[name] = AttentionKind(
            num_heads=heads.pop(), rope_base=float(rp["rope_theta"]),
            rope_fraction=float(rp.get("partial_rotary_factor", 1.0)), rope_yarn=yarn,
            window=z["window"] if name == "sliding_attention" else 0,
        )
    dense = [ref.is_dense(config, i) for i in layers]
    leading = dense.index(False) if False in dense else len(dense)
    if any(dense[leading:]):
        raise ValueError("the dense feed-forward layers must lead the stack")
    return TransformerConfig(
        vocab_size=z["V"], max_seq_len=config["max_position_embeddings"],
        dim=z["d"], num_layers=z["L"], num_heads=config["num_attention_heads"],
        num_kv_heads=z["Hkv"], head_dim=z["head"], dropout=0.0,
        tied_embeddings=bool(config["tie_word_embeddings"]),
        activation_dtype="bfloat16", pos_embedding="rope", norm="rmsnorm",
        norm_eps=z["eps"], mlp="swiglu", mlp_hidden=z["dense"], mlp_bias=False,
        attn_bias=bool(config["attention_bias"]), attn_head_gate=True,
        layer_types=tuple(config["layer_types"][:z["L"]]), attention_kinds=kinds,
        routed_experts=RoutedExpertsConfig(
            num_experts=z["E"], top_k=z["k"], hidden=z["expert"],
            norm_topk_prob=True, routed_scaling_factor=z["scaling"],
            shared_hidden=z["shared"], scoring="softmax",
            experts_held=(z["offset"], z["held"]),
        ),
        first_dense_layers=leading,
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
        # The block table covers the traffic's longest request, not the
        # positions the model declares.
        max_model_len=e["max_model_len"],
    ))


def layer_fns(config: dict, quant=None, window: bool = True) -> list:
    """``ref.layer_forward`` jitted, one per layer: layers of the same kind,
    feed-forward and head count share ONE compiled program."""
    compiled, fns = {}, []
    for i in range(config["num_hidden_layers"]):
        sig = (ref.kind(config, i), ref.is_dense(config, i), ref.heads(config, i))
        if sig not in compiled:
            compiled[sig] = jax.jit(functools.partial(
                ref.layer_forward, cfg=config, i=i, quant=quant, window=window))
        fns.append(compiled[sig])
    return fns


def reference_numbers(config: dict, seed: int, sample: list, *, span: int,
                      quant=None, window: bool = True, control: bool = False) -> list:
    """For each ``(prompt, served tokens)`` of ``sample``, per answer
    position: ``(gap, margin)`` arrays — the gap by which the served
    token's reference logit lies below the reference's best, and the
    reference router's smallest margin there (``ref.route``) over the
    routed layers. ``control=True`` reads instead the gap of the token that
    the reference with ``quant`` (a lower precision) or with ``window``
    False (sliding layers that attend the whole context) puts first."""
    params = make_weights(config, seed)
    layers, low_layers = layer_fns(config), layer_fns(config, quant, window)
    embed = jax.jit(ref.embed)

    def head_fn(quant):
        return jax.jit(lambda params, x, start: ref.head_logits(
            params, jax.lax.dynamic_slice_in_dim(x, start, span, axis=0), config, quant))

    head, low_head = head_fn(None), head_fn(quant)

    def forward(tokens, fns):
        x = embed(params, tokens)
        worst = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
        for i, fn in enumerate(fns):
            x, m = fn(params["layers"][str(i)], x)
            worst = jnp.minimum(worst, m)
        return x, worst

    out = []
    for prompt, served in sample:
        p, a = len(prompt), len(served)
        bucket = min(PAD, config["max_position_embeddings"])
        pad = max(-(-(p + a) // bucket), -(-span // bucket)) * bucket
        padded = np.zeros((pad,), np.int32)
        padded[:p + a] = np.concatenate([prompt, served])
        tokens = jnp.asarray(padded)
        # Position p-1+j predicts answer token j.
        start = min(p - 1, pad - span)
        x, worst = forward(tokens, layers)
        logits = head(params, x, start)
        picked = None
        if control:
            picked = jnp.argmax(low_head(params, forward(tokens, low_layers)[0], start), -1)
        logits, worst, picked = jax.device_get((logits, worst, picked))
        off = p - 1 - start
        logits = logits[off:off + a]
        chosen = picked[off:off + a] if control else served
        out.append((logits.max(axis=-1) - logits[np.arange(a), chosen],
                    worst[p - 1:p - 1 + a]))
    return out


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace_dir=None,
        setup_clock=None) -> dict:
    """One run of a serving cell of this family. ``trace_dir`` set = a
    traced run. ``serve.run`` does everything; its reference step lands in
    :func:`reference_numbers` through the cell's ``compare`` rule."""
    rule = cell["compare"]
    compared = {}
    before = {id(a) for a in jax.live_arrays()}

    def reference_gaps(config, seed, sample, *, span):
        # What this run put on the device (13 GB) goes before the
        # reference's weights arrive.
        free_since(before)
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
