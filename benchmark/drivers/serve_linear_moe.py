"""The serving driver for a hybrid of linear-attention (Gated DeltaNet) and
gated full-attention layers with a routed feed-forward in every layer
(``reference/qwen3_next_lm.py``): the open loop, the window, the lead-in,
the end-to-end arithmetic and the result's keys are ``drivers/serve.py``'s
— its :func:`~benchmark.drivers.serve.run` runs here unchanged, with the
three things a GPT-2 names by import (the engine's model and weights, the
operation count, the reference's comparison) taken from this
configuration's own reference module, as ``drivers/serve_hybrid_ssm.py``
does for its family.

``correct`` compares, like the other serve drivers, the gap by which a
served token's reference logit lies below the reference's best — over the
SAMPLE of the finished requests that ``serve.run`` picks by the cell's
``compare`` rule (``common.pick_sample``), prefill through chunks and then
decode through pool and state against the reference's full forward of the
whole sequence — leaving out, as ``drivers/serve_latent_moe.py`` does, the
positions at which the reference's own router was within ``margin`` (in
router logits) of a choice this chip would feel: the widest gap
(``token_gap_max``), the mean (``token_gap_mean``) and the share left out
(``routing_ambiguous_share``).

``state_gap`` reads the matrix state ALONE: before the lead-in one request
of the cell's ``compare.state_probe`` lengths (tokens from the seed) is
served by itself on the warm engine — prefill in chunks, the last one
padded, then decode waves — and the ``S`` its slot then holds in the FIRST
DeltaNet layer (whose input is the embedding itself: the mixer's own
arithmetic and the state's precision, not the error of the layers under
it) is compared with the reference's after the same tokens
(:func:`state_distance`). The probe belongs to set-up and costs the window
nothing.
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
from benchmark.drivers.serve_latent_moe import _as_serve
from benchmark.reference import qwen3_next_lm as ref

#: Sequences are padded to a multiple of this before the reference runs:
#: one compile per bucket, not per length — four buckets at 16,384 (at
#: 1,024 a sample of 19 requests met a dozen buckets, two kinds of layer
#: each, and the reference spent most of its 318 s compiling: PERF.md).
PAD = 4096


def transformer_config(config: dict):
    """The program's ``TransformerConfig`` for a configuration file of this
    family: every size is the file's."""
    from rocket_tpu.models.transformer import TransformerConfig
    from rocket_tpu.nn.gdn import GatedDeltaNetConfig
    from rocket_tpu.nn.moe import RoutedExpertsConfig

    z = ref.sizes(config)
    return TransformerConfig(
        vocab_size=z["V"], max_seq_len=config["max_position_embeddings"],
        dim=z["d"], num_layers=z["L"], num_heads=z["H"], num_kv_heads=z["Hkv"],
        head_dim=z["head"], dropout=0.0,
        tied_embeddings=bool(config["tie_word_embeddings"]),
        activation_dtype="bfloat16", pos_embedding="rope", rope_base=z["theta"],
        rope_fraction=float(config["partial_rotary_factor"]),
        norm="rmsnorm", norm_eps=z["eps"], norm_zero_centered=True,
        attn_bias=False, attn_gate=True, qk_norm=True,
        gdn=GatedDeltaNetConfig(
            num_k_heads=z["Hk"], num_v_heads=z["Hv"], head_k_dim=z["dk"],
            head_v_dim=z["dv"], d_conv=z["K"],
        ),
        # Attention at layers interval - 1, 2 * interval - 1, ...
        attn_layer_period=z["interval"], attn_layer_offset=z["interval"] - 1,
        routed_experts=RoutedExpertsConfig(
            num_experts=z["E"], top_k=z["k"], hidden=z["expert"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            shared_hidden=z["shared"], shared_gate=True, scoring="softmax",
            experts_held=(z["offset"], z["held"]),
        ),
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


def state_probe(engine, config: dict, lengths: dict, seed: int) -> dict:
    """One request served alone on the idle ``engine`` (``lengths``:
    ``prompt`` and ``answer`` tokens; the prompt drawn from ``seed``):
    ``{"tokens": what its slot's state has consumed, "S": the first
    DeltaNet layer's state, (value heads, dk, dv) float32}``. An idle
    engine admits into its lowest free slot, 0."""
    rng = np.random.default_rng([int(seed), 37])
    prompt = rng.integers(0, config["vocab_size"], size=int(lengths["prompt"]),
                          dtype=np.int32)
    rid = engine.submit(prompt, max_new_tokens=int(lengths["answer"]), temperature=0.0)
    engine.drain()
    served = np.asarray(engine.result(rid).tokens, np.int32)
    slots = engine.engine
    s_all = slots.pages[len(slots.spec.pages_shapes)]
    # The last token served has been handed out, not yet consumed.
    return {"tokens": np.concatenate([prompt, served[:-1]]),
            "S": np.array(s_all[0, 0], np.float32)}


def state_distance(s, want) -> float:
    """How far a state ``s`` lies from the reference's ``want`` (both
    ``(value heads, dk, dv)``): per head ``|s_h - want_h| / |want_h|`` over
    its ``dk x dv`` entries, the largest over the heads. A state carried in
    less than float32 errs most in the heads that decay slowest (every
    token's rounding stays for as long as the head remembers), which a norm
    over the whole state would average away; the rounding of ``q``, ``k``
    and ``v`` on their way into the rule moves every head alike. (A first
    form, the 99th percentile over the heads' value columns, told the two
    apart by 1.97 x where this one does by 3 x: its tail is columns whose
    reference norm is small. PERF.md, PR 37.)"""
    heads = s.shape[0]
    err = np.linalg.norm((s - want).reshape(heads, -1), axis=1)
    return float((err / np.linalg.norm(want.reshape(heads, -1), axis=1)).max())


def reference_numbers(config: dict, seed: int, sample: list, *, span: int,
                      probe=None, quant=None, state_dtype=jnp.float32,
                      control: bool = False) -> tuple:
    """``(per request, state gap)``. For each ``(prompt, served tokens)``
    of ``sample``, per answer position ``(gap, margin)`` arrays: the gap
    by which the served token's reference logit lies below the reference's
    best, and the reference router's smallest margin there over the layers
    (``ref.route``); and how far the ``S`` of ``probe``
    (:func:`state_probe`) lies from the reference's first DeltaNet layer
    after the same tokens (None without a probe). ``control=True`` reads
    instead the gap of the token that the lower precision (``quant`` on the
    matmul operands, ``state_dtype`` for the carried ``S``) puts first, and
    the distance of ITS ``S``."""
    params = make_weights(config, seed)

    def layers(quant, state_dtype):
        return jax.jit(functools.partial(
            ref.layer_forward, cfg=config, quant=quant, state_dtype=state_dtype))

    def head_fn(quant):
        return jax.jit(lambda params, x, start: ref.head_logits(
            params, jax.lax.dynamic_slice_in_dim(x, start, span, axis=0), config, quant))

    layer, low_layer = layers(None, jnp.float32), layers(quant, state_dtype)
    head, low_head = head_fn(None), head_fn(quant)
    embed = jax.jit(ref.embed)

    def padded_to(n):
        return max(-(-n // PAD) * PAD, -(-span // PAD) * PAD)

    def hidden(tokens, layer_fn, layers_run=None):
        """The rows after the last layer run, the smallest margin per
        position and the first DeltaNet layer's ``S`` after the real
        tokens (the padding moves it not)."""
        padded = np.zeros((padded_to(len(tokens)),), np.int32)
        padded[:len(tokens)] = tokens
        x, first = embed(params, jnp.asarray(padded)), None
        worst = jnp.full((len(padded),), jnp.inf, jnp.float32)
        for i in range(layers_run or config["num_hidden_layers"]):
            x, m, s = layer_fn(params["layers"][str(i)], x, state_after=len(tokens))
            worst = jnp.minimum(worst, m)
            first = s if first is None else first
        return x, worst, first

    state_gap = None
    if probe is not None:
        # The first layer is a DeltaNet layer: one layer's run gives its S.
        want = jax.device_get(hidden(probe["tokens"], layer, 1)[2])
        got = probe["S"]
        if control:
            got = np.asarray(jax.device_get(
                hidden(probe["tokens"], low_layer, 1)[2]), np.float32)
        state_gap = state_distance(got, want)

    out = []
    for prompt, served in sample:
        p, a = len(prompt), len(served)
        tokens = np.concatenate([prompt, served])
        # Position p-1+j predicts answer token j.
        start = min(p - 1, padded_to(p + a) - span)
        x, worst, _ = hidden(tokens, layer)
        logits = head(params, x, start)
        picked = None
        if control:
            picked = jnp.argmax(low_head(params, hidden(tokens, low_layer)[0], start), -1)
        logits, worst, picked = jax.device_get((logits, worst, picked))
        off = p - 1 - start
        logits = logits[off:off + a]
        chosen = picked[off:off + a] if control else served
        out.append((logits.max(axis=-1) - logits[np.arange(a), chosen],
                    worst[p - 1:p - 1 + a]))
    return out, state_gap


def summarise(per_request: list, margin: float, state_gap=None) -> dict:
    """The cell's numbers from :func:`reference_numbers`: the widest and
    the mean gap over the positions whose router margin is at least
    ``margin``, the share of positions left out, and the state's gap."""
    kept = np.concatenate([gap[m >= margin] for gap, m in per_request] or [np.zeros(0)])
    positions = sum(len(gap) for gap, _ in per_request)
    return {
        "token_gap_max": float(kept.max()) if len(kept) else None,
        "token_gap_mean": float(kept.mean()) if len(kept) else None,
        "routing_ambiguous_share": (positions - len(kept)) / max(positions, 1),
        "state_gap": state_gap,
    }


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace_dir=None,
        setup_clock=None) -> dict:
    """One run of a serving cell of this family. ``trace_dir`` set = a
    traced run. ``serve.run`` does everything; its reference step lands in
    :func:`reference_numbers`."""
    rule = cell["compare"]
    compared, probe = {}, {}
    before = {id(a) for a in jax.live_arrays()}

    def warm_up_and_probe(engine, config, chunk):
        warm_up(engine, config, chunk)
        probe.update(state_probe(engine, config, rule["state_probe"], seed))

    def reference_gaps(config, seed, sample, *, span):
        # What this run put on the device (12 GB) goes before the
        # reference's weights arrive.
        free_since(before)
        compared["per_request"], compared["state_gap"] = reference_numbers(
            config, seed, sample, span=span, probe=probe)
        compared["tokens"] = int(sum(len(served) for _, served in sample))
        compared["requests"] = len(sample)
        return [0.0]        # serve.run's own number is replaced below

    t = time.perf_counter()
    with _as_serve(
        build_engine=build_engine, warm_up=warm_up_and_probe,
        reference_gaps=reference_gaps,
        counts=types.SimpleNamespace(serve_flops=ref.serve_flops),
    ):
        result = serve.run(cell, config, seed=seed, seconds=seconds,
                           trace_dir=trace_dir, setup_clock=setup_clock)
    if compared:
        result["numbers"] = summarise(
            compared["per_request"], float(rule["margin"]), compared["state_gap"])
        result["reference"] = compared["per_request"]
        result["state_probe"] = probe
        result["compared_tokens"] = compared["tokens"]
        result["compared_requests"] = compared["requests"]
    common.note(setup_clock or common.Clock(t), (
        f"compared {compared.get('tokens')} tokens of {compared.get('requests')} "
        f"requests; numbers {result.get('numbers')}"))
    return result
