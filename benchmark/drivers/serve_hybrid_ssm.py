"""The serving driver for a hybrid of state-space and attention layers
(``reference/jamba_lm.py``): the open loop, the window, the lead-in, the
end-to-end arithmetic and the result's keys are ``drivers/serve.py``'s — its
:func:`~benchmark.drivers.serve.run` runs here unchanged, with the three
things a GPT-2 names by import (the engine's model and weights, the
operation count, the reference's comparison) taken from this
configuration's own reference module, as ``drivers/serve_latent_moe.py``
does for its family.

``correct`` compares, like the other serve drivers, the gap by which a
served token's reference logit lies below the reference's best — over the
SAMPLE of the finished requests that ``serve.run`` picks by the cell's
``compare`` rule (``common.pick_sample``), prefill through chunks and then
decode through pool and state against the reference's full forward of the
whole sequence: the widest gap (``token_gap_max``) and the mean
(``token_gap_mean``). A state carried wrongly from chunk to chunk or from
wave to wave moves every later token of its request, so it shows in both.

Those two read the state only through the tokens it leads to, beside the
error of twenty-eight layers of bfloat16 activations. ``state_gap`` reads
the state ALONE: before the lead-in one request of the cell's
``compare.state_probe`` lengths (tokens from the seed) is served by itself
on the warm engine — prefill in chunks, the last one padded, then decode
waves — and the ``h`` its slot then holds is fetched and compared, state
layer by state layer, with the ``h`` the reference holds after the same
tokens (:func:`state_distance`: per channel the distance relative to the
reference's norm over that channel's states, the 99th percentile over the
channels). A state carried in less than the configuration's float32 errs
most in the channels that decay slowest — rounding to bfloat16 stalls the
decay wherever ``exp(delta A) > 1 - 2^-9`` — and a norm over the whole
state averages those away under the bfloat16 error of the activations.
``state_gap`` is the distance in the FIRST state layer, whose input is the
embedding itself: there it is the mixer's own arithmetic and the state's
precision, where a deeper layer's is mostly the error of the layers under
it (the whole list goes to standard error). Every state layer runs the one
recurrence over the one array, so what the first shows holds for all. The
probe belongs to set-up and costs the window nothing.
"""

from __future__ import annotations

import functools
import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import common, serve
from benchmark.drivers.serve import GRACE_S, Loop, warm_up  # noqa: F401
from benchmark.drivers.serve_latent_moe import _as_serve
from benchmark.reference import jamba_lm as ref

#: Sequences are padded to a multiple of this before the reference runs:
#: one compile per bucket, not per length — four buckets at 4096 positions.
PAD = 1024


def transformer_config(config: dict):
    """The program's ``TransformerConfig`` for a configuration file of this
    family: every size is the file's."""
    from rocket_tpu.models.transformer import TransformerConfig
    from rocket_tpu.nn.ssm import SSMConfig

    z = ref.sizes(config)
    return TransformerConfig(
        vocab_size=z["V"], max_seq_len=config["max_position_embeddings"],
        dim=z["d"], num_layers=z["L"], num_heads=z["H"], num_kv_heads=z["Hkv"],
        dropout=0.0, tied_embeddings=bool(config["tie_word_embeddings"]),
        activation_dtype="bfloat16", pos_embedding="none", norm="rmsnorm",
        norm_eps=z["eps"], mlp="swiglu", mlp_hidden=z["inter"], mlp_bias=False,
        attn_bias=False,
        ssm=SSMConfig(
            d_inner=z["Di"], dt_rank=z["R"], d_state=z["N"], d_conv=z["K"],
            conv_bias=bool(config["mamba_conv_bias"]),
            proj_bias=bool(config["mamba_proj_bias"]),
        ),
        attn_layer_period=z["period"], attn_layer_offset=z["offset"],
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
        # 262,144 positions the model declares.
        max_model_len=e["max_model_len"],
    ))


def state_probe(engine, config: dict, lengths: dict, seed: int) -> dict:
    """One request served alone on the idle ``engine`` (``lengths``:
    ``prompt`` and ``answer`` tokens; the prompt drawn from ``seed``):
    ``{"tokens": what its slot's state has consumed, "h": that state,
    (state layers, d_state, d_inner) float32}``. An idle engine admits
    into its lowest free slot, 0."""
    rng = np.random.default_rng([int(seed), 35])
    prompt = rng.integers(0, config["vocab_size"], size=int(lengths["prompt"]),
                          dtype=np.int32)
    rid = engine.submit(prompt, max_new_tokens=int(lengths["answer"]), temperature=0.0)
    engine.drain()
    served = np.asarray(engine.result(rid).tokens, np.int32)
    slots = engine.engine
    h_all = slots.pages[len(slots.spec.pages_shapes)]
    # The last token served has been handed out, not yet consumed.
    return {"tokens": np.concatenate([prompt, served[:-1]]),
            "h": np.array(h_all[:, 0], np.float32)}


def state_distance(h, want) -> float:
    """How far a state ``h`` lies from the reference's ``want`` (both
    ``(d_state, d_inner)``): per channel ``|h_c - want_c| / |want_c|``
    over its ``d_state`` states, the 99th percentile over the channels."""
    per_channel = np.linalg.norm(h - want, axis=0) / np.linalg.norm(want, axis=0)
    return float(np.percentile(per_channel, 99))


def reference_numbers(config: dict, seed: int, sample: list, *, span: int,
                      probe=None, quant=None, state_dtype=jnp.float32,
                      control: bool = False) -> tuple:
    """``(gaps, state gaps)``. For each ``(prompt, served tokens)`` of
    ``sample``, the gap per answer position by which the served token's
    reference logit lies below the reference's best; for each state layer,
    how far the ``h`` of ``probe`` (:func:`state_probe`) lies from the
    reference's after the same tokens (None without a probe).
    ``control=True`` reads instead the gap of the token that the lower
    precision (``quant`` on the matmul operands, ``state_dtype`` for the
    carried ``h``) puts first, and the distance of ITS ``h``."""
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

    def hidden(tokens, layer_fn):
        """The rows after the last layer and, per state layer, ``h`` after
        the real tokens (the padding moves neither)."""
        padded = np.zeros((padded_to(len(tokens)),), np.int32)
        padded[:len(tokens)] = tokens
        x, states = embed(params, jnp.asarray(padded)), []
        for i in range(config["num_hidden_layers"]):
            x, h = layer_fn(params["layers"][str(i)], x, state_after=len(tokens))
            states += [] if h is None else [h]
        return x, states

    state_gaps = None
    if probe is not None:
        want = jax.device_get(hidden(probe["tokens"], layer)[1])
        got = probe["h"]
        if control:
            low = jax.device_get(hidden(probe["tokens"], low_layer)[1])
            got = [np.asarray(h, np.float32).T for h in low]
        # The program holds h as (d_state, d_inner), the reference as
        # (d_inner, d_state).
        state_gaps = [state_distance(g, w.T) for g, w in zip(got, want)]

    out = []
    for prompt, served in sample:
        p, a = len(prompt), len(served)
        tokens = np.concatenate([prompt, served])
        # Position p-1+j predicts answer token j.
        start = min(p - 1, padded_to(p + a) - span)
        logits = head(params, hidden(tokens, layer)[0], start)
        picked = None
        if control:
            picked = jnp.argmax(low_head(params, hidden(tokens, low_layer)[0], start), -1)
        logits, picked = jax.device_get((logits, picked))
        off = p - 1 - start
        logits = logits[off:off + a]
        chosen = picked[off:off + a] if control else served
        out.append(logits.max(axis=-1) - logits[np.arange(a), chosen])
    return out, state_gaps


def summarise(per_request: list, state_gaps=None) -> dict:
    """The cell's numbers from :func:`reference_numbers`."""
    gaps = np.concatenate(per_request or [np.zeros(0)])
    return {
        "token_gap_max": float(gaps.max()) if len(gaps) else None,
        "token_gap_mean": float(gaps.mean()) if len(gaps) else None,
        "state_gap": float(state_gaps[0]) if state_gaps else None,
    }


def free_since(before: set) -> None:
    """Delete every device array made since ``before`` (the ``id`` of each
    array alive then): the engine's jitted programs and their trace
    counters hold each other, so letting go of the engine frees nothing."""
    gc.collect()
    for a in jax.live_arrays():
        if id(a) not in before:
            a.delete()


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace_dir=None,
        setup_clock=None) -> dict:
    """One run of a serving cell of this family. ``trace_dir`` set = a
    traced run. ``serve.run`` does everything; its reference step lands in
    :func:`reference_numbers`."""
    compared, probe = {}, {}
    before = {id(a) for a in jax.live_arrays()}

    def warm_up_and_probe(engine, config, chunk):
        warm_up(engine, config, chunk)
        probe.update(state_probe(engine, config, cell["compare"]["state_probe"], seed))

    def reference_gaps(config, seed, sample, *, span):
        # What this run put on the device (7 GB) goes before the
        # reference's weights arrive.
        free_since(before)
        compared["per_request"], compared["state_gaps"] = reference_numbers(
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
        result["numbers"] = summarise(compared["per_request"], compared["state_gaps"])
        result["reference"] = compared["per_request"]
        result["state_probe"] = probe
        result["state_gaps"] = compared["state_gaps"]
        result["compared_tokens"] = compared["tokens"]
        result["compared_requests"] = compared["requests"]
    common.note(setup_clock or common.Clock(t), (
        f"compared {compared.get('tokens')} tokens of {compared.get('requests')} "
        f"requests; numbers {result.get('numbers')}; state gaps by layer "
        f"{[round(g, 5) for g in compared.get('state_gaps') or []]}"))
    return result
