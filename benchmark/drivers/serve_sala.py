"""The serving driver for a hybrid of lightning-attention layers (a matrix
state a slot, a fixed decay a head) and block-sparse attention layers (each
query picks ``topk`` pages through a compressed-key cache), dense
feed-forward in every layer (``reference/minicpm_sala_lm.py``): the open
loop, the window, the lead-in, the end-to-end arithmetic and the result's
keys are ``drivers/serve.py``'s — its :func:`~benchmark.drivers.serve.run`
runs here unchanged, with the three things a GPT-2 names by import (the
engine's model and weights, the operation count, the reference's
comparison) taken from this configuration's own reference module, as
``drivers/serve_linear_moe.py`` does for its family.

``correct`` compares, like the other serve drivers, the gap by which a
served token's reference logit lies below the reference's best — over the
SAMPLE of the finished requests that ``serve.run`` picks by the cell's
``compare`` rule (``common.pick_sample``), prefill through chunks and then
decode through pool, compressed keys and state against the reference's
full forward of the whole sequence — leaving out the positions at which
the reference's own selection was within ``margin`` (a relative gap
between the ``topk``-th and the next block score, in any sparse layer and
K/V head) of picking another page: the widest gap (``token_gap_max``),
the mean (``token_gap_mean``) and the share left out
(``selection_ambiguous_share``).

``state_gap`` reads the lightning state ALONE, as the DeltaNet family's
driver does: before the lead-in one request of the cell's
``compare.state_probe`` lengths is served by itself on the warm engine and
the ``S`` its slot then holds in the FIRST layer (a lightning layer whose
input is the embedding itself) is compared with the reference's after the
same tokens (``serve_linear_moe.state_distance``).
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
from benchmark.drivers.serve_linear_moe import state_distance, state_probe  # noqa: F401
from benchmark.reference import minicpm_sala_lm as ref

#: Sequences are padded to a multiple of this before the reference runs:
#: one compile per bucket, not per length.
PAD = 4096


def transformer_config(config: dict):
    """The program's ``TransformerConfig`` for a configuration file of this
    family: every size is the file's; ``minicpm4`` layers are sparse kinds
    with no rotary, ``lightning-attn`` layers the state kind."""
    from rocket_tpu.models.transformer import AttentionKind, TransformerConfig
    from rocket_tpu.nn.lightning import LightningConfig
    from rocket_tpu.ops.paged_attention import SparseAttentionConfig

    z = ref.sizes(config)
    indices = [ref.published_index(config, i) for i in range(z["L"])]
    if indices != list(range(indices[0], indices[0] + z["L"])):
        raise ValueError(f"layer_indices {indices} are not one run of the stack")
    sc = config["sparse_config"]
    return TransformerConfig(
        vocab_size=z["V"], max_seq_len=config["max_position_embeddings"],
        dim=z["d"], num_layers=z["L"], num_heads=z["H"], num_kv_heads=z["Hkv"],
        head_dim=z["head"], dropout=0.0,
        tied_embeddings=bool(config["tie_word_embeddings"]),
        activation_dtype="bfloat16", pos_embedding="rope", rope_base=z["theta"],
        norm="rmsnorm", norm_eps=z["eps"], mlp="swiglu", mlp_hidden=z["inter"],
        mlp_bias=False, attn_bias=bool(config["attention_bias"]), attn_gate=True,
        qk_norm=bool(config["qk_norm"]),
        layer_types=tuple(config["mixer_types"][:z["L"]]),
        attention_kinds={
            "minicpm4": AttentionKind(rope=bool(config["attn_use_rope"]), sparse=True),
            "lightning-attn": AttentionKind(state=True),
        },
        lightning=LightningConfig(
            num_heads=z["Hl"], head_dim=z["dl"], published_layers=z["published"],
            first_layer=indices[0], rope_base=z["theta"]),
        sparse_attention=SparseAttentionConfig(
            kernel_size=sc["kernel_size"], kernel_stride=sc["kernel_stride"],
            block_size=sc["block_size"], topk=sc["topk"],
            init_blocks=sc["init_blocks"], window_size=sc["window_size"],
            dense_len=sc["dense_len"]),
        embed_scale=z["emb"], residual_scale=z["alpha"],
        logit_divisor=z["width_ratio"],
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
        max_model_len=e["max_model_len"],
    ))


def reference_numbers(config: dict, seed: int, sample: list, *, span: int,
                      probe=None, quant=None, dense: bool = False,
                      decay_one: bool = False, control: bool = False) -> tuple:
    """``(per request, state gap)``. For each ``(prompt, served tokens)``
    of ``sample``, per answer position ``(gap, margin)`` arrays: the gap
    by which the served token's reference logit lies below the reference's
    best, and the reference selection's smallest margin there over the
    sparse layers; and how far the ``S`` of ``probe`` lies from the
    reference's first layer after the same tokens (None without a probe).
    ``control=True`` reads instead the gap of the token that the reference
    changed by ``quant`` (a lower precision), ``dense`` (sparse layers that
    attend every position) or ``decay_one`` (no decay) puts first, and the
    distance of ITS ``S``."""
    params = make_weights(config, seed)
    n_layers = config["num_hidden_layers"]

    def layer_fns(**how):
        # One program for the layers of a kind (the decay is an argument).
        fn = jax.jit(functools.partial(ref.layer_forward, cfg=config, **how))
        return [functools.partial(fn, log_lam=ref.layer_decay(config, i))
                for i in range(n_layers)]

    def head_fn(quant):
        return jax.jit(lambda params, x, start: ref.head_logits(
            params, jax.lax.dynamic_slice_in_dim(x, start, span, axis=0), config, quant))

    layers = layer_fns()
    low = dict(quant=quant, dense=dense, decay_one=decay_one)
    low_layers = layer_fns(**low) if control else layers
    head, low_head = head_fn(None), head_fn(quant)
    embed = jax.jit(functools.partial(ref.embed, cfg=config))

    bucket = min(PAD, config["max_position_embeddings"])

    def padded_to(n):
        return max(-(-n // bucket) * bucket, -(-span // bucket) * bucket)

    def hidden(tokens, fns, layers_run=None, state_after=None):
        padded = np.zeros((padded_to(len(tokens)),), np.int32)
        padded[:len(tokens)] = tokens
        x, first = embed(params, jnp.asarray(padded)), None
        worst = jnp.full((len(padded),), jnp.inf, jnp.float32)
        for i in range(layers_run or n_layers):
            x, m, s = fns[i](params["layers"][str(i)], x, state_after=state_after)
            worst = jnp.minimum(worst, m)
            first = s if first is None else first
        return x, worst, first

    state_gap = None
    if probe is not None:
        # The first layer is a lightning layer: one layer's run gives its S.
        n = len(probe["tokens"])
        want = np.asarray(jax.device_get(hidden(probe["tokens"], layers, 1, n)[2]))
        got = probe["S"]
        if control:
            got = np.asarray(jax.device_get(
                hidden(probe["tokens"], low_layers, 1, n)[2]), np.float32)
        state_gap = state_distance(got, want)

    out = []
    for prompt, served in sample:
        p, a = len(prompt), len(served)
        tokens = np.concatenate([prompt, served])
        # Position p-1+j predicts answer token j.
        start = min(p - 1, padded_to(p + a) - span)
        x, worst, _ = hidden(tokens, layers)
        logits = head(params, x, start)
        picked = None
        if control:
            picked = jnp.argmax(low_head(params, hidden(tokens, low_layers)[0], start), -1)
        logits, worst, picked = jax.device_get((logits, worst, picked))
        off = p - 1 - start
        logits = logits[off:off + a]
        chosen = picked[off:off + a] if control else served
        out.append((logits.max(axis=-1) - logits[np.arange(a), chosen],
                    worst[p - 1:p - 1 + a]))
    return out, state_gap


def summarise(per_request: list, margin: float, state_gap=None) -> dict:
    """The cell's numbers from :func:`reference_numbers`: the widest and
    the mean gap over the positions whose selection margin is at least
    ``margin``, the share of positions left out, and the state's gap."""
    kept = np.concatenate([gap[m >= margin] for gap, m in per_request] or [np.zeros(0)])
    positions = sum(len(gap) for gap, _ in per_request)
    return {
        "token_gap_max": float(kept.max()) if len(kept) else None,
        "token_gap_mean": float(kept.mean()) if len(kept) else None,
        "selection_ambiguous_share": (positions - len(kept)) / max(positions, 1),
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
        # What this run put on the device goes before the reference's
        # weights arrive.
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
