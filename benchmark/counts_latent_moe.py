"""Operations and bytes of the latent-attention decode kernel and of the
held experts' grouped matmuls, from shapes and from what the traced window
served: the arithmetic behind ``mla_decode_roofline`` and
``moe_gmm_roofline``. Each is checked against a hand count in
``benchmark/tests``. (The whole step's count, ``serve_flops``, is the
configuration's own: ``reference/deepseek_v3_lm.py``.)"""

from __future__ import annotations


def mla_decode(config: dict, live_contexts) -> dict:
    """ONE layer's latent decode attention over the running slots, from the
    traffic served: each decoded token of live context ``c`` needs its ``c``
    cached rows (``kv_lora_rank + qk_rope_head_dim`` values, bfloat16) read
    ONCE for all heads, its absorbed query in and its latent output out,
    and per row and head a dot over the whole row (score) and one over the
    latent (value), two operations a multiply-add."""
    heads = config["num_attention_heads"]
    latent = config["kv_lora_rank"]
    row = latent + config["qk_rope_head_dim"]
    rows = sum(int(c) for c in live_contexts)
    slots = len(live_contexts)
    return {
        "flops": 2.0 * rows * heads * (row + latent),
        "bytes": 2.0 * (rows * row + slots * heads * (row + latent)),
    }


def moe_gmm(config: dict, calls) -> dict:
    """The grouped matmuls of the held experts over the traced window.
    ``calls`` holds, for every program call (a decode wave or a prefill
    chunk), ``(kind, pairs)`` with ``pairs[layer][expert]`` the (token,
    choice) pairs each held expert received (the ``moe/expert_pairs``
    counter). A pair costs ``6 * hidden_size * moe_intermediate_size``
    operations (gate, up and down, two a multiply-add) and moves its row in
    and out of both matmuls; an expert that received a pair has its three
    matrices read ONCE in that call, one that received none is not read.
    A prefill chunk's logits are discarded, so its LAST routed layer's
    experts are no required work (the compiler drops them): not counted."""
    d, h = config["hidden_size"], config["moe_intermediate_size"]
    pairs = experts = 0
    for kind, per_layer in calls:
        layers = per_layer[:-1] if kind == "prefill" else per_layer
        for counts in layers:
            pairs += sum(int(c) for c in counts)
            experts += sum(1 for c in counts if int(c) > 0)
    return {
        "flops": 6.0 * pairs * d * h,
        "bytes": 2.0 * (experts * 3 * d * h + pairs * (2 * d + 3 * h)),
    }
