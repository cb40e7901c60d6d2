"""Operations and bytes of the paged-decode kernel for a model whose query
heads share fewer K/V heads, whose head width is its own and of whose
layers only some cache pages: the arithmetic behind
``paged_gqa_decode_roofline`` (``counts.paged_decode`` reads GPT-2's
``n_embd`` and counts every layer). ONE caching layer's work; the reader
multiplies by :func:`cache_layers`. Checked against a hand count in
``benchmark/tests``."""

from __future__ import annotations


def _widths(config: dict) -> tuple:
    """``(query lanes, K/V lanes)`` of one attention layer."""
    heads = config["num_attention_heads"]
    head = config.get("head_dim") or config["hidden_size"] // heads
    return heads * head, config["num_key_value_heads"] * head


def cache_layers(config: dict) -> int:
    """The layers that attend over cached pages: every
    ``full_attention_interval``-th, or those at ``attn_layer_offset`` in
    each ``attn_layer_period``, or all of them."""
    layers = range(config["num_hidden_layers"])
    if "full_attention_interval" in config:
        return sum((i + 1) % config["full_attention_interval"] == 0 for i in layers)
    if "attn_layer_period" in config:
        return sum(i % config["attn_layer_period"] == config["attn_layer_offset"]
                   for i in layers)
    return len(layers)


def paged_decode(config: dict, live_contexts) -> dict:
    """ONE caching layer's paged-decode attention over the running slots,
    from the traffic served: each decoded token of live context ``c`` needs
    its ``c`` K rows and ``c`` V rows (the K/V heads' lanes, bfloat16) read
    ONCE for all the query heads they serve, its query in and its output
    out, and per row and query head a dot over the head for the score and
    one for the value, two operations a multiply-add."""
    q_lanes, kv_lanes = _widths(config)
    rows = sum(int(c) for c in live_contexts)
    slots = len(live_contexts)
    return {
        "flops": 4.0 * rows * q_lanes,
        "bytes": 2.0 * (2 * rows * kv_lanes + 2 * slots * q_lanes),
    }
