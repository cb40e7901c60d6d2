"""Operations and bytes of the sliding-window decode kernel
(``window_decode``, ``ops/paged_attention.py``), from shapes and from what
the traced window served: the arithmetic behind ``window_decode_roofline``.
ONE window layer's work; the reader multiplies by :func:`window_layers`.
Checked against a hand count in ``benchmark/tests``. (The whole step's
count, ``serve_flops``, is the configuration's own:
``reference/laguna_lm.py``.)"""

from __future__ import annotations


def _sliding(config: dict) -> list:
    """Indices of the sliding-window layers among the configuration's
    ``num_hidden_layers`` (the first entries of ``layer_types``)."""
    return [i for i in range(config["num_hidden_layers"])
            if config["layer_types"][i] == "sliding_attention"]


def window_layers(config: dict) -> int:
    """The layers that attend a window over a ring a slot."""
    return len(_sliding(config))


def window_decode(config: dict, live_contexts) -> dict:
    """ONE window layer's decode attention over the running slots, from the
    traffic served: each decoded token of live context ``c`` needs its
    ``min(c, sliding_window)`` K rows and as many V rows (the K/V heads'
    lanes, bfloat16) read ONCE for all the query heads they serve, its
    query in and its output out (the window layer's heads), and per row
    and query head a dot over the head for the score and one for the
    value, two operations a multiply-add."""
    layer = _sliding(config)[0]
    head = config["head_dim"]
    q_lanes = config["num_attention_heads_per_layer"][layer] * head
    kv_lanes = config["num_key_value_heads"] * head
    window = config["sliding_window"]
    rows = sum(min(int(c), window) for c in live_contexts)
    slots = len(live_contexts)
    return {
        "flops": 4.0 * rows * q_lanes,
        "bytes": 2.0 * (2 * rows * kv_lanes + 2 * slots * q_lanes),
    }
