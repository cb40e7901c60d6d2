"""Operations and bytes of the gated delta rule, from shapes and from what
the traced window served: the arithmetic behind ``gdn_step_roofline`` and
``gdn_chunk_roofline``. ONE DeltaNet layer's work; the reader multiplies by
:func:`state_layers`. Each is checked against a hand count in
``benchmark/tests``. (The whole step's count, ``serve_flops``, is the
configuration's own: ``reference/qwen3_next_lm.py``.)

What is counted is what the rule REQUIRES, whatever implements it
(``rocket_tpu/nn/gdn.py``, the functions named ``gdn_step`` and
``gdn_chunk`` whose device events the readers sum): per token, value head
and element of ``S`` (key x value), the decay, a multiply and an add into
``r = S^T k``, a multiply and an add into ``S``, a multiply and an add into
``o = S^T q`` — 7 operations (the chunked form spends more, on the MXU, to
touch ``S`` once a block: not required work); in bytes the rows the rule
reads and writes at the widths the program holds them in (``q``, ``k``,
``v``, ``z`` bfloat16; ``g``, ``beta``, ``o`` float32), ``S`` in float32
read and written, and for a wave the convolution's tail (bfloat16) read and
written. A slot that does not run, and the rows past a chunk's ``valid``,
are no required work."""

from __future__ import annotations


def _sizes(config: dict) -> tuple:
    return (config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"])


def state_layers(config: dict) -> int:
    """The layers that carry a state: all but every
    ``full_attention_interval``-th."""
    every = config["full_attention_interval"]
    return sum((i + 1) % every != 0 for i in range(config["num_hidden_layers"]))


def _state_bytes(config: dict) -> int:
    _, hv, dk, dv, _ = _sizes(config)
    return hv * dk * dv * 4


def _row_bytes(config: dict) -> int:
    hk, hv, dk, dv, _ = _sizes(config)
    # q, k, v, z in bfloat16; o in float32; g and beta in float32.
    return (2 * hk * dk + 2 * hv * dv) * 2 + hv * dv * 4 + 2 * hv * 4


def _rule_flops(config: dict, tokens: int) -> float:
    _, hv, dk, dv, _ = _sizes(config)
    return 7.0 * tokens * hv * dk * dv


def gdn_step(config: dict, tokens: int) -> dict:
    """ONE DeltaNet layer's rule for ``tokens`` decoded tokens (one row of
    one running slot each): ``S`` read and written, the convolution's tail
    read and written, the token's rows."""
    hk, hv, dk, dv, k = _sizes(config)
    tail = 2 * (k - 1) * (2 * hk * dk + hv * dv) * 2
    per_token = 2 * _state_bytes(config) + tail + _row_bytes(config)
    return {"flops": _rule_flops(config, tokens), "bytes": float(tokens * per_token)}


def gdn_chunk(config: dict, chunk_rows) -> dict:
    """ONE DeltaNet layer's rule over the prefill chunks of the window:
    ``chunk_rows`` holds each chunk's REAL rows (its ``valid``). A chunk
    reads its slot's ``S`` once and writes it once, and moves the rows of
    its real tokens."""
    rows = sum(int(r) for r in chunk_rows)
    chunks = len(chunk_rows)
    return {
        "flops": _rule_flops(config, rows),
        "bytes": float(rows * _row_bytes(config) + chunks * 2 * _state_bytes(config)),
    }
