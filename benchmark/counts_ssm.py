"""Operations and bytes of the state-space recurrence, from shapes and from
what the traced window served: the arithmetic behind ``ssm_step_roofline``
and ``ssm_scan_roofline``. ONE state layer's work; the reader multiplies by
:func:`state_layers`. Each is checked against a hand count in
``benchmark/tests``. (The whole step's count, ``serve_flops``, is the
configuration's own: ``reference/jamba_lm.py``.)

What is counted is what the recurrence REQUIRES, whatever implements it
(``rocket_tpu/nn/ssm.py``, the functions named ``ssm_step`` and
``ssm_scan`` whose device events the readers sum): per token and channel
and state, ``delta * A``, its exponential, two multiplies and an add into
``h``, a multiply and an add into ``y`` — 7 operations; in bytes the rows
the recurrence reads and writes at the widths the program holds them in
(``u``, ``z``, ``y`` bfloat16; ``delta``, ``B``, ``C`` float32), ``h`` in
float32 read and written, and for a wave the convolution's tail (bfloat16)
read and written. A slot that does not run, and the rows past a chunk's
``valid``, are no required work."""

from __future__ import annotations


def _sizes(config: dict) -> tuple:
    di = config["mamba_expand"] * config["hidden_size"]
    return di, config["mamba_d_state"], config["mamba_d_conv"]


def state_layers(config: dict) -> int:
    """The layers that carry a state: all but every
    ``attn_layer_period``-th from ``attn_layer_offset``."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return sum(i % period != offset for i in range(config["num_hidden_layers"]))


def _row_bytes(di: int, n: int) -> int:
    # u, z, y in bfloat16; delta in float32; B and C in float32.
    return di * (2 + 2 + 2 + 4) + 2 * n * 4


def ssm_step(config: dict, tokens: int) -> dict:
    """ONE state layer's recurrence for ``tokens`` decoded tokens (one row
    of one running slot each): ``h`` read and written, the convolution's
    tail read and written, the token's rows."""
    di, n, k = _sizes(config)
    per_token = 2 * di * n * 4 + 2 * (k - 1) * di * 2 + _row_bytes(di, n)
    return {"flops": 7.0 * tokens * di * n, "bytes": float(tokens * per_token)}


def ssm_scan(config: dict, chunk_rows) -> dict:
    """ONE state layer's recurrence over the prefill chunks of the window:
    ``chunk_rows`` holds each chunk's REAL rows (its ``valid``). A chunk
    reads its slot's ``h`` once and writes it once, and moves the rows of
    its real tokens."""
    di, n, _ = _sizes(config)
    rows = sum(int(r) for r in chunk_rows)
    chunks = len(chunk_rows)
    return {
        "flops": 7.0 * rows * di * n,
        "bytes": float(rows * _row_bytes(di, n) + chunks * 2 * di * n * 4),
    }
