"""Operations and bytes of MiniCPM-SALA's two mixers, from shapes and from
what the traced window served: the arithmetic behind
``lightning_step_roofline``, ``lightning_chunk_roofline``,
``sparse_decode_roofline`` and ``sparse_prefill_roofline``. ONE layer's work of its kind; the reader
multiplies by :func:`lightning_layers` or :func:`sparse_layers`. Each is
checked against a hand count in ``benchmark/tests``. (The whole step's
count, ``serve_flops``, is the configuration's own:
``reference/minicpm_sala_lm.py``.)

What is counted is what the mixer REQUIRES, whatever implements it
(``rocket_tpu/nn/lightning.py`` and ``rocket_tpu/ops/paged_attention.py``,
the functions named ``lightning_step``, ``lightning_chunk``,
``sparse_decode`` and ``sparse_prefill`` whose device events the readers
sum):

* the lightning rule, per token, head and element of ``S``: the decay, a
  multiply and an add into ``S`` (``k^T v``), a multiply and an add into
  ``o = q S`` — 5 operations; in bytes ``q``, ``k``, ``v`` in bfloat16 and
  ``o`` in float32 a row, ``S`` in float32 read and written once a decoded
  token and once a chunk (the chunked form spends more, on the MXU, to
  touch ``S`` once a block: not required work);
* a sparse layer's decode attention, per decoded token: the K and V rows
  its query attends (every position up to it under ``dense_len``; past it
  the ``topk`` picked pages, the last of them up to the query's position)
  of each K/V head, read once for the group's query heads, the query in and
  the output out, and per attended row and query head a dot over the head
  for the score and one for the value (two operations a multiply-add). The
  choice of pages (the compressed keys scored, the top-k) runs in XLA
  before the kernel and is not the kernel's work.

A slot that does not run, and the rows past a chunk's ``valid``, are no
required work."""

from __future__ import annotations


def _layers(config: dict, kind: str) -> int:
    return sum(t == kind for t in config["mixer_types"][:config["num_hidden_layers"]])


def lightning_layers(config: dict) -> int:
    """The layers that carry a lightning state."""
    return _layers(config, "lightning-attn")


def sparse_layers(config: dict) -> int:
    """The layers that attend the pages they pick."""
    return _layers(config, "minicpm4")


def _width(config: dict) -> tuple:
    return config["lightning_nh"], config["lightning_head_dim"]


def _state_bytes(config: dict) -> int:
    h, dh = _width(config)
    return h * dh * dh * 4


def _row_bytes(config: dict) -> int:
    h, dh = _width(config)
    # q, k, v in bfloat16; o in float32.
    return 3 * h * dh * 2 + h * dh * 4


def _rule_flops(config: dict, tokens: int) -> float:
    h, dh = _width(config)
    return 5.0 * tokens * h * dh * dh


def lightning_step(config: dict, tokens: int) -> dict:
    """ONE lightning layer's rule for ``tokens`` decoded tokens (one row of
    one running slot each): ``S`` read and written, the token's rows."""
    per_token = 2 * _state_bytes(config) + _row_bytes(config)
    return {"flops": _rule_flops(config, tokens), "bytes": float(tokens * per_token)}


def lightning_chunk(config: dict, chunk_rows) -> dict:
    """ONE lightning layer's rule over the prefill chunks of the window:
    ``chunk_rows`` holds each chunk's REAL rows. A chunk reads its slot's
    ``S`` once and writes it once, and moves the rows of its real
    tokens."""
    rows = sum(int(r) for r in chunk_rows)
    return {
        "flops": _rule_flops(config, rows),
        "bytes": float(rows * _row_bytes(config)
                       + len(chunk_rows) * 2 * _state_bytes(config)),
    }


def attended_rows(config: dict, position: int) -> int:
    """Key rows one K/V head of a sparse query at ``position`` attends."""
    sc = config["sparse_config"]
    block = sc["block_size"]
    if position < sc["dense_len"]:
        return position + 1
    return min(position + 1, (sc["topk"] - 1) * block + position % block + 1)


def sparse_decode(config: dict, live_contexts) -> dict:
    """ONE sparse layer's decode attention over the running slots, from
    the traffic served: each decoded token of live context ``c`` (its
    query at position ``c - 1``) reads :func:`attended_rows` K rows and as
    many V rows of each
    K/V head (bfloat16), its query in and its output out (bfloat16), and
    computes 4 operations a row and query lane."""
    head = config["head_dim"]
    hkv, hq = config["num_key_value_heads"], config["num_attention_heads"]
    rows = sum(attended_rows(config, int(c) - 1) for c in live_contexts)
    tokens = len(live_contexts)
    return {
        "flops": 4.0 * rows * (hq // hkv) * hkv * head,
        "bytes": 2.0 * (2 * rows * hkv * head + 2 * tokens * hq * head),
    }


def sparse_prefill(config: dict, chunks) -> dict:
    """ONE sparse layer's chunk attention over the prefill chunks of the
    window, ``chunks`` holding each chunk's ``(start, valid)``: every real
    row at position ``p`` attends :func:`attended_rows` keys of each K/V
    head (4 operations a key and query lane); the rows' queries come in and
    their outputs go out (bfloat16), and the K and V of the keys the
    chunk's last row attends are read once (the pages other rows pick
    beside them are not counted: a lower bound of the bytes)."""
    head = config["head_dim"]
    hkv, hq = config["num_key_value_heads"], config["num_attention_heads"]
    keys = rows = kv = 0
    for start, valid in chunks:
        start, valid = int(start), int(valid)
        keys += sum(attended_rows(config, p) for p in range(start, start + valid))
        rows += valid
        kv += attended_rows(config, start + valid - 1) if valid else 0
    return {
        "flops": 4.0 * keys * hq * head,
        "bytes": 2.0 * (2 * rows * hq * head + 2 * kv * hkv * head),
    }
