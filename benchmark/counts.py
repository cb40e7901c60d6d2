"""Operations and bytes from shapes: the arithmetic behind every share of a
peak. Found by name from a metric's file; each is checked against a hand
count in ``benchmark/tests``."""

from __future__ import annotations


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    the blocks' four matrices and the (tied) output head. Embedding
    lookups, biases and LayerNorm do no matmul."""
    d, L, V = config["n_embd"], config["n_layer"], config["vocab_size"]
    inner = config.get("n_inner") or 4 * d
    return L * (3 * d * d + d * d + 2 * d * inner) + V * d


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward operations a trained token requires:
    ``6 * N`` for the matmuls (N = matmul parameters) plus ``12 * L * T * d``
    for attention scores and values at full (not causal-halved) T, the
    usual convention (PaLM, appendix B). Recomputed work is not counted."""
    d, L = config["n_embd"], config["n_layer"]
    return 6.0 * matmul_params(config) + 12.0 * L * seq_len * d


def serve_flops(config: dict, positions) -> float:
    """Forward operations to process one token at each of ``positions``
    (0-based index of the token in its sequence), prefill and decode
    alike: ``2 * N`` for the matmuls plus ``4 * L * d`` per attended
    position (scores and values over the ``position + 1`` live keys)."""
    d, L = config["n_embd"], config["n_layer"]
    positions = [int(p) for p in positions]
    attended = sum(positions) + len(positions)
    return 2.0 * matmul_params(config) * len(positions) + 4.0 * L * d * attended


def flash_fwd(config: dict, batch: int, seq_len: int) -> dict:
    """One causal flash-attention forward call over ``(batch, seq_len)``:
    operations (QK^T and PV over the causal half) and the bytes the
    algorithm must move (q, k, v in, o out, bfloat16)."""
    d = config["n_embd"]
    flops = 4.0 * batch * seq_len * seq_len * d / 2
    return {"flops": flops, "bytes": 4.0 * batch * seq_len * d * 2}


def flash_bwd(config: dict, batch: int, seq_len: int) -> dict:
    """One causal flash-attention backward call: five matmuls of the
    forward's two (recomputed scores, dV, dP, dQ, dK) over the causal
    half; q, k, v, o, do in and dq, dk, dv out, bfloat16."""
    d = config["n_embd"]
    flops = 10.0 * batch * seq_len * seq_len * d / 2
    return {"flops": flops, "bytes": 8.0 * batch * seq_len * d * 2}


def paged_decode(config: dict, live_contexts) -> dict:
    """One layer's paged-decode attention over the running slots, from the
    traffic served: K and V rows of each slot's live context, q and out
    (bfloat16); two operations per K element and per V element."""
    d = config["n_embd"]
    rows = sum(int(c) for c in live_contexts)
    slots = len(live_contexts)
    return {
        "flops": 4.0 * rows * d,
        "bytes": (2.0 * rows * d + 2.0 * slots * d) * 2,
    }
