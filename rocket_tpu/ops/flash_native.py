"""Flash attention, native-layout generation — zero operand layout copies.

Second-generation pallas TPU kernel (see ``ops/flash_attention.py`` for the
first, which remains the ring-attention inner op). The round-2 profiler
trace charged ~6 ms/step of a GPT-2 124M step to pallas operand layout
copies: the fused QKV projection emits ``(B, T, 3*H*D)`` while the old
kernel wants ``(3, B, H, T, D)``, and pallas pins operands to their default
layout, so XLA materialized a physical transpose in AND out every layer.

This kernel consumes the projection output's OWN layout:

* operands are ``(B, T, F)`` feature-major arrays — for the fused MHA path
  literally the ``(B, T, 3*H*D)`` projection output (one operand, three
  BlockSpecs indexing the q/k/v feature offsets), for the GQA/RoPE path the
  ``(B, T, Hq*D)`` / ``(B, T, Hkv*D)`` arrays RoPE writes anyway. Splitting
  ``(B, T, 3HD) -> (B, T, 3, H, D)`` is a free bitcast; no transposes exist
  anywhere in the data path, and the output ``(B, T, H*D)`` feeds the
  output projection directly;
* grouped-query attention is native (round-2 verdict weak #5): the grid
  iterates KV heads and each grid step serves that head's whole group of
  ``g = Hq/Hkv`` query heads via feature-offset slices — K/V HBM traffic is
  ``Hkv``-sized, never repeated to full heads;
* scores are computed TRANSPOSED — ``(bk, bq)``, q along lanes — in BOTH
  passes, so every softmax statistic (running max, normalizer, lse, delta)
  is a ``(1, bq)`` row that broadcasts across the sublane (k) dim natively:
  the kernel contains zero in-kernel transposes except one per-q-block
  relayout of the output accumulator at flush time (1/nk of tile work);
* per-head matmuls are plain 2D ``dot_general``s on lane-sliced operands
  (head j = ``tile[:, j*D:(j+1)*D]``) — no batched dims, no sublane-padded
  rank-4 blocks; with ``D = 64`` two MHA heads pack into one 128-lane
  feature block (``kv_block`` heads per grid step);
* same numerics as the first-generation kernel: base-2 online softmax, f32
  statistics/accumulators over bf16 operands, causal masking only on
  diagonal blocks;
* backward has two strategies, selected by kv-block count ``nk``. Default:
  one fused pass with dk/dv accumulated in f32 scratch across the query
  sweep and dq written as per-kv-block f32 partials summed by one XLA add
  outside (f32 per the round-3 advisor — a bf16 partial would round before
  the sum, with error growing in nk). When the O(nk) x dq partial buffer
  would exceed ``_DQ_PARTIALS_MAX_BYTES`` (a multi-GB allocation at large
  B*T), dq moves to its own kernel with the transposed sweep (ik
  innermost) accumulating in f32 scratch — linear HBM, at the price of
  recomputing the score matmuls (7 vs 5 backward matmuls; measured ~9%
  slower attention-bwd at T=8192, faster only in memory terms — numbers
  at ``_DQ_PARTIALS_MAX_BYTES`` below).

The reference framework has no attention code (SURVEY §0); this op backs
the north-star transformer configs (BASELINE.json configs[2,4]).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocket_tpu.ops.flash_attention import (
    _check_causal_blocks,
    resolve_tuned_blocks,
)

__all__ = [
    "flash_fused",
    "flash_fused_sharded",
    "flash_bthd",
    "flash_bthd_sharded",
]

_NEG_INF = -1e30
_LOG2E = math.log2(math.e)


def _interpret_default() -> bool:
    return jax.devices()[0].platform == "cpu"


def _kv_block(h_kv: int, g: int, d: int, q_total: int, kv_total: int) -> int:
    """KV heads per grid step.

    Mosaic requires a block's last dim to be a multiple of 128 lanes or
    equal to the whole array dim, so ``kb`` is the smallest divisor of
    ``h_kv`` making both the q width (kb*g*d) and the kv width (kb*d)
    legal; the fallback kb = h_kv always is (whole-feature blocks). Larger
    kb also packs small heads into full lane tiles (two D=64 MHA heads per
    128-lane block)."""
    def ok(width, total):
        return width % 128 == 0 or width == total

    legal = [
        kb for kb in range(1, h_kv + 1)
        if h_kv % kb == 0
        and ok(kb * g * d, q_total) and ok(kb * d, kv_total)
    ]
    if not legal:
        return h_kv  # whole-feature blocks always satisfy the width rule
    # Among legal blockings prefer a ~256-lane q tile: chip A/B at GPT-2
    # shapes measured kb=4 (256 lanes) ~15% faster than kb=2 (128) and
    # kb=6 (384) ~2x slower (VMEM/register pressure past two lane tiles).
    return min(legal, key=lambda kb: (abs(kb * g * d - 256), kb))


def _fused_kb(h: int, d: int) -> Optional[int]:
    """kb for the single-operand fused path, or None when no legal blocking
    exists (the fused feature dim 3*H*D is never equal to a block width, so
    widths must be true 128-multiples; callers then fall back to sliced
    operands). Same ~256-lane preference as :func:`_kv_block`."""
    legal = [
        kb for kb in range(1, h + 1)
        if h % kb == 0 and (kb * d) % 128 == 0
    ]
    if not legal:
        return None
    return min(legal, key=lambda kb: (abs(kb * d - 256), kb))


def _causal_mask_t(s):
    """Transposed-block causal mask: ``s`` is (bk, bq) on an aligned
    diagonal block — keep k_idx (rows) <= q_idx (cols)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows <= cols, s, _NEG_INF)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                kb, g, d, scale2, causal):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def tile(masked: bool):
        for jk in range(kb):
            k = k_ref[0, :, jk * d:(jk + 1) * d]  # (bk, d)
            v = v_ref[0, :, jk * d:(jk + 1) * d]  # (bk, d)
            for jq in range(g):
                row = jk * g + jq
                q = q_ref[0, :, row * d:(row + 1) * d]  # (bq, d)
                # Transposed scores (bk, bq): stats become (1, bq) rows.
                s2t = jax.lax.dot_general(
                    k, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale2
                if masked:
                    s2t = _causal_mask_t(s2t)
                m_prev = m_s[row:row + 1]  # (1, bq)
                m_new = jnp.maximum(
                    m_prev, jnp.max(s2t, axis=0, keepdims=True)
                )
                p = jnp.exp2(s2t - m_new)  # (bk, bq)
                alpha = jnp.exp2(m_prev - m_new)  # (1, bq)
                l_s[row:row + 1] = (
                    l_s[row:row + 1] * alpha
                    + jnp.sum(p, axis=0, keepdims=True)
                )
                # pv transposed: (d, bq) — alpha rows broadcast over the
                # feature sublanes of the (F, bq) accumulator.
                pv_t = jax.lax.dot_general(
                    v, p.astype(v_ref.dtype), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                acc[row * d:(row + 1) * d] = (
                    acc[row * d:(row + 1) * d] * alpha + pv_t
                )
                m_s[row:row + 1] = m_new

    if causal:
        @pl.when(ik < iq)
        def _interior():
            tile(masked=False)

        @pl.when(ik == iq)
        def _diagonal():
            tile(masked=True)
    else:
        tile(masked=False)

    @pl.when(ik == nk - 1)
    def _flush():
        l = l_s[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # (kb*g, bq)
        # Normalize in the transposed domain (per-head l rows broadcast over
        # that head's d sublane rows), then ONE relayout to (bq, F).
        inv = 1.0 / safe_l
        inv_f = jnp.repeat(inv, d, axis=0)  # (kb*g*d, bq)
        o_ref[0] = jnp.swapaxes(acc[:] * inv_f, 0, 1).astype(o_ref.dtype)
        # lse in base-2, (heads, bq) rows — HBM array (B, H/(kb*g), kb*g, T).
        lse_ref[0, 0] = m_s[:] + jnp.log2(safe_l)


def _fwd(q_arr, k_arr, v_arr, *, h, h_kv, d, kb, q_off, k_off, v_off,
         causal, block_q, block_k, interpret):
    _check_causal_blocks(block_q, block_k, causal, "flash_native._fwd")
    b, t, _ = q_arr.shape
    g = h // h_kv
    scale2 = _LOG2E / math.sqrt(d)
    nq, nk = t // block_q, t // block_k
    qw, kw = kb * g * d, kb * d  # feature widths per grid step

    # Feature offsets are in units of the respective block widths so the
    # index_map can address them; guaranteed by callers (q_off=0 etc.).
    assert q_off % qw == 0 and k_off % kw == 0 and v_off % kw == 0

    qs = pl.BlockSpec(
        (1, block_q, qw),
        lambda b, hh, iq, ik: (b, iq, q_off // qw + hh),
    )
    ks = pl.BlockSpec(
        (1, block_k, kw),
        lambda b, hh, iq, ik: (b, ik, k_off // kw + hh),
    )
    vs = pl.BlockSpec(
        (1, block_k, kw),
        lambda b, hh, iq, ik: (b, ik, v_off // kw + hh),
    )

    kernel = functools.partial(
        _fwd_kernel, kb=kb, g=g, d=d, scale2=scale2, causal=causal
    )
    # lse lives as (B, H/(kb*g) blocks, kb*g rows, T): the head-block dim
    # equals the whole array dim, satisfying Mosaic's block-shape rule for
    # any kb*g (a flat (B, H, T) head dim would need kb*g % 8 == 0).
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h_kv // kb, nq, nk),
        in_specs=[qs, ks, vs],
        out_specs=[
            pl.BlockSpec(
                (1, block_q, qw), lambda b, hh, iq, ik: (b, iq, hh)
            ),
            pl.BlockSpec(
                (1, 1, kb * g, block_q), lambda b, hh, iq, ik: (b, hh, 0, iq)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, h * d), q_arr.dtype),
            jax.ShapeDtypeStruct((b, h // (kb * g), kb * g, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((kb * g * d, block_q), jnp.float32),
            pltpu.VMEM((kb * g, block_q), jnp.float32),
            pltpu.VMEM((kb * g, block_q), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q_arr, k_arr, v_arr)
    return out, lse


# --------------------------------------------------------------------------
# backward — one fused pass
# --------------------------------------------------------------------------


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                *refs, kb, g, d, scale, scale2, causal, with_dq):
    """dk/dv sweep (iq innermost). With ``with_dq`` it also emits per-kv-
    block dq partials (f32, summed by one XLA add outside) — the fused
    one-pass strategy for small nk."""
    if with_dq:
        dqp_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile(masked: bool):
        for jk in range(kb):
            k = k_ref[0, :, jk * d:(jk + 1) * d]  # (bk, d)
            v = v_ref[0, :, jk * d:(jk + 1) * d]
            for jq in range(g):
                row = jk * g + jq
                q = q_ref[0, :, row * d:(row + 1) * d]  # (bq, d)
                do = do_ref[0, :, row * d:(row + 1) * d]  # (bq, d)
                s2t = jax.lax.dot_general(
                    k, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale2  # (bk, bq)
                if masked:
                    s2t = _causal_mask_t(s2t)
                pt = jnp.exp2(s2t - lse_ref[0, 0, row:row + 1])  # (bk, bq)
                ptc = pt.astype(do.dtype)
                dv_acc[:, jk * d:(jk + 1) * d] += jax.lax.dot_general(
                    ptc, do, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # (bk, d)
                dpt = jax.lax.dot_general(
                    v, do, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # (bk, bq)
                ds_t = pt * (dpt - delta_ref[0, 0, row:row + 1]) * scale
                ds_c = ds_t.astype(q.dtype)
                dk_acc[:, jk * d:(jk + 1) * d] += jax.lax.dot_general(
                    ds_c, q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # (bk, d)
                if with_dq:
                    # This kv block's dq contribution — summed outside.
                    dqp_ref[0, 0, :, row * d:(row + 1) * d] = (
                        jax.lax.dot_general(
                            ds_c, k, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                    )  # (bq, d), f32

    if causal:
        @pl.when(ik < iq)
        def _interior():
            tile(masked=False)

        @pl.when(ik == iq)
        def _diagonal():
            tile(masked=True)

        if with_dq:
            @pl.when(ik > iq)
            def _skipped():
                dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])
    else:
        tile(masked=False)

    @pl.when(iq == nq - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, kb, g, d, scale, scale2, causal):
    """Accumulating dq sweep (ik innermost): recomputes the score and dp
    matmuls but writes dq ONCE per q block from f32 scratch — HBM linear in
    T where the partial strategy's O(nk) x dq buffer is quadratic."""
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def tile(masked: bool):
        for jk in range(kb):
            k = k_ref[0, :, jk * d:(jk + 1) * d]  # (bk, d)
            v = v_ref[0, :, jk * d:(jk + 1) * d]
            for jq in range(g):
                row = jk * g + jq
                q = q_ref[0, :, row * d:(row + 1) * d]  # (bq, d)
                do = do_ref[0, :, row * d:(row + 1) * d]  # (bq, d)
                s2t = jax.lax.dot_general(
                    k, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale2  # (bk, bq)
                if masked:
                    s2t = _causal_mask_t(s2t)
                pt = jnp.exp2(s2t - lse_ref[0, 0, row:row + 1])  # (bk, bq)
                dpt = jax.lax.dot_general(
                    v, do, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # (bk, bq)
                ds_t = pt * (dpt - delta_ref[0, 0, row:row + 1]) * scale
                ds_c = ds_t.astype(q.dtype)
                dq_acc[:, row * d:(row + 1) * d] += jax.lax.dot_general(
                    ds_c, k, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # (bq, d)

    if causal:
        @pl.when(ik < iq)
        def _interior():
            tile(masked=False)

        @pl.when(ik == iq)
        def _diagonal():
            tile(masked=True)
    else:
        tile(masked=False)

    @pl.when(ik == nk - 1)
    def _flush():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


#: Partial-buffer byte bound at which the backward switches from the
#: fused one-pass kernel (dq as O(nk) x dq f32 partials summed outside —
#: quadratic HBM in T) to the split accumulating dq kernel (linear HBM,
#: ~2 extra score matmuls). Chip A/B (GPT-2 dims, block 512): partials
#: are FASTER at every measured length — T=1024/nk=2: 125.8k vs 121.2k
#: tok/s full-model; T=4096/nk=8: 6.7 vs 6.8 ms; T=8192/nk=16: 8.5 vs
#: 9.3 ms attention-only (and e2e llama T=8192 B=1 measured ~5% faster
#: on partials) — the split's recomputed score matmuls cost more than
#: the partial traffic. The split is purely the MEMORY guard: the f32
#: partial buffer is nk*B*T*Hq*D*4 bytes (~3 GB at B=8, T=8192, GPT-2
#: dims); past this bound the ~9% attention-bwd premium buys back that
#: allocation. Ring attention remains the real long-T answer
#: (docs/performance.md).
_DQ_PARTIALS_MAX_BYTES = 1 << 30


def _bwd_arrays(q_arr, k_arr, v_arr, out, lse, dout, *, h, h_kv, d, kb,
                q_off, k_off, v_off, causal, block_q, block_k, interpret,
                dq_split=None):
    """Shared backward body -> (dq (B,T,HqD), dk (B,T,HkvD), dv)."""
    _check_causal_blocks(block_q, block_k, causal, "flash_native._bwd")
    b, t, _ = q_arr.shape
    g = h // h_kv
    scale = 1.0 / math.sqrt(d)
    scale2 = _LOG2E / math.sqrt(d)
    nq, nk = t // block_q, t // block_k
    qw, kw = kb * g * d, kb * d
    if dq_split is None:
        dq_split = nk * b * t * h * d * 4 > _DQ_PARTIALS_MAX_BYTES

    # delta = rowsum(dout * out) per head, in lse's blocked head layout.
    delta = jnp.swapaxes(
        jnp.sum(
            (dout.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
                b, t, h, d
            ),
            axis=-1,
        ),
        1, 2,
    ).reshape(b, h // (kb * g), kb * g, t)

    compiler_params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )

    # dk/dv (+ dq partials when fused): grid (b, hh, ik, iq), iq innermost.
    qs = pl.BlockSpec(
        (1, block_q, qw), lambda b, hh, ik, iq: (b, iq, q_off // qw + hh)
    )
    ks = pl.BlockSpec(
        (1, block_k, kw), lambda b, hh, ik, iq: (b, ik, k_off // kw + hh)
    )
    vs = pl.BlockSpec(
        (1, block_k, kw), lambda b, hh, ik, iq: (b, ik, v_off // kw + hh)
    )
    in_specs = [
        qs, ks, vs,
        pl.BlockSpec(
            (1, block_q, qw), lambda b, hh, ik, iq: (b, iq, hh)
        ),
        pl.BlockSpec(
            (1, 1, kb * g, block_q), lambda b, hh, ik, iq: (b, hh, 0, iq)
        ),
        pl.BlockSpec(
            (1, 1, kb * g, block_q), lambda b, hh, ik, iq: (b, hh, 0, iq)
        ),
    ]
    kv_specs = [
        pl.BlockSpec((1, block_k, kw), lambda b, hh, ik, iq: (b, ik, hh)),
        pl.BlockSpec((1, block_k, kw), lambda b, hh, ik, iq: (b, ik, hh)),
    ]
    kv_shapes = [
        jax.ShapeDtypeStruct((b, t, h_kv * d), q_arr.dtype),
        jax.ShapeDtypeStruct((b, t, h_kv * d), q_arr.dtype),
    ]
    dqp_spec = pl.BlockSpec(
        (1, 1, block_q, qw), lambda b, hh, ik, iq: (ik, b, iq, hh)
    )
    kernel = functools.partial(
        _bwd_kernel, kb=kb, g=g, d=d, scale=scale, scale2=scale2,
        causal=causal, with_dq=not dq_split,
    )
    outs = pl.pallas_call(
        kernel,
        grid=(b, h_kv // kb, nk, nq),
        in_specs=in_specs,
        out_specs=([] if dq_split else [dqp_spec]) + kv_specs,
        out_shape=(
            [] if dq_split
            # f32 partials: a bf16 partial would round BEFORE the outer
            # sum, with dq error growing in nk (round-3 advisor finding);
            # dk/dv already accumulate in f32 scratch.
            else [jax.ShapeDtypeStruct((nk, b, t, h * d), jnp.float32)]
        ) + kv_shapes,
        scratch_shapes=[
            pltpu.VMEM((block_k, kw), jnp.float32),
            pltpu.VMEM((block_k, kw), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q_arr, k_arr, v_arr, dout, lse, delta)

    if dq_split:
        dk, dv = outs
        # dq: grid (b, hh, iq, ik), ik innermost — accumulate in scratch,
        # one write per q block.
        dq, = pl.pallas_call(
            functools.partial(
                _dq_kernel, kb=kb, g=g, d=d, scale=scale, scale2=scale2,
                causal=causal,
            ),
            grid=(b, h_kv // kb, nq, nk),
            in_specs=[
                pl.BlockSpec(
                    (1, block_q, qw),
                    lambda b, hh, iq, ik: (b, iq, q_off // qw + hh),
                ),
                pl.BlockSpec(
                    (1, block_k, kw),
                    lambda b, hh, iq, ik: (b, ik, k_off // kw + hh),
                ),
                pl.BlockSpec(
                    (1, block_k, kw),
                    lambda b, hh, iq, ik: (b, ik, v_off // kw + hh),
                ),
                pl.BlockSpec(
                    (1, block_q, qw), lambda b, hh, iq, ik: (b, iq, hh)
                ),
                pl.BlockSpec(
                    (1, 1, kb * g, block_q),
                    lambda b, hh, iq, ik: (b, hh, 0, iq),
                ),
                pl.BlockSpec(
                    (1, 1, kb * g, block_q),
                    lambda b, hh, iq, ik: (b, hh, 0, iq),
                ),
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, block_q, qw), lambda b, hh, iq, ik: (b, iq, hh)
                ),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, t, h * d), q_arr.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((block_q, qw), jnp.float32)],
            compiler_params=compiler_params,
            interpret=interpret,
            name="flash_bwd_dq",
        )(q_arr, k_arr, v_arr, dout, lse, delta)
        return dq, dk, dv

    dq_part, dk, dv = outs
    dq = (dq_part[0] if nk == 1 else jnp.sum(dq_part, axis=0)).astype(
        q_arr.dtype
    )
    return dq, dk, dv


# --------------------------------------------------------------------------
# public op: fused single-operand MHA (the GPT-2 hot path)
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _flash_fused(fused, h, d, causal, blocks, interpret, dq_split):
    out, _ = _fwd(
        fused, fused, fused, h=h, h_kv=h, d=d, kb=_fused_kb(h, d),
        q_off=0, k_off=h * d, v_off=2 * h * d,
        causal=causal, block_q=blocks[0], block_k=blocks[1],
        interpret=interpret,
    )
    return out


def _flash_fused_fwd(fused, h, d, causal, blocks, interpret, dq_split):
    out, lse = _fwd(
        fused, fused, fused, h=h, h_kv=h, d=d, kb=_fused_kb(h, d),
        q_off=0, k_off=h * d, v_off=2 * h * d,
        causal=causal, block_q=blocks[0], block_k=blocks[1],
        interpret=interpret,
    )
    return out, (fused, out, lse)


def _flash_fused_bwd(h, d, causal, blocks, interpret, dq_split, res, dout):
    fused, out, lse = res
    dq, dk, dv = _bwd_arrays(
        fused, fused, fused, out, lse, dout, h=h, h_kv=h, d=d,
        kb=_fused_kb(h, d),
        q_off=0, k_off=h * d, v_off=2 * h * d,
        causal=causal, block_q=blocks[2], block_k=blocks[3],
        interpret=interpret, dq_split=dq_split,
    )
    return (jnp.concatenate([dq, dk, dv], axis=-1),)


_flash_fused.defvjp(_flash_fused_fwd, _flash_fused_bwd)


def flash_fused(
    fused: jax.Array,
    num_heads: int,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    dq_split: Optional[bool] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
) -> jax.Array:
    """Flash attention directly on the fused QKV projection output.

    ``fused`` is (B, T, 3*H*D) laid out ``[q | k | v]`` along features
    (each segment head-major) — exactly what ``MultiHeadAttention.qkv``
    emits. Zero layout copies: three BlockSpecs index the q/k/v offsets of
    the ONE operand. Returns (B, T, H*D), ready for the output projection.
    Differentiable (custom VJP, one-pass fused backward producing the
    (B, T, 3*H*D) cotangent).

    Block sizes left ``None`` resolve through the tuned-config table
    (``rocket_tpu.tune`` — ``flash_fwd``/``flash_bwd`` entries for this
    device kind / shape bucket / dtype), falling back to the hand-picked
    512s with the backward riding the forward's blocks; explicit values
    always win.

    ``dq_split``: backward dq strategy — None (default) picks by the
    partial-buffer footprint (``_DQ_PARTIALS_MAX_BYTES``); False forces
    the fused f32-partials pass (fastest, O(nk) x dq HBM); True forces
    the separate accumulating dq kernel (linear HBM, ~9% slower
    attention-bwd — the memory-bound escape below the automatic bound).
    """
    b, t, f = fused.shape
    if f % (3 * num_heads):
        raise ValueError(
            f"flash_fused: feature dim {f} is not 3*H*D for H={num_heads}"
        )
    d = f // (3 * num_heads)
    blocks = resolve_tuned_blocks(
        t, d, num_heads, num_heads, fused.dtype, causal,
        block_q, block_k, bwd_block_q, bwd_block_k,
    )
    if interpret is None:
        interpret = _interpret_default()
    if _fused_kb(num_heads, d) is None:
        # No 128-multiple head blocking exists inside the fused operand
        # (e.g. odd head counts at D=64): slice the segments — the separate
        # (B, T, H*D) operands may use whole-feature blocks.
        hd = num_heads * d
        return flash_bthd(
            fused[..., :hd], fused[..., hd:2 * hd], fused[..., 2 * hd:],
            num_heads, causal=causal, block_q=blocks[0], block_k=blocks[1],
            interpret=interpret, dq_split=dq_split,
            bwd_block_q=blocks[2], bwd_block_k=blocks[3],
        )
    return _flash_fused(
        fused, num_heads, d, causal, blocks, interpret, dq_split
    )


# --------------------------------------------------------------------------
# public op: separate-operand (B, T, F) attention — GQA / RoPE / TP path
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_bthd(q2, k2, v2, h, h_kv, d, causal, blocks, interpret, dq_split):
    kb = _kv_block(h_kv, h // h_kv, d, h * d, h_kv * d)
    out, _ = _fwd(
        q2, k2, v2, h=h, h_kv=h_kv, d=d, kb=kb,
        q_off=0, k_off=0, v_off=0,
        causal=causal, block_q=blocks[0], block_k=blocks[1],
        interpret=interpret,
    )
    return out


def _flash_bthd_fwd(q2, k2, v2, h, h_kv, d, causal, blocks, interpret,
                    dq_split):
    kb = _kv_block(h_kv, h // h_kv, d, h * d, h_kv * d)
    out, lse = _fwd(
        q2, k2, v2, h=h, h_kv=h_kv, d=d, kb=kb,
        q_off=0, k_off=0, v_off=0,
        causal=causal, block_q=blocks[0], block_k=blocks[1],
        interpret=interpret,
    )
    return out, (q2, k2, v2, out, lse)


def _flash_bthd_bwd(h, h_kv, d, causal, blocks, interpret, dq_split,
                    res, dout):
    q2, k2, v2, out, lse = res
    kb = _kv_block(h_kv, h // h_kv, d, h * d, h_kv * d)
    return _bwd_arrays(
        q2, k2, v2, out, lse, dout, h=h, h_kv=h_kv, d=d, kb=kb,
        q_off=0, k_off=0, v_off=0,
        causal=causal, block_q=blocks[2], block_k=blocks[3],
        interpret=interpret, dq_split=dq_split,
    )


_flash_bthd.defvjp(_flash_bthd_fwd, _flash_bthd_bwd)


def flash_bthd(
    q2: jax.Array,
    k2: jax.Array,
    v2: jax.Array,
    num_heads: int,
    num_kv_heads: Optional[int] = None,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    dq_split: Optional[bool] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
) -> jax.Array:
    """Flash attention on feature-major (B, T, H*D) operands.

    ``q2`` is (B, T, Hq*D); ``k2``/``v2`` are (B, T, Hkv*D) with Hkv | Hq —
    native grouped-query attention: each grid step loads ONE kv head and
    serves its whole query group, so K/V HBM traffic is Hkv-sized (the old
    path repeated K/V to full heads, materializing the 4x traffic GQA
    exists to avoid). Also the layout RoPE emits (rotation on (B, T, H, D)
    then a free trailing-dim merge). Returns (B, T, Hq*D).
    ``dq_split``: backward dq strategy override — see :func:`flash_fused`.
    """
    if num_kv_heads is None:
        num_kv_heads = num_heads
    b, t, f = q2.shape
    if f % num_heads or k2.shape != (b, t, (f // num_heads) * num_kv_heads):
        raise ValueError(
            f"flash_bthd: q {q2.shape} / k {k2.shape} inconsistent with "
            f"H={num_heads}, Hkv={num_kv_heads}"
        )
    if num_heads % num_kv_heads:
        raise ValueError("flash_bthd: num_kv_heads must divide num_heads")
    if v2.shape != k2.shape:
        raise ValueError("flash_bthd: k and v must share one shape")
    d = f // num_heads
    blocks = resolve_tuned_blocks(
        t, d, num_heads, num_kv_heads, q2.dtype, causal,
        block_q, block_k, bwd_block_q, bwd_block_k,
    )
    if interpret is None:
        interpret = _interpret_default()
    return _flash_bthd(
        q2, k2, v2, num_heads, num_kv_heads, d, causal,
        blocks, interpret, dq_split,
    )


def flash_fused_sharded(
    fused: jax.Array,
    num_heads: int,
    causal: bool = True,
    *,
    mesh,
    batch_axes=("data",),
    head_axis: str = "model",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """:func:`flash_fused` composed with a multi-device mesh.

    The fused (B, T, 3*H*D) operand cannot shard its feature dim over a
    tensor-parallel axis (a contiguous cut would slice across the q|k|v
    segment boundaries), so: with a usable ``head_axis`` the q/k/v segments
    are sliced out and routed through :func:`flash_bthd_sharded` (each
    (B, T, H*D) slice DOES head-align under a contiguous feature cut);
    otherwise the fused zero-copy op runs under shard_map with only the
    batch dim sharded.
    """
    from jax import shard_map as _shard_map
    from jax.sharding import PartitionSpec as P

    from rocket_tpu.ops.flash_attention import shardable_axes

    b, t, f = fused.shape
    if f % (3 * num_heads):
        raise ValueError(
            f"flash_fused_sharded: feature dim {f} is not 3*H*D for "
            f"H={num_heads}"
        )
    d = f // (3 * num_heads)
    baxes, haxis = shardable_axes(mesh, b, num_heads, batch_axes, head_axis)
    if haxis is not None:
        hd = num_heads * d
        return flash_bthd_sharded(
            fused[..., :hd], fused[..., hd:2 * hd], fused[..., 2 * hd:],
            num_heads, causal=causal, mesh=mesh, batch_axes=batch_axes,
            head_axis=head_axis, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )

    fn = functools.partial(
        flash_fused, num_heads=num_heads, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    if baxes is None:
        return fn(fused)
    sharded = _shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(baxes, None, None),),
        out_specs=P(baxes, None, None),
        check_vma=False,
    )
    return sharded(fused)


def flash_bthd_sharded(
    q2: jax.Array,
    k2: jax.Array,
    v2: jax.Array,
    num_heads: int,
    num_kv_heads: Optional[int] = None,
    causal: bool = True,
    *,
    mesh,
    batch_axes=("data",),
    head_axis: str = "model",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """:func:`flash_bthd` composed with a multi-device mesh via shard_map.

    Batch over ``batch_axes``; the FEATURE dim over ``head_axis`` (the
    Megatron-TP activation layout: a contiguous feature cut of (B, T, H*D)
    at H/tp boundaries is exactly a head split, so each shard runs the
    kernel on its local heads). Axes that don't exist or don't divide
    (including Hq or Hkv not divisible by the axis size) are dropped from
    the specs. Zero communication added. See
    ``ops.flash_attention.flash_attention_qkv_sharded`` for the seam
    rationale; this is its native-layout sibling.
    """
    from jax import shard_map as _shard_map
    from jax.sharding import PartitionSpec as P

    from rocket_tpu.ops.flash_attention import shardable_axes

    if num_kv_heads is None:
        num_kv_heads = num_heads
    b = q2.shape[0]
    baxes, haxis = shardable_axes(
        mesh, b, num_heads, batch_axes, head_axis
    )
    if haxis is not None and num_kv_heads % mesh.shape[haxis]:
        haxis = None  # kv heads must split evenly too
    tp = mesh.shape[haxis] if haxis else 1

    def local(q2, k2, v2):
        return flash_bthd(
            q2, k2, v2, num_heads // tp, num_kv_heads // tp, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )

    if baxes is None and haxis is None:
        return local(q2, k2, v2)
    spec = P(baxes, None, haxis)
    sharded = _shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return sharded(q2, k2, v2)
