"""Flash attention — pallas TPU kernel (fwd + fused bwd, causal or full).

Blockwise online-softmax attention that never materializes the (T, T) score
matrix: per query block, KV blocks stream through VMEM while running max /
normalizer / accumulator stats are carried in f32 scratch (the flash
attention recurrence).

The reference framework has no attention code at all (SURVEY §0 — it is
model-agnostic); attention enters through the north-star configs
(BASELINE.json configs[2,4]). This kernel is the TPU-native hot-op
counterpart of what torch users get from ``F.scaled_dot_product_attention``.

Performance notes (what the profiler said, and what this design does):

* q, k and v travel as ONE stacked (3, B, H, T, D) array (three block specs
  index into the same operand). Pallas custom calls pin their operands to
  the default layout, so every separate operand costs a physical
  layout-conversion copy per layer — the stacked form needs exactly one
  bf16 copy in and one out, where three separate operands cost six (and
  XLA was materializing two of them in f32);
* the backward is ONE kernel pass: s2 and the softmax reconstruction are
  computed once and shared by the dv / dk / dq products (the classic
  two-kernel split recomputes them twice). dk/dv accumulate in f32 scratch
  across the query sweep; dq is written as per-kv-block partials (input
  dtype) and summed by one cheap XLA add outside. The partial buffer is
  O(nk) times dq — fine at trained context lengths (nk = T/512); very long
  single-device sequences should shard T instead (parallel/ring_attention);
* at GPT-2's D=64, one elementwise pass over a (bq, bk) score block costs
  as much VPU time as the whole QK^T matmul costs MXU time, so VPU passes
  are minimized: causal masking runs only on diagonal blocks (fully masked
  blocks are skipped, interior blocks take a mask-free path), and the
  softmax works in base-2 (``exp2``) so the scale folds into one fma;
* all matmuls declare ``preferred_element_type=jnp.float32``; softmax
  statistics and accumulators stay f32 while operands stay bf16;
* TPU grids iterate sequentially with the last axis innermost, so f32
  scratch carries across the inner sweep and outputs flush on the last
  visit (see /opt/skills/guides/pallas_guide.md).

On non-TPU backends (the virtual-CPU test mesh) the kernels run in pallas
interpret mode, so the same code path is unit-testable without a chip.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "DEFAULT_BLOCK",
    "flash_attention",
    "flash_attention_qkv",
    "flash_attention_qkv_sharded",
    "in_manual_axes",
    "pick_block",
    "resolve_tuned_blocks",
    "shardable_axes",
]

_NEG_INF = -1e30
_LOG2E = math.log2(math.e)


def _interpret_default() -> bool:
    return jax.devices()[0].platform == "cpu"


def pick_block(t: int, preferred: int = 512) -> Optional[int]:
    """Largest supported block size (<= preferred) that divides ``t``.

    Shared with ``nn.attention.resolve_impl`` so the "can flash handle this
    sequence length" predicate lives in exactly one place.
    """
    for block in (preferred, 256, 128):
        if block <= preferred and t % block == 0 and block <= t:
            return block
    return None


def _causal_mask(s, transposed: bool = False):
    """Causal mask for an aligned diagonal block (broadcasts over the
    leading head-batch dim).

    ``s`` is (hb, block_q, block_k): keep q_idx (rows) >= k_idx (cols).
    With ``transposed`` it is (hb, block_k, block_q): keep rows <= cols."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 2)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    keep = rows <= cols if transposed else rows >= cols
    return jnp.where(keep, s, _NEG_INF)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                scale2, causal):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    # Diagonal alignment assumes block_q == block_k (enforced by caller for
    # causal). Interior blocks run mask-free; blocks above the diagonal are
    # skipped entirely.
    def tile(masked: bool):
        q = q_ref[0, 0]  # (hb, bq, d)
        k = k_ref[0, 0]
        # s2 = (q . k) * scale * log2(e): base-2 domain, scale folded in.
        s2 = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale2  # (hb, block_q, block_k)
        if masked:
            s2 = _causal_mask(s2)
        m_prev = m_s[:]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1, keepdims=True))
        p = jnp.exp2(s2 - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_s[:] = l_s[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc[:] = acc[:] * alpha + pv
        m_s[:] = m_new

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
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)
        # lse kept in the base-2 domain: lse2 = m2 + log2(l). Stored
        # (hb, 1, bq) — q along LANES — so the HBM array is (B, H, 1, T):
        # a (T, 1) trailing layout would be tile-padded 128x (~48 MB/layer
        # of padding at GPT-2 shapes), (1, T) only pads sublanes 8x, and
        # the transposed backward kernel broadcasts it for free.
        lse_ref[0] = jnp.swapaxes(m_s[:] + jnp.log2(safe_l), 1, 2)


def _head_block(h: int) -> int:
    """Heads processed per grid step — halves the per-step grid overhead
    (the dominant cost at D=64 block sizes) when the head count allows."""
    return 2 if h % 2 == 0 else 1


def _check_causal_blocks(block_q: int, block_k: int, causal: bool,
                         where: str) -> None:
    """Fail FAST on the diagonal-alignment constraint: causal masking
    runs only on diagonal blocks, which is correct ONLY for aligned
    square blocks (``block_q == block_k``). An unaligned pair would
    silently mis-mask scores — an illegal tuner candidate must raise
    here, at the kernel entry, not return wrong attention output."""
    if causal and block_q != block_k:
        raise ValueError(
            f"{where}: causal diagonal-block masking requires "
            f"block_q == block_k (got block_q={block_q}, "
            f"block_k={block_k}). Use equal blocks, or causal=False for "
            "asymmetric blocking."
        )


def _fwd(qkv, *, causal, block_q, block_k, interpret):
    _check_causal_blocks(block_q, block_k, causal, "flash_attention._fwd")
    _, b, h, t, d = qkv.shape
    scale2 = _LOG2E / math.sqrt(d)
    nq, nk = t // block_q, t // block_k
    hb = _head_block(h)

    def qs(i):
        return pl.BlockSpec(
            (1, 1, hb, block_q, d), lambda b, h, iq, ik, i=i: (i, b, h, iq, 0)
        )

    def ks(i):
        return pl.BlockSpec(
            (1, 1, hb, block_k, d), lambda b, h, iq, ik, i=i: (i, b, h, ik, 0)
        )

    kernel = functools.partial(_fwd_kernel, scale2=scale2, causal=causal)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h // hb, nq, nk),
        in_specs=[qs(0), ks(1), ks(2)],
        out_specs=[
            pl.BlockSpec((1, hb, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, hb, 1, block_q), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), qkv.dtype),
            jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, block_q, d), jnp.float32),
            pltpu.VMEM((hb, block_q, 1), jnp.float32),
            pltpu.VMEM((hb, block_q, 1), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qkv, qkv, qkv)
    return out, lse


# --------------------------------------------------------------------------
# backward — one fused pass
# --------------------------------------------------------------------------


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dqp_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, scale2, causal):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile(masked: bool):
        # Scores are computed TRANSPOSED — (hb, bk, bq), q along lanes — so
        # the per-q stats lse/delta, stored (hb, 1, bq), broadcast across
        # the sublane (k) dim natively; the (bq, bk) orientation would need
        # the stats in a 128x-tile-padded (T, 1) HBM layout instead.
        q = q_ref[0, 0]  # (hb, bq, d)
        k = k_ref[0, 0]
        s2t = jax.lax.dot_general(
            k, q, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale2  # (hb, bk, bq)
        if masked:
            s2t = _causal_mask(s2t, transposed=True)
        pt = jnp.exp2(s2t - lse_ref[0])  # lse (hb, 1, bq)
        do = do_ref[0]  # (hb, bq, d)
        dv_acc[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (hb, bk, d)
        dpt = jax.lax.dot_general(
            v_ref[0, 0], do, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (hb, bk, bq)
        ds_t = pt * (dpt - delta_ref[0]) * scale
        ds_c = ds_t.astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds_c, q, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (hb, bk, d)
        # This kv block's contribution to dq — summed over blocks outside.
        dqp_ref[0, 0] = jax.lax.dot_general(
            ds_c, k, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).astype(dqp_ref.dtype)  # (hb, bq, d)

    if causal:
        @pl.when(ik < iq)
        def _interior():
            tile(masked=False)

        @pl.when(ik == iq)
        def _diagonal():
            tile(masked=True)

        @pl.when(ik > iq)
        def _skipped():
            dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])
    else:
        tile(masked=False)

    @pl.when(iq == nq - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(causal, blocks, interpret, res, dout):
    block_q, block_k = blocks[2], blocks[3]
    _check_causal_blocks(block_q, block_k, causal, "flash_attention._bwd")
    qkv, out, lse = res
    _, b, h, t, d = qkv.shape
    scale = 1.0 / math.sqrt(d)
    scale2 = _LOG2E / math.sqrt(d)
    nq, nk = t // block_q, t // block_k

    # delta = rowsum(dout * out), (B, H, 1, T) row layout to match lse — a
    # (T, 1) trailing layout would be tile-padded 128x in HBM.
    delta = jnp.sum(
        out.astype(jnp.float32) * dout.astype(jnp.float32), axis=-1,
    )[:, :, None, :]  # (B, H, 1, T)

    hb = _head_block(h)

    def qs(i):
        return pl.BlockSpec(
            (1, 1, hb, block_q, d), lambda b, h, ik, iq, i=i: (i, b, h, iq, 0)
        )

    def ks(i):
        return pl.BlockSpec(
            (1, 1, hb, block_k, d), lambda b, h, ik, iq, i=i: (i, b, h, ik, 0)
        )

    dq_part, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, scale2=scale2, causal=causal),
        grid=(b, h // hb, nk, nq),
        in_specs=[
            qs(0), ks(1), ks(2),
            pl.BlockSpec((1, hb, block_q, d), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, hb, 1, block_q), lambda b, h, ik, iq: (b, h, 0, iq)),
            pl.BlockSpec((1, hb, 1, block_q), lambda b, h, ik, iq: (b, h, 0, iq)),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, hb, block_q, d), lambda b, h, ik, iq: (ik, b, h, iq, 0)
            ),
            pl.BlockSpec((1, hb, block_k, d), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, hb, block_k, d), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nk, b, h, t, d), qkv.dtype),
            jax.ShapeDtypeStruct((b, h, t, d), qkv.dtype),
            jax.ShapeDtypeStruct((b, h, t, d), qkv.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, block_k, d), jnp.float32),
            pltpu.VMEM((hb, block_k, d), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qkv, qkv, qkv, dout, lse, delta)

    dq = dq_part[0] if nk == 1 else jnp.sum(
        dq_part.astype(jnp.float32), axis=0
    ).astype(qkv.dtype)
    return (jnp.stack([dq, dk, dv]),)


# --------------------------------------------------------------------------
# public ops
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flash(qkv, causal, blocks, interpret):
    out, _ = _fwd(
        qkv, causal=causal, block_q=blocks[0], block_k=blocks[1],
        interpret=interpret,
    )
    return out


def _flash_fwd(qkv, causal, blocks, interpret):
    out, lse = _fwd(
        qkv, causal=causal, block_q=blocks[0], block_k=blocks[1],
        interpret=interpret,
    )
    return out, (qkv, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def _resolve_blocks(t: int, causal: bool, block_q: int, block_k: int):
    bq = pick_block(t, min(block_q, t))
    bk = pick_block(t, min(block_k, t))
    if bq is None or bk is None:
        raise ValueError(
            f"flash_attention: seq len {t} must be a multiple of a "
            "supported block size (128); use the XLA path for ragged shapes."
        )
    if causal:
        # Diagonal-block masking needs aligned square blocks (the kernel
        # entry raises otherwise — _check_causal_blocks).
        bq = bk = min(bq, bk)
    return bq, bk


#: The hand-picked block size the tuned-table lookup falls back to —
#: the measured best at bench shapes (docs/performance.md: 512x512 best,
#: 256-variants 10-18% worse).
DEFAULT_BLOCK = 512


def resolve_tuned_blocks(
    t: int, d: int, h: int, h_kv: int, dtype, causal: bool,
    block_q, block_k, bwd_block_q, bwd_block_k,
) -> tuple:
    """(block_q, block_k, bwd_block_q, bwd_block_k) with ``None`` args
    resolved through the tuned-config table (`rocket_tpu.tune`,
    kernels ``flash_fwd``/``flash_bwd``) and today's defaults as the
    fallback: fwd ``DEFAULT_BLOCK``; bwd the RESOLVED fwd blocks (the
    pre-tuner behavior — one block pair threaded through both passes).
    Explicit arguments always win (callers pin blocks in tests and
    A/Bs). All four are then clamped/validated by `_resolve_blocks`."""
    shape = {"t": t, "d": d, "h": h, "h_kv": h_kv, "causal": causal}
    fwd_pinned = block_q is not None and block_k is not None
    if not fwd_pinned:
        from rocket_tpu.tune import get_config

        config = get_config("flash_fwd", shape=shape, dtype=dtype) or {}
        if block_q is None:
            block_q = config.get("block_q", DEFAULT_BLOCK)
        if block_k is None:
            block_k = config.get("block_k", DEFAULT_BLOCK)
    bq, bk = _resolve_blocks(t, causal, block_q, block_k)
    if bwd_block_q is None or bwd_block_k is None:
        # A caller that pinned the forward blocks gets the pre-tuner
        # behavior for an unpinned backward — the SAME blocks, no table
        # consultation: pinned A/Bs and repro tests must run exactly the
        # blocks they name in both passes.
        if fwd_pinned:
            config = {}
        else:
            from rocket_tpu.tune import get_config

            config = get_config("flash_bwd", shape=shape, dtype=dtype) or {}
        if bwd_block_q is None:
            bwd_block_q = config.get("block_q", bq)
        if bwd_block_k is None:
            bwd_block_k = config.get("block_k", bk)
    bbq, bbk = _resolve_blocks(t, causal, bwd_block_q, bwd_block_k)
    return bq, bk, bbq, bbk


def flash_attention_qkv(
    qkv: jax.Array,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
) -> jax.Array:
    """Flash attention on a stacked (3, B, H, T, D) q/k/v array.

    The stacked form is the fast path: pallas pins operand layouts, so one
    stacked operand costs one layout copy where three separate ones cost
    six. Returns (B, H, T, D). Differentiable (custom VJP, fused one-pass
    backward).

    Block sizes default to the tuned-config table for this device kind /
    shape bucket / dtype (``rocket_tpu.tune``), falling back to the
    hand-picked 512s when no entry matches; the backward pass may run
    its own tuned blocks (``flash_bwd`` table) independent of the
    forward's. Explicit arguments override the table.
    """
    if qkv.ndim != 5 or qkv.shape[0] != 3:
        raise ValueError(
            f"flash_attention_qkv: expected stacked (3, B, H, T, D), got "
            f"{qkv.shape}; for separate q/k/v use flash_attention()."
        )
    _, _, h, t, d = qkv.shape
    blocks = resolve_tuned_blocks(
        t, d, h, h, qkv.dtype, causal,
        block_q, block_k, bwd_block_q, bwd_block_k,
    )
    if interpret is None:
        interpret = _interpret_default()
    return _flash(qkv, causal, blocks, interpret)


def in_manual_axes(axis_names) -> bool:
    """True when tracing inside a ``shard_map`` that binds any of
    ``axis_names`` (e.g. the pipeline-parallel stage body). There the
    operands are already per-shard local arrays — the kernel must be called
    directly; nesting another shard_map over the same mesh is an error."""
    for name in axis_names:
        try:
            jax.lax.axis_index(name)  # dead op if bound; DCE'd
            return True
        except NameError:
            continue
    return False


def shardable_axes(mesh, b: int, h: int, batch_axes=("data",),
                   head_axis: str = "model"):
    """(batch_axes_tuple | None, head_axis | None) usable by the seam:
    axes that exist in ``mesh`` with size > 1 and divide the corresponding
    dim. Shared by the ``resolve_impl`` "auto" gate (which must NOT pick
    flash when nothing is shardable — a replicated pallas call would
    all-gather the batch) and the wrapper itself."""
    baxes = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    if not baxes or b % bsize:
        baxes = None
    haxis = head_axis if mesh.shape.get(head_axis, 1) > 1 else None
    if haxis is not None and h % mesh.shape[haxis]:
        haxis = None
    return baxes, haxis


def flash_attention_qkv_sharded(
    qkv: jax.Array,
    causal: bool = True,
    *,
    mesh,
    batch_axes=("data",),
    head_axis: str = "model",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention composed with a multi-device mesh via ``shard_map``.

    Batch and head dims are embarrassingly parallel for attention (each
    (b, h) pair is an independent softmax), so the kernel runs per-shard
    with the batch dim split over ``batch_axes`` (data parallel / FSDP) and
    the head dim over ``head_axis`` (Megatron tensor parallel, where the
    QKV projection already produced head-sharded activations) — zero
    communication is added; GSPMD reshards operands only if they arrived in
    a different layout. The sequence axis stays shard-local: sequence
    parallelism is ring attention's job (``parallel/ring_attention.py``).

    Mesh axes that don't exist, are trivial (size 1), or don't divide the
    corresponding dim are simply dropped from the specs (that dim is then
    replicated over them). The reference composes kernels with DDP for free
    through torch's prepared module (``/root/reference/rocket/core/
    module.py:47``); this seam is the TPU-native equivalent for a pallas
    custom call, which GSPMD would otherwise fully replicate.
    """
    from jax import shard_map as _shard_map
    from jax.sharding import PartitionSpec as P

    _, b, h, t, d = qkv.shape
    baxes, haxis = shardable_axes(mesh, b, h, batch_axes, head_axis)

    fn = functools.partial(
        flash_attention_qkv,
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    if baxes is None and haxis is None:
        return fn(qkv)  # nothing shardable — plain (replicated) call
    sharded = _shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(None, baxes, haxis, None, None),),
        out_specs=P(baxes, haxis, None, None),
        # The kernel is elementwise-independent across (b, h): outputs vary
        # exactly like inputs; vma checking chokes on custom_vjp + pallas.
        check_vma=False,
    )
    return sharded(qkv)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Blockwise (flash) attention for (B, H, T, D) operands.

    Differentiable (custom VJP with a fused one-pass recomputation
    backward). ``T`` must be a multiple of a supported block size (the
    caller falls back to the XLA path otherwise — see ``nn/attention.py``);
    causal requires t_q == t_kv. Softmax statistics and all accumulators
    are float32 regardless of input dtype.
    """
    if causal and q.shape[2] != k.shape[2]:
        raise ValueError("flash_attention: causal requires t_q == t_kv.")
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(
            "flash_attention: q, k, v must share one shape (cross-attention "
            "with t_q != t_kv goes through the XLA path)."
        )
    return flash_attention_qkv(
        jnp.stack([q, k, v]), causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
