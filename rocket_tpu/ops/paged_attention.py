"""Paged KV-cache attention — gather/scatter over a shared block pool.

The serving engine (``rocket_tpu.serve``) keeps every sequence's KV cache
in a FIXED pool of HBM blocks instead of a per-call ``(B, T_max)`` dense
cache: ``k_pages``/``v_pages`` are ``(L, num_blocks, block_len, Hkv*D)``
arrays shared by every live request and every layer, and a per-slot
``block_table`` maps a sequence's logical positions onto pool blocks
(vLLM's PagedAttention layout, arXiv 2309.06180). Thousands of concurrent
sequences then share ``num_blocks * block_bytes`` of HBM regardless of how
many are admitted — the pool is allocated once and only the tables change.

ONE layout, addressed in place. A page is stored as the ``(block_len,
Hkv*D)`` tile the kernel streams: every kv head's row side by side on the
lane axis, the page's rows on the sublane axis, so the scatter writes
whole rows, the kernel's block IS the array as stored and the XLA path
splits heads only on the small context it gathered. Every function here
takes the WHOLE pool and a ``layer`` coordinate (a Python int or a traced
scalar): nothing slices a layer out or puts one back, so under donation
the only thing a program does to the pool is the in-place row scatter. (A
``(…, Hkv, D)`` pool made every one of those views a relayout copy of a
whole layer on a TPU, 63 % of the chat cell's device time; PERF.md, PR 28.)

Two device-side implementations share one signature:

* **XLA path** (portable — every backend): :func:`write_kv_pages`
  scatters the chunk's new K/V rows into the pool, then the mapped
  blocks are gathered back to a contiguous ``(S, T, Hkv*D)`` context
  and causally-masked GQA attention runs over it in the feature-major
  layout. The gather materializes a transient
  ``(max_slots, max_blocks_per_seq * block_len, Hkv*D)`` context per
  wave (RKT602's CPU cost model prices it at 4.6x the analytic floor
  for a decode wave: a prediction, not a measurement).
* **pallas paged-decode kernel** (TPU, C=1 decode waves): the same
  scatter, then gather and attend are FUSED per block-table page —
  each grid step streams one ``(block_kv, Hkv*D)`` tile of one mapped
  page (all kv heads side by side on the lane axis) straight into VMEM
  and folds each head's lane slice into a flash-style running softmax,
  so only the slot's ACTIVE pages ever leave HBM and no transient
  context materializes. Inactive table entries point at the reserved
  trash block 0; Mosaic's pipeline skips re-fetching a repeated block
  index, so the dead tail of a short sequence costs at most one trash
  PAGE of fetches (``block_len / block_kv`` tiles, cycled thereafter),
  not ``max_blocks_per_seq`` gathers.

Implementation choice and the ``block_kv`` tile height resolve through
the ``paged_decode`` tune table (``rocket_tpu.tune``) — ``impl`` is a
real structural search axis (the tuner can measure the XLA path beating
the kernel on a shape and pin it). With nothing pinned the choice is a
function of what the call can observe: the kernel for C=1 decode on a
TPU wherever :func:`paged_decode_supported` holds, the XLA path for
prefill chunks, unsupported pool geometries and on the CPU (bitwise
identical to an untuned checkout — asserted in tests).
``ROCKET_TPU_PAGED_DECODE`` (``pallas``/``xla``) force-overrides the
table. A PINNED ``pallas`` (argument, table or environment) that cannot
run raises — it never silently becomes the other path.

Layout notes for TPU: the kernel's block spans the pool's whole
``Hkv*D`` lane axis (so any head count and width is Mosaic-legal) and
``block_len`` must be a multiple of the dtype's sublane tile (8 f32 / 16
bf16).

Inference only (no custom VJP — serving never differentiates).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "write_pages",
    "write_kv_pages",
    "paged_latent_decode",
    "paged_attention",
    "paged_gather",
    "paged_decode_supported",
]

_NEG_INF = -1e30

#: Sublane minimum per itemsize — mirrors ``tune.space.sublane_min``.
_SUBLANE = {4: 8, 2: 16, 1: 32}


def write_pages(pages, block_table, positions, valid, rows, *, layer=0):
    """Scatter one chunk's rows into layer ``layer`` of ONE pool array.

    ``pages`` ``(L, NB, BL, lanes)``; ``block_table`` ``(S, MB)`` int32 block
    ids (0 = the reserved trash block); ``positions`` ``(S,)`` int32 — slot
    ``s``'s chunk occupies global positions ``[positions[s], positions[s] +
    C)``; ``valid`` ``(S,)`` int32 — only the first ``valid[s]`` rows of the
    chunk are real (the rest are padding and land in the trash block);
    ``rows`` ``(S, C, ...)`` with ``lanes`` values a row. Returns the
    updated array: one scatter of whole rows at ``(layer, block, row)``, in
    place where the pool is donated. What a row holds is the layer's own
    business: K or V of every kv head side by side, or a latent
    (``nn.attention.LatentAttention``)."""
    bl = pages.shape[2]
    s, c = rows.shape[0], rows.shape[1]
    pos = positions[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # (S, C)
    slot = jnp.clip(pos // bl, 0, block_table.shape[1] - 1)
    block = jnp.take_along_axis(block_table, slot, axis=1)              # (S, C)
    ok = jnp.arange(c, dtype=jnp.int32)[None, :] < valid[:, None]
    # Masked rows collapse onto row 0 of the trash block (block 0 is
    # never allocated, so collisions there are harmless).
    block = jnp.where(ok, block, 0).reshape(-1)
    row = jnp.where(ok, pos % bl, 0).reshape(-1)
    return pages.at[layer, block, row].set(
        rows.astype(pages.dtype).reshape(s * c, -1)
    )


def write_kv_pages(k_pages, v_pages, block_table, positions, valid,
                   k_new, v_new, *, layer=0):
    """:func:`write_pages` for a K and a V array: ``k_new``/``v_new``
    ``(S, C, Hkv, D)`` into ``k_pages``/``v_pages`` ``(L, NB, BL, Hkv*D)``.
    Returns the updated ``(k_pages, v_pages)``."""
    k_pages = write_pages(k_pages, block_table, positions, valid, k_new,
                          layer=layer)
    v_pages = write_pages(v_pages, block_table, positions, valid, v_new,
                          layer=layer)
    return k_pages, v_pages


def paged_gather(pages, block_table, *, layer=0):
    """Gather a slot batch's mapped blocks of layer ``layer`` to a
    contiguous context: ``(L, NB, BL, Hkv*D)`` pages + ``(S, MB)`` table
    -> ``(S, MB*BL, Hkv*D)``. Row ``t`` of the result is the slot's global
    position ``t`` (table slot ``j`` covers positions ``[j*BL, (j+1)*BL)``);
    unmapped entries gather the trash block and must be masked off by
    position."""
    s, mb = block_table.shape
    ctx = pages[layer, block_table]                     # (S, MB, BL, Hkv*D)
    return ctx.reshape(s, mb * pages.shape[2], pages.shape[3])


def paged_decode_supported(block_len: int, head_dim: int, itemsize: int = 4) -> bool:
    """Shape gate for the fused kernel: a page streams as
    ``(block_kv, Hkv*D)`` VMEM tiles (the whole lane axis, so any head
    count is Mosaic-legal) — block_len must be a multiple of the dtype's
    sublane minimum so such a tile divides the page, and D a multiple
    of 8 (the per-head lane slice). ``tests/test_tpu_compile.py``
    compiles the kernel for a v5e chip across this gate's edge."""
    sub = _SUBLANE.get(itemsize, 8)
    return block_len % sub == 0 and head_dim % 8 == 0 and head_dim >= 8


def _on_cpu() -> bool:
    """Whether this process's default backend is the CPU, where the
    kernel can only run interpreted."""
    return jax.devices()[0].platform == "cpu"


def _default_block_kv(block_len: int, itemsize: int = 4) -> int:
    """The hand-picked tile height: the largest power-of-two row count
    (<= 128) that divides the page — one page per grid step when the
    page itself is small."""
    sub = _SUBLANE.get(itemsize, 8)
    for rows in (128, 64, 32, 16, 8):
        if rows % sub == 0 and block_len % rows == 0:
            return rows
    return block_len


def _decode_kernel(layer_ref, table_ref, pos_ref, q_ref, k_ref, *refs,
                   block_kv, sub, mb, scale, h_kv, g, d, d_v, shared):
    """One (slot, kv-tile) grid step of the fused paged decode.

    Streams a ``(block_kv, Hkv*D)`` tile of the mapped page — every kv
    head's rows side by side on the lane axis — and folds each head's
    ``(block_kv, D)`` lane slice into the flash-style running softmax
    held in f32 scratch (one row group of ``g`` query heads per kv
    head); the normalized output is written once, after the last tile.
    The new K/V row was scattered into the pool BEFORE the kernel, so
    key positions ``<= pos`` (the query's own row included) are all
    read from the pool — exact prefix semantics, one code path. All ops
    stay 2D per head (Mosaic rejects 3D shape casts).

    ``shared``: there is no V array — a row's first ``d_v`` lanes ARE its
    value (a latent pool: one ``d``-lane row per token, read once for
    every query head), so the tile that gave the scores gives the values
    too and the pool is streamed once."""
    del layer_ref, table_ref  # consumed by the index maps
    if shared:
        v_ref, (o_ref, m_ref, l_ref, acc_ref) = k_ref, refs
    else:
        v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[i]
    n_ctx = pos + 1                       # visible keys: positions [0, pos]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = j * block_kv                   # global position of tile row 0

    @pl.when(base < n_ctx)
    def _tile():
        for h in range(h_kv):
            rows = slice(h * g, (h + 1) * g)
            q = q_ref[0, rows, :]                      # (g, D)
            k = k_ref[0, 0, :, h * d:(h + 1) * d]      # (block_kv, D)
            v = v_ref[0, 0, :, h * d:h * d + d_v]
            s_ij = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                  # (g, block_kv) f32
            idx = base + jax.lax.broadcasted_iota(jnp.int32, s_ij.shape, 1)
            s_ij = jnp.where(idx < n_ctx, s_ij, _NEG_INF)

            m_prev = m_ref[rows, 0:1]                  # (g, 1)
            l_prev = l_ref[rows, 0:1]
            m_new = jnp.maximum(
                m_prev, jnp.max(s_ij, axis=1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_new)            # (g, 1)
            p = jnp.exp(s_ij - m_new)                  # (g, block_kv)
            m_ref[rows, :] = jnp.broadcast_to(m_new, (g, m_ref.shape[1]))
            l_ref[rows, :] = jnp.broadcast_to(
                l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                (g, l_ref.shape[1]),
            )
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(j == sub * mb - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pages, v_pages, block_table, positions,
                         *, layer=0, block_kv: int, interpret: bool,
                         scale: Optional[float] = None,
                         d_v: Optional[int] = None,
                         name: str = "paged_decode"):
    """The fused gather+attend for one decode wave: ``q`` (S, Hq, D),
    pool/table/positions/layer as in :func:`paged_attention` (new rows
    already scattered). Returns ``out`` (S, Hq, d_v).

    ``v_pages=None`` is the latent pool (:func:`paged_latent_decode`): ONE
    kv "head" of ``D`` = the array's whole lane axis for all ``Hq`` query
    heads, values = the first ``d_v`` lanes of the same rows, ``scale``
    given by the caller. With a V array ``d_v`` is ``D`` and ``scale``
    ``1/sqrt(D)`` — one kernel body for both.

    Mosaic wants the last two dims of every block divisible by the
    (sublane, 128) tile or equal to the array's own: a page tile is
    ``(block_kv, Hkv*D)`` of the pool AS STORED, with the whole lane
    axis, and q/out blocks carry the whole ``(Hq, D)`` head axis; the
    kernel selects each kv head by a static lane slice. The layer rides
    in as a prefetched scalar beside the table, so the index map
    addresses ``(layer, page, tile)`` of the whole pool and one kernel
    serves a Python-loop layer and a scanned one. A per-head block
    ``(1, block_kv, 1, D)`` / ``(1, g, D)`` is refused by the TPU
    lowering whenever Hkv > 1 or g < 8."""
    s, hq, d = q.shape
    _, _, bl, hd = k_pages.shape
    shared = v_pages is None
    h_kv = hd // d
    mb = block_table.shape[1]
    g = hq // h_kv
    sub = bl // block_kv
    d_v = d if d_v is None else int(d_v)
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)

    def q_map(i, j, layer_ref, table_ref, pos_ref):
        del j, layer_ref, table_ref, pos_ref
        return (i, 0, 0)

    def page_map(i, j, layer_ref, table_ref, pos_ref):
        del pos_ref
        # Dims 0 and 1 are blocked at one layer and one whole page, so
        # the layer and the page id ARE their block indices; dim 2 is
        # tiled at block_kv rows, so the within-page tile is its index.
        return (layer_ref[0], table_ref[i * mb + j // sub], j % sub, 0)

    page_spec = pl.BlockSpec((1, 1, block_kv, hd), page_map)
    pools = (k_pages,) if shared else (k_pages, v_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, mb * sub),
        in_specs=[pl.BlockSpec((1, hq, d), q_map)] + [page_spec] * len(pools),
        out_specs=pl.BlockSpec((1, hq, d_v), q_map),
        scratch_shapes=[
            pltpu.VMEM((hq, 128), jnp.float32),   # running max (lane-bcast)
            pltpu.VMEM((hq, 128), jnp.float32),   # running denom
            pltpu.VMEM((hq, d_v), jnp.float32),   # unnormalized accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, block_kv=block_kv, sub=sub, mb=mb, scale=scale,
            h_kv=h_kv, g=g, d=d, d_v=d_v, shared=shared,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hq, d_v), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_table.reshape(-1).astype(jnp.int32),
      jnp.asarray(positions, jnp.int32), q, *pools)


def paged_latent_decode(q, pages, block_table, positions, *, layer=0,
                        d_v: int, scale: float,
                        block_kv: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """One decode wave of latent attention (MLA, absorbed form) against
    ONE pool array: ``q`` ``(S, Hq, Dk)`` — every query head already
    carried into the latent's own space, its rotary part behind it —
    ``pages`` ``(L, NB, BL, Dk)`` holding one ``Dk``-lane row per token
    (the new rows already scattered, :func:`write_pages`). Scores are
    ``q . row * scale`` over all ``Dk`` lanes, values the first ``d_v``
    lanes of the SAME rows. Returns ``(S, Hq, d_v)``.

    Where the fused kernel can run (a TPU, or ``interpret=True``) it is
    :func:`_paged_decode_pallas` with no V array, under the Pallas name
    ``mla_decode``: each live page is streamed once for all ``Hq`` heads.
    Elsewhere the slot's pages are gathered and attended in XLA."""
    bl = int(pages.shape[2])
    itemsize = jnp.dtype(pages.dtype).itemsize
    on_cpu = _on_cpu()
    if paged_decode_supported(bl, q.shape[-1], itemsize) and (
        not on_cpu or interpret
    ):
        return _paged_decode_pallas(
            q, pages, None, block_table, positions, layer=layer,
            block_kv=int(block_kv or _default_block_kv(bl, itemsize)),
            interpret=on_cpu or bool(interpret), scale=scale, d_v=d_v,
            name="mla_decode",
        )
    ctx = paged_gather(pages, block_table, layer=layer)      # (S, T, Dk)
    logits = jnp.einsum(
        "shd,std->sht", q, ctx, preferred_element_type=jnp.float32
    ) * scale
    seen = jnp.arange(ctx.shape[1], dtype=jnp.int32)[None, :] \
        <= positions[:, None]                                # (S, T)
    logits = jnp.where(seen[:, None, :], logits, -jnp.inf)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(
        "sht,stv->shv", weights.astype(ctx.dtype), ctx[..., :d_v]
    )


def _attend_xla(q, k_pages, v_pages, block_table, positions, layer):
    """The portable gather+attend: contiguous per-slot context, einsum
    attention with f32 softmax statistics. ``q`` (S, C, Hq, D); returns
    ``out`` (S, C, Hq*D). Heads are split on the gathered context, never
    on the pool. Padded query rows produce well-defined garbage the
    callers ignore."""
    s, c, hq, d = q.shape
    h_kv = k_pages.shape[3] // d
    g = hq // h_kv
    k_ctx = paged_gather(k_pages, block_table, layer=layer) \
        .reshape(s, -1, h_kv, d)                        # (S, T, Hkv, D)
    v_ctx = paged_gather(v_pages, block_table, layer=layer) \
        .reshape(s, -1, h_kv, d)
    t = k_ctx.shape[1]
    scale = 1.0 / math.sqrt(d)
    q5 = q.reshape(s, c, h_kv, g, d)
    logits = jnp.einsum(
        "sckgd,stkd->skgct", q5, k_ctx, preferred_element_type=jnp.float32
    ) * scale                                           # (S, Hkv, G, C, T)
    # Query at global position positions[s]+i sees key positions <= it.
    key_pos = jnp.arange(t, dtype=jnp.int32)
    q_pos = positions[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    mask = key_pos[None, None, :] <= q_pos[:, :, None]  # (S, C, T)
    logits = jnp.where(mask[:, None, None, :, :], logits, -jnp.inf)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(
        "skgct,stkd->sckgd", weights.astype(v_ctx.dtype), v_ctx
    ).reshape(s, c, hq * d)


def paged_attention(q, k_new, v_new, k_pages, v_pages, block_table,
                    positions, valid, *, layer=0,
                    impl: Optional[str] = None,
                    block_kv: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """One chunk of causal GQA attention against the paged pool.

    ``q`` ``(S, C, Hq, D)``; ``k_new``/``v_new`` ``(S, C, Hkv, D)`` (RoPE
    already applied); pool ``(L, NB, BL, Hkv*D)``, table/positions/valid
    and ``layer`` (int or traced scalar: which layer of the pool this
    call reads and writes) as in :func:`write_kv_pages`. The chunk's rows
    are written into the pool FIRST, then each query row ``i`` attends
    over key positions
    ``<= positions[s] + i`` — exact prefix semantics at any chunk size
    (C=1 decode and C=chunk prefill share this one signature, which is
    what makes chunked prefill bit-match one-shot prefill).

    ``impl``/``block_kv`` pin the implementation explicitly (the tuner's
    candidate runs); left ``None`` they resolve through the
    ``paged_decode`` tune table, defaulting to the fused pallas kernel
    for C=1 decode on TPU where :func:`paged_decode_supported` holds and
    the XLA path everywhere else. A pinned ``"pallas"`` that cannot run
    (C > 1, unsupported pool geometry) raises ``ValueError``; on a CPU
    host it runs interpreted. ``interpret=True`` runs the kernel
    interpreted on any backend (CPU parity tests).

    Returns ``(out (S, C, Hq*D), k_pages', v_pages')``. Padded query rows
    (``i >= valid[s]``) produce well-defined garbage (position 0 is always
    visible, so the softmax never sees an all-masked row) — callers ignore
    them.
    """
    s, c, hq, d = q.shape
    bl = int(k_pages.shape[2])
    h_kv = int(k_new.shape[2])
    mb = int(block_table.shape[1])
    if hq % h_kv:
        raise ValueError(f"paged_attention: Hq {hq} not a multiple of Hkv {h_kv}")
    if k_pages.ndim != 4 or k_pages.shape[3] != h_kv * d:
        raise ValueError(
            f"paged_attention: pool {k_pages.shape} is not "
            f"(L, NB, BL, Hkv*D) with Hkv*D = {h_kv} * {d}"
        )
    itemsize = jnp.dtype(k_pages.dtype).itemsize
    on_cpu = _on_cpu()
    kernel_can_run = c == 1 and paged_decode_supported(bl, d, itemsize)
    if (impl is None or block_kv is None) and c == 1:
        # Tunable surface (tune kernel "paged_decode"): impl is a REAL
        # structural axis (fused pallas kernel vs XLA gather) and
        # block_kv the streamed tile height; the lookup also records
        # serving-path config provenance. Prefill chunks (C > 1) skip it
        # entirely — the axes cannot affect them (always the XLA path),
        # so they must not pollute the provenance log with inert rows.
        from rocket_tpu.tune import get_config

        config = get_config(
            "paged_decode",
            shape={"s": s, "mb": mb, "bl": bl, "hkv": h_kv, "hq": hq,
                   "d": d},
            dtype=k_pages.dtype,
        ) or {}
        if impl is None:
            impl = os.environ.get("ROCKET_TPU_PAGED_DECODE") \
                or config.get("impl")
        if block_kv is None:
            block_kv = config.get("block_kv")
    if impl is None:
        # Nobody pinned a path: the choice is a function of what the call
        # can observe — the kernel wherever it can run compiled (or was
        # asked to run interpreted), the XLA gather everywhere else.
        impl = "pallas" if kernel_can_run and (not on_cpu or interpret) \
            else "xla"
    block_kv = block_kv or _default_block_kv(bl, itemsize)
    if impl not in ("pallas", "xla"):
        raise ValueError(
            f"paged_attention: unknown impl {impl!r} — the table is "
            "ahead of the implementation (expected 'pallas' or 'xla')"
        )
    if impl == "pallas" and not kernel_can_run:
        # A pinned kernel that cannot run is an error, never a silent
        # switch to the other path.
        raise ValueError(
            f"paged_attention: impl='pallas' cannot run here (C={c}, "
            f"block_len={bl}, head_dim={d}, itemsize={itemsize}) — the "
            "fused kernel is C=1 decode only and needs "
            "paged_decode_supported(block_len, head_dim, itemsize); "
            "pin impl='xla' for this shape"
        )

    k_pages, v_pages = write_kv_pages(
        k_pages, v_pages, block_table, positions, valid, k_new, v_new,
        layer=layer,
    )

    if impl == "pallas":
        if block_kv % _SUBLANE.get(itemsize, 8) or bl % block_kv:
            raise ValueError(
                f"paged_attention: block_kv={block_kv} must be a "
                f"multiple of the sublane tile dividing block_len={bl}"
            )
        out = _paged_decode_pallas(
            q[:, 0], k_pages, v_pages, block_table, positions, layer=layer,
            block_kv=int(block_kv), interpret=on_cpu or bool(interpret),
        ).reshape(s, 1, hq * d)
        return out, k_pages, v_pages
    out = _attend_xla(q, k_pages, v_pages, block_table, positions, layer)
    return out, k_pages, v_pages
