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
  scatter, then gather and attend are FUSED over the slot's LIVE pages.
  The grid is the slots; the pool stays in HBM and each slot that RUNS
  this wave (``valid[s] > 0``) loops over ``cdiv(positions[s] + 1,
  block_kv)`` tiles of its context, copying each tile's pages — every
  kv head side by side on the lane axis — from where the block table
  says they lie into one of two VMEM buffers while the other is folded,
  head by lane slice, into a flash-style running softmax. So a call
  costs a small constant per slot plus what the running slots' contexts
  cost: a free slot, a slot still prefilling and the unmapped tail of a
  table cost no copy and no loop step, and no transient context
  materializes. (The earlier body walked a static grid of ``max_slots x
  max_blocks_per_seq`` steps and skipped the dead ones' work, which still
  cost 0.12 us a step: 99 % of a call at the chat cell's load; PERF.md,
  PR 30.)

A sliding-window layer keeps no pages: :func:`window_attention` reads and
writes a RING of ``window`` rows a slot (position ``p`` at row ``p mod
window``), its decode wave through the same kernel body, named
``window_decode``, over the ring as one page a slot.

Implementation choice and the ``block_kv`` tile height resolve through
the ``paged_decode`` tune table (``rocket_tpu.tune``) — ``impl`` is a
real structural search axis (the tuner can measure the XLA path beating
the kernel on a shape and pin it). With nothing pinned the choice is a
function of what the call can observe: on a TPU the kernel for C=1
decode where :func:`paged_decode_supported` holds and ``kv_prefill`` for
a chunk against a long table; the XLA path for other chunks, other pool
geometries and on the CPU (bitwise an untuned checkout's); the tile height
from the page length and the row's width (:func:`_default_block_kv`).
``ROCKET_TPU_PAGED_DECODE`` (``pallas``/``xla``) force-overrides the
table. A PINNED ``pallas`` (argument, table or environment) that cannot
run raises — it never silently becomes the other path.

Layout notes for TPU: a copy moves whole rows of the pool as stored, so
any head count and width whose ``Hkv*D`` is a multiple of 128 lanes is
Mosaic-legal (an HBM array is sliced at its tiling only), and
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
    "window_attention",
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


def paged_decode_supported(block_len: int, head_dim: int, itemsize: int = 4,
                           *, lanes: int) -> bool:
    """Shape gate for the fused kernel: it copies whole rows of a page —
    ``lanes`` = the pool array's lane axis, every kv head side by side —
    from HBM into VMEM tiles. Mosaic slices an HBM array only at its
    (sublane, 128) tiling, so ``lanes`` must be a multiple of 128 and
    block_len of the dtype's sublane minimum; D a multiple of 8 (the
    per-head lane slice). ``tests/test_tpu_compile.py`` compiles the
    kernel for a v5e chip across this gate's edge."""
    sub = _SUBLANE.get(itemsize, 8)
    return (block_len % sub == 0 and lanes % 128 == 0
            and head_dim % 8 == 0 and head_dim >= 8)


def _on_cpu() -> bool:
    """Whether this process's default backend is the CPU, where the
    kernel can only run interpreted."""
    return jax.devices()[0].platform == "cpu"


#: Most rows of context one compute step folds in. A taller tile pays
#: the step's fixed costs (the loop, the copies' waits, the accumulator's
#: rescale) less often and computes more masked rows in a context's last
#: tile: on a v5e 512 rows beat 128 by a third at 640 lanes and one kv
#: head (PERF.md, PR 30).
_TILE_ROWS = 512
#: Most elements the streamed tiles may hold in VMEM (both buffers of
#: every pool array): 2 MiB of bfloat16, 4 MiB of float32, well under the
#: 16 MiB a kernel is given by default. A tile is thus about the same
#: BYTES whatever the row's width: 128 rows of 20 K and V heads of 64
#: (where 128 rows measured best), 512 rows of a 640-lane latent.
_TILE_ELEMENTS = 1 << 20


def _default_block_kv(block_len: int, itemsize: int = 4,
                      row_lanes: int = 0) -> int:
    """The tile height nobody pinned: ``_TILE_ROWS`` rows — several small
    pages a compute step, or part of a large one — halved while the
    double-buffered tiles (``row_lanes`` = one context row of every pool
    array) overrun ``_TILE_ELEMENTS``, and while the height neither
    divides the page nor is a multiple of it."""
    sub = _SUBLANE.get(itemsize, 8)
    rows = _TILE_ROWS
    while rows > sub and (
        2 * rows * row_lanes > _TILE_ELEMENTS
        or (block_len % rows and rows % block_len)
    ):
        rows //= 2
    return rows


def _decode_kernel(layer_ref, table_ref, pos_ref, valid_ref, q_ref, k_hbm,
                   *refs, block_kv, bl, mb, scale, h_kv, g, d, d_v, shared):
    """One SLOT of the fused paged decode: a loop over the slot's LIVE
    tiles, so a call costs a constant per slot plus what its running
    slots' contexts cost — nothing per page that nobody holds.

    The pool stays in HBM (``memory_space=HBM``). A tile is ``block_kv``
    rows of context: ``block_kv // bl`` whole pages, or a ``block_kv``-row
    part of one, copied by ``make_async_copy`` from where the prefetched
    table says they lie into one of two VMEM buffers while the other is
    computed on. The trip count is ``cdiv(pos + 1, block_kv)`` for a slot
    that runs this wave and 0 for one that does not (``valid`` 0: free, or
    mid-prefill — its output row is zeros, which the wave ignores); page
    copies past the context are not started, their rows keep what an
    earlier tile left (zeros at first) and are masked by position.

    Each tile has every kv head's rows side by side on the lane axis; each
    head's ``(block_kv, D)`` lane slice is folded into the flash-style
    running softmax held in f32 scratch (one row group of ``g`` query
    heads per kv head) and the normalized output written once, after the
    last tile. The new K/V row was scattered into the pool BEFORE the
    kernel, so key positions ``<= pos`` (the query's own row included) are
    all read from the pool — exact prefix semantics, one code path. All
    ops stay 2D per head (Mosaic rejects 3D shape casts).

    ``shared``: there is no V array — a row's first ``d_v`` lanes ARE its
    value (a latent pool: one ``d``-lane row per token, read once for
    every query head), so the tile that gave the scores gives the values
    too and the pool is streamed once."""
    n = 1 if shared else 2                # pool arrays: K (and V)
    pools, (o_ref, *refs) = (k_hbm, *refs[:n - 1]), refs[n - 1:]
    bufs, (sems, m_ref, l_ref, acc_ref) = refs[:n], refs[n:]
    k_buf, v_buf = bufs[0], bufs[-1]
    i = pl.program_id(0)
    layer = layer_ref[0]
    # Visible keys: positions [0, pos] of a slot that runs, none otherwise.
    n_ctx = jnp.where(valid_ref[i] > 0, pos_ref[i] + 1, 0)
    n_tiles = pl.cdiv(n_ctx, block_kv)
    chunk = min(bl, block_kv)             # rows of one copy

    def copies(t, buf):
        """The copies of tile ``t`` into buffer ``buf``, each with the
        condition under which it is live."""
        out = []
        for c in range(block_kv // chunk):
            row = t * block_kv + c * chunk          # global position
            page = table_ref[i * mb + jnp.minimum(row // bl, mb - 1)]
            for n, (hbm, vmem) in enumerate(zip(pools, bufs)):
                src = hbm.at[layer, page] if chunk == bl else \
                    hbm.at[layer, page, pl.ds(row % bl, chunk)]
                out.append((row < n_ctx, pltpu.make_async_copy(
                    src, vmem.at[buf, pl.ds(c * chunk, chunk)],
                    sems.at[buf, n],
                )))
        return out

    def start(t, buf):
        for live, copy in copies(t, buf):
            pl.when(live)(copy.start)

    def wait(t, buf):
        for live, copy in copies(t, buf):
            pl.when(live)(copy.wait)

    @pl.when(i == 0)
    def _clear():
        # Rows no copy has written yet must hold finite values: a masked
        # column's weight is exactly 0, and 0 x garbage may be NaN.
        for vmem in bufs:
            vmem[...] = jnp.zeros_like(vmem)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_tiles > 0)
    def _first():
        start(0, 0)

    def tile(t, carry):
        buf = t % 2

        @pl.when(t + 1 < n_tiles)
        def _next():
            start(t + 1, 1 - buf)

        wait(t, buf)
        base = t * block_kv               # global position of tile row 0
        for h in range(h_kv):
            rows = slice(h * g, (h + 1) * g)
            q = q_ref[0, rows, :]                      # (g, D)
            k = k_buf[buf, :, h * d:(h + 1) * d]       # (block_kv, D)
            v = v_buf[buf, :, h * d:h * d + d_v]
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
        return carry

    jax.lax.fori_loop(0, n_tiles, tile, None)
    # A slot that did not run folded nothing in: 0 / 1, not 0 / 0.
    denom = jnp.where(n_tiles > 0, l_ref[:, 0:1], 1.0)
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_kv", "interpret", "scale", "d_v", "name")
)
def _paged_decode_pallas(q, k_pages, v_pages, block_table, positions, valid,
                         *, layer=0, block_kv: int, interpret: bool,
                         scale: Optional[float] = None,
                         d_v: Optional[int] = None,
                         name: str = "paged_decode"):
    """The fused gather+attend for one decode wave: ``q`` (S, Hq, D),
    pool/table/positions/valid/layer as in :func:`paged_attention` (new
    rows already scattered). Returns ``out`` (S, Hq, d_v); the row of a
    slot with ``valid`` 0 is zeros.

    ``v_pages=None`` is the latent pool (:func:`paged_latent_decode`): ONE
    kv "head" of ``D`` = the array's whole lane axis for all ``Hq`` query
    heads, values = the first ``d_v`` lanes of the same rows, ``scale``
    given by the caller. With a V array ``d_v`` is ``D`` and ``scale``
    ``1/sqrt(D)`` — one kernel body for both.

    The grid is the slots; the pool is handed over where it lies and the
    body copies a slot's live tiles itself (:func:`_decode_kernel`). A
    copy moves whole rows of the pool AS STORED, every kv head side by
    side on the lane axis, so any head count and width is Mosaic-legal;
    q/out blocks carry the whole ``(Hq, D)`` head axis and the kernel
    selects each kv head by a static lane slice. The layer rides in as a
    prefetched scalar beside the table, positions and valid, so one
    kernel serves a Python-loop layer and a scanned one — and, jitted,
    the layers of a Python-loop model share ONE traced and lowered body
    (36 lowerings of it cost GPT-2 large 30 s of every start; PERF.md,
    PR 30)."""
    s, hq, d = q.shape
    _, _, bl, hd = k_pages.shape
    shared = v_pages is None
    h_kv = hd // d
    mb = block_table.shape[1]
    g = hq // h_kv
    d_v = d if d_v is None else d_v
    scale = 1.0 / math.sqrt(d) if scale is None else scale

    def slot_map(i, *prefetched):
        del prefetched
        return (i, 0, 0)

    pools = (k_pages,) if shared else (k_pages, v_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s,),
        in_specs=[pl.BlockSpec((1, hq, d), slot_map)]
        + [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pools),
        out_specs=pl.BlockSpec((1, hq, d_v), slot_map),
        scratch_shapes=[pltpu.VMEM((2, block_kv, hd), k_pages.dtype)
                        for _ in pools] + [
            pltpu.SemaphoreType.DMA((2, len(pools))),
            pltpu.VMEM((hq, 128), jnp.float32),   # running max (lane-bcast)
            pltpu.VMEM((hq, 128), jnp.float32),   # running denom
            pltpu.VMEM((hq, d_v), jnp.float32),   # unnormalized accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, block_kv=block_kv, bl=bl, mb=mb, scale=scale,
            h_kv=h_kv, g=g, d=d, d_v=d_v, shared=shared,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hq, d_v), q.dtype),
        # One slot after another: the tile buffers are cleared at slot 0.
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_table.reshape(-1).astype(jnp.int32),
      jnp.asarray(positions, jnp.int32), jnp.asarray(valid, jnp.int32),
      q, *pools)


def paged_latent_decode(q, pages, block_table, positions, valid=None, *,
                        layer=0, d_v: int, scale: float,
                        block_kv: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """One decode wave of latent attention (MLA, absorbed form) against
    ONE pool array: ``q`` ``(S, Hq, Dk)`` — every query head already
    carried into the latent's own space, its rotary part behind it —
    ``pages`` ``(L, NB, BL, Dk)`` holding one ``Dk``-lane row per token
    (the new rows already scattered, :func:`write_pages`). Scores are
    ``q . row * scale`` over all ``Dk`` lanes, values the first ``d_v``
    lanes of the SAME rows. Returns ``(S, Hq, d_v)``. ``valid`` ``(S,)``
    as in :func:`paged_attention` (None: every slot runs): the row of a
    slot with ``valid`` 0 is garbage the caller ignores.

    Where the fused kernel can run (a TPU, or ``interpret=True``) it is
    :func:`_paged_decode_pallas` with no V array, under the Pallas name
    ``mla_decode``: each live page of a slot that runs is streamed once
    for all ``Hq`` heads. Elsewhere the slot's pages are gathered and
    attended in XLA."""
    bl = int(pages.shape[2])
    itemsize = jnp.dtype(pages.dtype).itemsize
    on_cpu = _on_cpu()
    if paged_decode_supported(
        bl, q.shape[-1], itemsize, lanes=pages.shape[3]
    ) and (not on_cpu or interpret):
        if valid is None:
            valid = jnp.ones(positions.shape, jnp.int32)
        return _paged_decode_pallas(
            q, pages, None, block_table, positions, valid, layer=layer,
            block_kv=int(block_kv or _default_block_kv(
                bl, itemsize, pages.shape[3])),
            interpret=on_cpu or bool(interpret), scale=float(scale),
            d_v=int(d_v), name="mla_decode",
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


#: Most float32 scores (slots x heads x chunk rows x table positions) the
#: one-shot chunk attention may materialise: 256 MiB. A chunk against a
#: longer table walks its LIVE context in tiles instead
#: (:func:`_attend_chunk_live`).
_CHUNK_SCORES_MAX = 1 << 26
#: Context rows a tile of that walk holds.
_CHUNK_TILE_ROWS = 2048


def _attend_chunk_live(q, k_pages, v_pages, block_table, positions, valid,
                       layer):
    """:func:`_attend_xla` for a prefill chunk against a LONG table: the
    context is gathered a tile of pages at a time and folded into a running
    softmax, over the tiles that hold a live row only (a loop whose trip
    count is a traced value: ``max(positions + valid)`` rows), so neither
    the whole table's context nor its ``(C, table)`` scores materialise.
    The one-shot numbers up to the sums' order; a TPU runs ``kv_prefill``."""
    s, c, hq, d = q.shape
    h_kv = k_pages.shape[3] // d
    g = hq // h_kv
    bl = k_pages.shape[2]
    mb = block_table.shape[1]
    per = max(n for n in range(1, mb + 1)
              if mb % n == 0 and n * bl <= max(_CHUNK_TILE_ROWS, bl))
    tile = per * bl
    scale = 1.0 / math.sqrt(d)
    q5 = q.reshape(s, c, h_kv, g, d)
    q_pos = positions[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    live = jnp.max(positions + jnp.maximum(valid, 1))
    f32 = jnp.float32

    def body(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(block_table, j * per, per, axis=1)
        k_ctx = k_pages[layer, ids].reshape(s, tile, h_kv, d)
        v_ctx = v_pages[layer, ids].reshape(s, tile, h_kv, d)
        logits = jnp.einsum(
            "sckgd,stkd->skgct", q5, k_ctx, preferred_element_type=f32
        ) * scale
        key_pos = j * tile + jnp.arange(tile, dtype=jnp.int32)
        mask = key_pos[None, None, :] <= q_pos[:, :, None]      # (S, C, T)
        logits = jnp.where(mask[:, None, None, :, :], logits, -jnp.inf)
        m2 = jnp.maximum(m, jnp.max(logits, axis=-1))
        w = jnp.exp(logits - m2[..., None])
        fade = jnp.exp(m - m2)
        l = l * fade + jnp.sum(w, axis=-1)
        acc = acc * fade[..., None] + jnp.einsum(
            "skgct,stkd->skgcd", w.astype(v_ctx.dtype), v_ctx,
            preferred_element_type=f32)
        return m2, l, acc

    shape = (s, h_kv, g, c)
    # Position 0 is visible to every row, so after tile 0 the running
    # maximum is finite everywhere.
    m, l, acc = jax.lax.fori_loop(
        0, -(-live // tile), body,
        (jnp.full(shape, -jnp.inf, f32), jnp.zeros(shape, f32),
         jnp.zeros(shape + (d,), f32)))
    out = (acc / l[..., None]).astype(q.dtype)                  # (S, Hkv, G, C, D)
    return jnp.moveaxis(out, 3, 1).reshape(s, c, hq * d)


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
    ``paged_decode`` tune table (C=1), defaulting on a TPU to a kernel
    (C=1 decode, or ``ops.kv_prefill`` for a chunk against a long table)
    and the XLA path everywhere else. A pinned ``"pallas"`` that cannot
    run (a short table, unsupported geometry) raises ``ValueError``; on a CPU
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
    kernel_can_run = paged_decode_supported(bl, d, itemsize, lanes=h_kv * d) \
        and (c == 1 or _chunk_kernel_fits(s, c, hq, h_kv, d, bl, mb, itemsize))
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
    block_kv = block_kv or _default_block_kv(bl, itemsize, 2 * h_kv * d)
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
            f"block_len={bl}, head_dim={d}, lanes={h_kv * d}, "
            f"itemsize={itemsize}) — the kernels are C=1 decode under "
            "paged_decode_supported and long-table chunks under "
            "kv_prefill_supported; pin impl='xla' for this shape"
        )

    k_pages, v_pages = write_kv_pages(
        k_pages, v_pages, block_table, positions, valid, k_new, v_new,
        layer=layer,
    )

    if impl == "pallas" and c == 1:
        if block_kv % _SUBLANE.get(itemsize, 8) or (
            bl % block_kv and block_kv % bl
        ):
            raise ValueError(
                f"paged_attention: block_kv={block_kv} must be a "
                f"multiple of the sublane tile that divides "
                f"block_len={bl} or is a multiple of it"
            )
        out = _paged_decode_pallas(
            q[:, 0], k_pages, v_pages, block_table, positions, valid,
            layer=layer,
            block_kv=int(block_kv), interpret=on_cpu or bool(interpret),
        ).reshape(s, 1, hq * d)
        return out, k_pages, v_pages
    if impl == "xla" and _long_chunk(s, c, hq, mb, bl):
        out = _attend_chunk_live(
            q, k_pages, v_pages, block_table, positions, valid, layer)
    elif impl == "xla":
        out = _attend_xla(q, k_pages, v_pages, block_table, positions, layer)
    else:
        from rocket_tpu.ops.kv_prefill import kv_prefill

        out = kv_prefill(
            q, k_pages, v_pages, block_table, positions, valid, layer,
            interpret=on_cpu or bool(interpret),
        )
    return out, k_pages, v_pages


def _long_chunk(s, c, hq, mb, bl) -> bool:
    """Whether a chunk's one-shot scores would pass ``_CHUNK_SCORES_MAX``,
    so that its attention walks the live context."""
    return c > 1 and s * hq * c * mb * bl > _CHUNK_SCORES_MAX


def _chunk_kernel_fits(s, c, hq, h_kv, d, bl, mb, itemsize) -> bool:
    """The chunk kernel's gate: the chunk walks the live context and
    ``ops.kv_prefill`` takes its shapes."""
    from rocket_tpu.ops.kv_prefill import kv_prefill_supported

    return _long_chunk(s, c, hq, mb, bl) and kv_prefill_supported(
        c, hq, h_kv, d, bl, mb * bl, itemsize)


def _ring_write(ring, slot_ids, rows, keep, new, *, layer):
    """Rows ``new`` ``(S, C, ...)`` into ``ring`` ``(Lw, max_slots, W,
    lanes)`` at ``(layer, slot_ids[s], rows[s, i])`` where ``keep[s, i]``;
    the others are dropped (an index past the ring), so a slot that does
    not run keeps its ring bitwise. One scatter of whole rows, in place
    where the ring is donated."""
    s, c = rows.shape
    rows = jnp.where(keep, rows, ring.shape[2])
    return ring.at[layer, slot_ids[:, None], rows].set(
        new.astype(ring.dtype).reshape(s, c, -1), mode="drop")


def window_attention(q, k_new, v_new, k_ring, v_ring, positions, valid, *,
                     slots=None, layer=0, interpret: Optional[bool] = None):
    """One chunk of sliding-window GQA attention against a RING: query row
    ``i`` of slot ``s`` (global position ``positions[s] + i``) sees the
    keys of positions ``p`` with ``positions[s] + i - W < p <= positions[s]
    + i``.

    ``q`` ``(S, C, Hq, D)``; ``k_new``/``v_new`` ``(S, C, Hkv, D)`` (RoPE
    already applied); ``k_ring``/``v_ring`` ``(Lw, max_slots, W, Hkv*D)``:
    the row of position ``p`` of a slot lies at ring row ``p mod W`` of
    layer ``layer``, and nothing but position says which rows are live, so
    a ring needs no reset and no allocator. ``slots`` ``(S,)`` int32 names
    the slot of each row (None: row ``s`` is slot ``s``, the decode wave);
    ``valid`` as in :func:`paged_attention`.

    * **Decode** (C = 1) where the fused kernel can run (a TPU, or
      ``interpret=True``): the new row is written first, then the slot's
      rows ``0 .. min(position, W - 1)`` are all visible — every one of
      them inside the window, and keys carry their rotation, so ring order
      does not matter — through :func:`_paged_decode_pallas` over the ring
      viewed as ONE page of ``W`` rows a slot, under the Pallas name
      ``window_decode``. Elsewhere a decode row is a chunk of one.
    * **Chunk** (C > 1, or a decode row off the kernel): ``[ring as it was
      before the chunk | the chunk's
      own rows]`` under the band mask (a ring row's position is the
      latest ``p < positions[s]`` with ``p = row mod W``, live where ``p >=
      0``), one K/V head at a time so that the scores stay ``(C, W + C)``
      a head group; then the chunk's last ``min(valid, W)`` rows are
      written (an earlier row's ring row is a later row's).

    Returns ``(out (S, C, Hq*D), k_ring', v_ring')``. Padded query rows
    produce well-defined garbage (a row sees itself) the callers ignore."""
    s, c, hq, d = q.shape
    _, _, w, lanes = k_ring.shape
    h_kv = lanes // d
    g = hq // h_kv
    slot_ids = jnp.arange(s, dtype=jnp.int32) if slots is None else slots
    q_pos = positions[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    j = jnp.arange(c, dtype=jnp.int32)[None, :]
    keep = (j < valid[:, None]) & (j >= valid[:, None] - w)

    def write(k_ring, v_ring):
        rows = jnp.mod(q_pos, w)
        return (_ring_write(k_ring, slot_ids, rows, keep, k_new, layer=layer),
                _ring_write(v_ring, slot_ids, rows, keep, v_new, layer=layer))

    itemsize = jnp.dtype(k_ring.dtype).itemsize
    on_cpu = _on_cpu()
    if c == 1 and paged_decode_supported(w, d, itemsize, lanes=lanes) and (
            not on_cpu or interpret):
        k_ring, v_ring = write(k_ring, v_ring)
        out = _paged_decode_pallas(
            q[:, 0], k_ring, v_ring,
            slot_ids[:, None], jnp.minimum(positions, w - 1), valid, layer=layer,
            block_kv=min(w, _default_block_kv(w, itemsize, 2 * lanes)),
            interpret=on_cpu or bool(interpret), name="window_decode",
        )
        return out.reshape(s, 1, hq * d), k_ring, v_ring

    scale = 1.0 / math.sqrt(d)
    r = jnp.arange(w, dtype=jnp.int32)[None, :]
    before = positions[:, None] - 1
    ring_pos = before - jnp.mod(before - r, w)                     # (S, W)
    key_pos = jnp.concatenate([ring_pos, q_pos], axis=1)          # (S, W + C)
    key_ok = jnp.concatenate([ring_pos >= 0, jnp.ones_like(q_pos, bool)], axis=1)
    band = key_ok[:, None, :] & (key_pos[:, None, :] <= q_pos[:, :, None]) & (
        key_pos[:, None, :] > q_pos[:, :, None] - w)               # (S, C, W + C)

    def keys(ring, new):
        old = ring[layer, slot_ids].reshape(s, w, h_kv, d)
        both = jnp.concatenate([old, new.astype(ring.dtype)], axis=1)
        return jnp.moveaxis(both, 2, 0)                           # (Hkv, S, W + C, D)

    def one_head(xs):
        qh, kh, vh = xs                    # (S, C, G, D), (S, W + C, D) x 2
        logits = jnp.einsum(
            "scgd,std->sgct", qh, kh, preferred_element_type=jnp.float32
        ) * scale
        logits = jnp.where(band[:, None], logits, -jnp.inf)
        weights = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("sgct,std->scgd", weights.astype(vh.dtype), vh)

    out = jax.lax.map(one_head, (
        jnp.moveaxis(q.reshape(s, c, h_kv, g, d), 2, 0),
        keys(k_ring, k_new), keys(v_ring, v_new)))                # (Hkv, S, C, G, D)
    out = jnp.moveaxis(out, 0, 2).reshape(s, c, hq * d)
    k_ring, v_ring = write(k_ring, v_ring)
    return out, k_ring, v_ring


# -- block-sparse attention over the pool (InfLLM v2, MiniCPM4) ---------------
#
# A sparse layer caches its K/V rows in the pool like any other; what it
# adds is a COMPRESSED-KEY cache a slot (one row of ``Hkv * D`` lanes for
# every ``kernel_stride`` positions: the mean of ``kernel_size`` keys), which
# a query past ``dense_len`` scores to choose ``topk`` pages per K/V head.
# The selection runs in XLA; the attention over the chosen pages is the paged
# decode kernel walking each (slot, K/V head)'s own page list
# (``sparse_decode``) and, for a prefill chunk, a walk of the live context
# under a (row, page) mask.

import dataclasses  # noqa: E402 -- here: a line added above would move the source locations the decode kernel's lowered body carries

__all__ += [
    "SparseAttentionConfig",
    "write_compressed_keys",
    "select_pages",
    "sparse_attention",
]


@dataclasses.dataclass(frozen=True)
class SparseAttentionConfig:
    """Block-sparse attention (MiniCPM4's ``sparse_config``): keys are
    pooled by ``kernel_size`` at a ``kernel_stride``, scored against each
    query, and the ``topk`` blocks of ``block_size`` positions with the
    highest scores are attended, block ``0 .. init_blocks - 1`` and every
    block holding one of the last ``window_size`` positions always. A query
    at a position under ``dense_len`` attends every position before it."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def units(self, max_len: int) -> int:
        """Compressed-key rows a slot of ``max_len`` positions holds."""
        return -(-max_len // self.kernel_stride)

    @property
    def list_len(self) -> int:
        """Entries of a decode row's page list: the ``topk`` of a sparse
        query, or every page of a dense one."""
        return max(self.topk, -(-self.dense_len // self.block_size))


def write_compressed_keys(kc, k_pages, block_table, positions, valid, *,
                          rows: int, layer, slots, cfg: SparseAttentionConfig):
    """The compressed keys that complete in a chunk of ``rows`` rows, into
    ``kc`` ``(Ls, max_slots, units, lanes)`` at ``(layer, slots[s], j)``:
    unit ``j`` is the mean, in float32, of the keys of positions ``j *
    stride .. j * stride + kernel - 1`` and completes with the last of them.
    The chunk's K rows are already in ``k_pages`` (``write_kv_pages``), the
    earlier ones a unit straddles are read from there too. A unit that does
    not complete in the chunk is not written."""
    stride, size = cfg.kernel_stride, cfg.kernel_size
    bl = k_pages.shape[2]
    mb = block_table.shape[1]
    n = -(-rows // stride) + 1
    first = jnp.maximum(-((size - 1 - positions) // stride), 0)      # ceil
    j = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]     # (S, n)
    end = j * stride + size - 1
    done = (end >= positions[:, None]) & (end < (positions + valid)[:, None])
    pos = j[..., None] * stride + jnp.arange(size, dtype=jnp.int32)  # (S, n, K)
    page = jnp.take_along_axis(
        block_table, jnp.clip(pos // bl, 0, mb - 1).reshape(pos.shape[0], -1),
        axis=1).reshape(pos.shape)
    keys = k_pages[layer, page, pos % bl].astype(jnp.float32)        # (S, n, K, lanes)
    mean = jnp.mean(keys, axis=2).astype(kc.dtype)
    units = jnp.where(done, j, kc.shape[2])
    return kc.at[layer, slots[:, None], units].set(mean, mode="drop")


def _unit_scores(q, kc_rows, pos, *, h_kv: int, cfg: SparseAttentionConfig):
    """``(S, C, Hkv, units)`` float32: each query row's score of every
    unit — per K/V head ``g`` the softmax over the complete units (``j *
    stride + kernel - 1 <= pos``) of ``q_h . Kc_g / sqrt(D)``, summed over
    the group's query heads; -1 where the unit is incomplete. ``q`` (S, C,
    Hq, D); ``kc_rows`` (S, units, Hkv * D); ``pos`` (S, C). A loop over
    the group's heads in XLA: the CPU's path and the specification of
    the kernel ``select_scores`` (``ops/select_scores.py``)."""
    s, c, hq, d = q.shape
    g = hq // h_kv
    u = kc_rows.shape[1]
    stride, size = cfg.kernel_stride, cfg.kernel_size
    kcs = kc_rows.reshape(s, u, h_kv, d)
    q5 = q.reshape(s, c, h_kv, g, d)
    complete = (jnp.arange(u, dtype=jnp.int32) * stride + size - 1)[None, None, :] \
        <= pos[..., None]                                             # (S, C, U)
    scale = 1.0 / math.sqrt(d)

    def head(i, acc):
        qh = jax.lax.dynamic_index_in_dim(q5, i, axis=3, keepdims=False)
        logits = jnp.einsum("sckd,sukd->scku", qh, kcs,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(complete[:, :, None, :], logits, _NEG_INF)
        return acc + jax.nn.softmax(logits, axis=-1)

    c_sum = jax.lax.fori_loop(0, g, head, jnp.zeros((s, c, h_kv, u), jnp.float32))
    return jnp.where(complete[:, :, None, :], c_sum, -1.0)


def _block_scores(q, kc, pos, *, h_kv: int, cfg: SparseAttentionConfig,
                  layer, slots, valid, interpret: Optional[bool]):
    """``(S, C, Hkv, blocks)`` float32: each query row's score of every
    block — the most of its unit scores over the units that overlap the
    block; -1 where none is complete. The unit scores come from the
    kernel ``select_scores`` where it runs (a TPU, or ``interpret=True``)
    and takes the shapes, else from :func:`_unit_scores`. ``kc`` (layers,
    max_slots, units, Hkv * D) read at ``(layer, slots[s])``, all slots
    in order where ``slots`` is None."""
    from rocket_tpu.ops.select_scores import select_scores, select_scores_supported

    s, c, hq, d = q.shape
    u = kc.shape[2]
    stride, size, bsz = cfg.kernel_stride, cfg.kernel_size, cfg.block_size
    on_cpu = _on_cpu()
    if select_scores_supported(c, hq, h_kv, d, u) and (not on_cpu or interpret):
        slot_ids = jnp.arange(s, dtype=jnp.int32) if slots is None else slots
        live = jnp.ones((s,), jnp.int32) if valid is None else valid
        c_sum = select_scores(q, kc, layer, slot_ids, pos[:, 0], live,
                              stride=stride, size=size,
                              interpret=on_cpu or bool(interpret))
    else:
        if slots is None:
            kc_rows = kc[layer]
        else:
            # One slot's rows, sliced and copied on their own: a gather of
            # them had the compiler relayout the whole cache first.
            kc_rows = jax.lax.optimization_barrier(kc[layer, slots])
        c_sum = _unit_scores(q, kc_rows, pos, h_kv=h_kv, cfg=cfg)
    per = bsz // stride
    nb = u // per
    lo, hi = -((size - 1) // stride), (bsz - 1) // stride
    padded = jnp.pad(c_sum, ((0, 0), (0, 0), (0, 0), (-lo, hi)), constant_values=-1.0)
    r = None
    for o in range(lo, hi + 1):
        take = jax.lax.slice_in_dim(padded, o - lo, o - lo + nb * per, stride=per, axis=3)
        r = take if r is None else jnp.maximum(r, take)
    return r


def select_pages(q, kc, pos, *, h_kv: int, cfg: SparseAttentionConfig,
                 layer=0, slots=None, valid=None,
                 interpret: Optional[bool] = None):
    """The blocks each query row attends: ``(top (S, C, Hkv, topk) block
    ids, sparse (S, C) bool)`` — ``top`` the ``topk`` best by
    :func:`_block_scores` among the blocks that start at or before the row
    (blocks ``< init_blocks`` and those holding a position in ``(pos -
    window_size, pos]`` first, ties to the lower id), ``sparse`` whether the
    row is at or past ``dense_len`` (else it attends every block). ``kc``
    (layers, max_slots, units, Hkv * D), row ``s`` reading slot
    ``slots[s]`` of ``layer`` (all slots in order where ``slots`` is None);
    a slot row whose ``valid`` is 0 does not run, and its ``top`` is
    never read."""
    nb = kc.shape[2] // (cfg.block_size // cfg.kernel_stride)
    start = jnp.arange(nb, dtype=jnp.int32) * cfg.block_size
    sparse = pos >= cfg.dense_len

    def scored():
        r = _block_scores(q, kc, pos, h_kv=h_kv, cfg=cfg, layer=layer,
                          slots=slots, valid=valid, interpret=interpret)
        p = pos[..., None, None]
        forced = (jnp.arange(nb) < cfg.init_blocks) | (
            start + cfg.block_size - 1 > p - cfg.window_size)
        r = jnp.where(forced, 1e30, r)
        r = jnp.where(start <= p, r, -1e30)
        return jax.lax.top_k(r, cfg.topk)[1].astype(jnp.int32)

    s, c = pos.shape
    top = jax.lax.cond(
        jnp.any(sparse), scored,
        lambda: jnp.zeros((s, c, h_kv, cfg.topk), jnp.int32))
    return top, sparse


def _sparse_decode_kernel(layer_ref, table_ref, ids_ref, count_ref, pos_ref,
                          q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
                          m_ref, l_ref, acc_ref, *, per, bl, mb, nl, h_kv,
                          d, scale):
    """One (slot, K/V head) of a sparse decode wave: ``_decode_kernel``'s
    double-buffered walk over the head's OWN page list (``ids``, logical
    block ids, ``count`` of them) in tiles of ``per`` pages, each page's
    ``D`` lanes of this head copied from where the table says it lies. A
    key is seen where its page is on the list and its position is at most
    the slot's; a slot that does not run has ``count`` 0 and folds
    nothing."""
    i, h = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    row = i * h_kv + h
    n = count_ref[row]
    pos = pos_ref[i]
    n_tiles = pl.cdiv(n, per)
    lane = h * d
    g = q_ref.shape[2]
    width = per * bl

    def entry(t, c):
        return ids_ref[row * nl + jnp.minimum(t * per + c, nl - 1)]

    def copies(t, buf):
        out = []
        for c in range(per):
            page = table_ref[i * mb + jnp.minimum(entry(t, c), mb - 1)]
            for m, (hbm, vmem) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                out.append((t * per + c < n, pltpu.make_async_copy(
                    hbm.at[layer, page, :, pl.ds(lane, d)],
                    vmem.at[buf, pl.ds(c * bl, bl)],
                    sems.at[buf, m],
                )))
        return out

    def start(t, buf):
        for live, copy in copies(t, buf):
            pl.when(live)(copy.start)

    def wait(t, buf):
        for live, copy in copies(t, buf):
            pl.when(live)(copy.wait)

    @pl.when((i == 0) & (h == 0))
    def _clear():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_tiles > 0)
    def _first():
        start(0, 0)

    def tile(t, carry):
        buf = t % 2

        @pl.when(t + 1 < n_tiles)
        def _next():
            start(t + 1, 1 - buf)

        wait(t, buf)
        col = jax.lax.broadcasted_iota(jnp.int32, (g, width), 1)
        key_pos = jnp.full((g, width), -1, jnp.int32)
        for c in range(per):
            base = jnp.where(t * per + c < n, entry(t, c) * bl - c * bl, -width - 1)
            here = (col >= c * bl) & (col < (c + 1) * bl)
            key_pos = jnp.where(here, col + base, key_pos)
        seen = (key_pos >= 0) & (key_pos <= pos)
        q = q_ref[0, 0]                                   # (g, D)
        k = k_buf[buf]                                    # (width, D)
        v = v_buf[buf]
        s_ij = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale
        s_ij = jnp.where(seen, s_ij, _NEG_INF)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s_ij, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s_ij - m_new), 0.0)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(0, n_tiles, tile, None)
    denom = jnp.where(n_tiles > 0, l_ref[:, 0:1], 1.0)
    o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


#: Context rows a tile of ``sparse_decode`` folds at once (whole pages).
_SPARSE_TILE_ROWS = 512


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sparse_decode_pallas(q, k_pages, v_pages, block_table, ids, count,
                          positions, layer, *, interpret: bool):
    """The fused walk of a decode wave over each (slot, K/V head)'s page
    list, under the Pallas name ``sparse_decode``: ``q`` (S, Hq, D);
    ``ids`` (S, Hkv, NL) logical block ids, the first ``count`` (S, Hkv)
    of them attended. Returns (S, Hq, D); a row with ``count`` 0 is
    zeros."""
    s, hq, d = q.shape
    _, _, bl, lanes = k_pages.shape
    h_kv = lanes // d
    g = hq // h_kv
    mb = block_table.shape[1]
    nl = ids.shape[2]
    per = max(1, min(nl, _SPARSE_TILE_ROWS // bl))

    def head_map(i, h, *prefetched):
        del prefetched
        return (i, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s, h_kv),
        in_specs=[pl.BlockSpec((1, 1, g, d), head_map),
                  pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, 1, g, d), head_map),
        scratch_shapes=[
            pltpu.VMEM((2, per * bl, d), k_pages.dtype),
            pltpu.VMEM((2, per * bl, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((g, 128), jnp.float32),   # running max (lane-bcast)
            pltpu.VMEM((g, 128), jnp.float32),   # running denom
            pltpu.VMEM((g, d), jnp.float32),     # unnormalized accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _sparse_decode_kernel, per=per, bl=bl, mb=mb, nl=nl, h_kv=h_kv,
            d=d, scale=1.0 / math.sqrt(d),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h_kv, g, d), q.dtype),
        # One step after another: the tile buffers are cleared at the first.
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="sparse_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_table.reshape(-1).astype(jnp.int32),
      ids.reshape(-1).astype(jnp.int32), count.reshape(-1).astype(jnp.int32),
      jnp.asarray(positions, jnp.int32),
      q.reshape(s, h_kv, g, d), k_pages, v_pages)
    return out.reshape(s, hq, d)


def _sparse_decode_xla(q, k_pages, v_pages, block_table, ids, count,
                       positions, layer):
    """:func:`_sparse_decode_pallas` gathered and attended in XLA: each
    (slot, K/V head)'s listed pages, the same keys seen."""
    s, hq, d = q.shape
    _, _, bl, lanes = k_pages.shape
    h_kv = lanes // d
    g = hq // h_kv
    mb = block_table.shape[1]
    nl = ids.shape[2]
    pages = jnp.take_along_axis(
        block_table, jnp.clip(ids, 0, mb - 1).reshape(s, -1), axis=1
    ).reshape(s, h_kv, nl)

    def ctx(pool):
        rows = pool[layer, pages].reshape(s, h_kv, nl * bl, h_kv, d)
        return jnp.take_along_axis(
            rows, jnp.arange(h_kv)[None, :, None, None, None], axis=3)[:, :, :, 0]

    k_ctx, v_ctx = ctx(k_pages), ctx(v_pages)                    # (S, Hkv, T, D)
    key_pos = (ids[..., None] * bl + jnp.arange(bl)).reshape(s, h_kv, -1)
    listed = (jnp.arange(nl)[None, None, :] < count[..., None])
    listed = jnp.repeat(listed, bl, axis=2)
    seen = listed & (key_pos <= positions[:, None, None])
    logits = jnp.einsum("skgd,sktd->skgt", q.reshape(s, h_kv, g, d), k_ctx,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    logits = jnp.where(seen[:, :, None], logits, -jnp.inf)
    weights = jax.nn.softmax(logits, axis=-1)
    weights = jnp.where(jnp.any(seen, -1)[:, :, None, None], weights, 0.0)
    out = jnp.einsum("skgt,sktd->skgd", weights.astype(v_ctx.dtype), v_ctx)
    return out.reshape(s, hq, d)


def _sparse_chunk_xla(q, k_pages, v_pages, block_table, positions, valid,
                      layer, pick):
    """:func:`_attend_chunk_live` under a (row, block) mask: ``pick`` (S,
    Hkv, C, blocks) bool — a query row sees the keys at or before it in
    the blocks its K/V head picked. The walk skips no tile; each tile's
    scores are float32 ``(S, Hkv, G, C, tile)``."""
    s, c, hq, d = q.shape
    h_kv = k_pages.shape[3] // d
    g = hq // h_kv
    bl = k_pages.shape[2]
    mb = block_table.shape[1]
    per = max(n for n in range(1, mb + 1)
              if mb % n == 0 and n * bl <= max(_CHUNK_TILE_ROWS, bl))
    tile = per * bl
    scale = 1.0 / math.sqrt(d)
    q5 = q.reshape(s, c, h_kv, g, d)
    q_pos = positions[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    live = jnp.max(positions + jnp.maximum(valid, 1))
    f32 = jnp.float32

    def body(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(block_table, j * per, per, axis=1)
        k_ctx = k_pages[layer, ids].reshape(s, tile, h_kv, d)
        v_ctx = v_pages[layer, ids].reshape(s, tile, h_kv, d)
        logits = jnp.einsum(
            "sckgd,stkd->skgct", q5, k_ctx, preferred_element_type=f32
        ) * scale
        key_pos = j * tile + jnp.arange(tile, dtype=jnp.int32)
        causal = key_pos[None, None, :] <= q_pos[:, :, None]          # (S, C, T)
        chosen = jnp.repeat(
            jax.lax.dynamic_slice_in_dim(pick, j * per, per, axis=3), bl, axis=3)
        mask = causal[:, None] & chosen                               # (S, Hkv, C, T)
        logits = jnp.where(mask[:, :, None], logits, -jnp.inf)
        m2 = jnp.maximum(m, jnp.max(logits, axis=-1))
        safe = jnp.where(jnp.isfinite(m2), m2, 0.0)
        w = jnp.exp(logits - safe[..., None])
        fade = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
        l = l * fade + jnp.sum(w, axis=-1)
        acc = acc * fade[..., None] + jnp.einsum(
            "skgct,stkd->skgcd", w.astype(v_ctx.dtype), v_ctx,
            preferred_element_type=f32)
        return m2, l, acc

    shape = (s, h_kv, g, c)
    m, l, acc = jax.lax.fori_loop(
        0, -(-live // tile), body,
        (jnp.full(shape, -jnp.inf, f32), jnp.zeros(shape, f32),
         jnp.zeros(shape + (d,), f32)))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return jnp.moveaxis(out, 3, 1).reshape(s, c, hq * d)


def sparse_attention(q, k_new, v_new, k_pages, v_pages, kc, block_table,
                     positions, valid, *, slots=None, layer=0,
                     cfg: SparseAttentionConfig,
                     interpret: Optional[bool] = None):
    """One chunk of block-sparse GQA attention against the paged pool.

    ``q`` ``(S, C, Hq, D)``; ``k_new``/``v_new`` ``(S, C, Hkv, D)``; pool,
    table, positions, valid and ``layer`` as in :func:`paged_attention`;
    ``kc`` ``(sparse layers, max_slots, units, Hkv * D)`` the compressed
    keys, read and written at ``(layer, slots[s])`` (``slots`` None: row
    ``s`` is slot ``s``, the decode wave). The chunk's K/V rows are written
    first, then the compressed keys that complete in it
    (:func:`write_compressed_keys`), then every query row picks its pages
    (:func:`select_pages`) and attends the keys at or before it on them.

    * **Decode** (C = 1): each (slot, K/V head)'s page list — its ``topk``
      picks in order of position, or every page below ``dense_len`` — is
      walked by the fused kernel ``sparse_decode`` where it runs (a TPU, or
      ``interpret=True``), else gathered and attended in XLA.
    * **Chunk**: the picks as a (row, page) mask over a walk of the live
      context: the Pallas kernel ``sparse_prefill``
      (``ops/sparse_prefill.py``) where it runs and takes the shapes, which
      copies no page that no row picked; else :func:`_sparse_chunk_xla`.

    Returns ``(out (S, C, Hq*D), k_pages', v_pages', kc')``."""
    s, c, hq, d = q.shape
    h_kv = k_new.shape[2]
    bl = k_pages.shape[2]
    if bl != cfg.block_size:
        raise ValueError(
            f"sparse_attention: a page ({bl} rows) must be a selection block "
            f"({cfg.block_size})")
    slot_ids = jnp.arange(s, dtype=jnp.int32) if slots is None else slots
    k_pages, v_pages = write_kv_pages(
        k_pages, v_pages, block_table, positions, valid, k_new, v_new,
        layer=layer)
    kc = write_compressed_keys(kc, k_pages, block_table, positions, valid,
                               rows=c, layer=layer, slots=slot_ids, cfg=cfg)
    pos = positions[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    top, sparse = select_pages(q, kc, pos, h_kv=h_kv, cfg=cfg, layer=layer,
                               slots=slots, valid=valid, interpret=interpret)
    if c == 1:
        nl = cfg.list_len
        picked = jnp.pad(jnp.sort(top[:, 0], axis=-1),
                         ((0, 0), (0, 0), (0, nl - cfg.topk)))
        every = jnp.broadcast_to(jnp.arange(nl, dtype=jnp.int32), picked.shape)
        ids = jnp.where(sparse[:, 0, None, None], picked, every)
        live = jnp.minimum(positions // bl + 1, nl)
        count = jnp.where(sparse[:, 0], cfg.topk, live)
        count = jnp.where(valid > 0, count, 0)[:, None] * jnp.ones((1, h_kv), jnp.int32)
        itemsize = jnp.dtype(k_pages.dtype).itemsize
        on_cpu = _on_cpu()
        if paged_decode_supported(bl, d, itemsize, lanes=h_kv * d) and d % 128 == 0 \
                and (not on_cpu or interpret):
            out = _sparse_decode_pallas(
                q[:, 0], k_pages, v_pages, block_table, ids, count, positions,
                layer, interpret=on_cpu or bool(interpret))
        else:
            out = _sparse_decode_xla(q[:, 0], k_pages, v_pages, block_table,
                                     ids, count, positions, layer)
        return out.reshape(s, 1, hq * d), k_pages, v_pages, kc
    nb = block_table.shape[1]
    start = jnp.arange(nb, dtype=jnp.int32) * bl
    rows = s * c * h_kv
    chosen = jnp.zeros((rows, nb), bool).at[
        jnp.arange(rows)[:, None], top.reshape(rows, -1)].set(True, mode="drop")
    chosen = chosen.reshape(s, c, h_kv, nb)
    every = (start[None, None, :] <= pos[..., None])[:, :, None, :]
    pick = jnp.moveaxis(jnp.where(sparse[..., None, None], chosen, every), 2, 1)
    from rocket_tpu.ops.sparse_prefill import sparse_prefill, sparse_prefill_supported

    itemsize = jnp.dtype(k_pages.dtype).itemsize
    on_cpu = _on_cpu()
    if sparse_prefill_supported(c, hq, h_kv, d, bl, nb * bl, itemsize) and (
            not on_cpu or interpret):
        out = sparse_prefill(q, k_pages, v_pages, block_table, positions, valid,
                             pick, layer, interpret=on_cpu or bool(interpret))
    else:
        out = _sparse_chunk_xla(q, k_pages, v_pages, block_table, positions,
                                valid, layer, pick)
    return out, k_pages, v_pages, kc
