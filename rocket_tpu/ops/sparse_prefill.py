"""Block-sparse chunk attention against the paged K/V pool — one
flash-style Pallas TPU kernel, ``sparse_prefill``.

``ops/kv_prefill.py``'s kernel with a (row, page) mask: each query row of a
prefill chunk attends only the pages its K/V head picked
(``paged_attention.select_pages``; every page at or before it below
``dense_len``), at or before its own position. Grid = (slots, head blocks),
a head block ``hb`` query heads of ONE K/V head; inside a step the kernel
walks the slot's live key tiles as ``kv_prefill`` does, but a page that no
row of the chunk picked is never copied, and a tile none of whose pages
any row picked is not folded: the chunk is one query tile, so what is
skipped is every (chunk, page) pair no row selected. The picks come in as
a ``(pages, C)`` mask a K/V head (block-major, the chunk's rows on the
lanes) and, prefetched beside the table, one flag a page (any row picked
it). A tile's ``(C, tile)`` mask is the MXU's product of the tile's
``(pages, C)`` rows of the picks with a ``(pages, tile)`` page-to-row
spread.

Same precision at every point as the XLA walk
(``paged_attention._sparse_chunk_xla``): scores ``q . k`` in float32 scaled
by ``1/sqrt(D)``, float32 running maximum, sum and accumulator,
probabilities cast to ``v``'s dtype for ``P . V``.

Inference only (no custom VJP — serving never differentiates).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocket_tpu.ops import kv_prefill as _kv

__all__ = ["sparse_prefill", "sparse_prefill_supported"]

_NEG_INF = -1e30
_LANES = 128


def sparse_prefill_supported(c: int, hq: int, h_kv: int, d: int, block_len: int,
                             max_len: int, itemsize: int) -> bool:
    """Shape gate: ``kv_prefill``'s, and a tile's pages a whole sublane
    tile of the bfloat16 mask (16 rows) and the chunk whole lane tiles."""
    if not _kv.kv_prefill_supported(c, hq, h_kv, d, block_len, max_len, itemsize):
        return False
    pages = _kv._tile_rows(block_len, max_len) // block_len
    return pages % 16 == 0 and c % _LANES == 0


def _kernel(layer_ref, table_ref, pos_ref, valid_ref, live_ref, q_ref, pick_ref,
            k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref,
            *, block_kv, bl, mb, hb, blocks, h_kv, d, scale):
    """One (slot, head block) step; see the module docstring. ``pick_ref``
    (MB, C) the K/V head's picks; ``live_ref`` (prefetched) one flag a
    page."""
    s = pl.program_id(0)
    head = pl.program_id(1) // blocks                 # the K/V head
    lane = head * d
    layer = layer_ref[0]
    pos = pos_ref[s]
    c = q_ref.shape[0]
    n_ctx = pos + jnp.maximum(valid_ref[s], 1)
    n_tiles = pl.cdiv(n_ctx, block_kv)
    pages = block_kv // bl

    def page_live(t, p):
        index = jnp.minimum(t * pages + p, mb - 1)
        return (live_ref[(s * h_kv + head) * mb + index] > 0) & (
            (t * pages + p) * bl < n_ctx)

    def tile_live(t):
        live = page_live(t, 0)
        for p in range(1, pages):
            live = live | page_live(t, p)
        return live

    def copies(t, buf):
        out = []
        for p in range(pages):
            page = table_ref[s * mb + jnp.minimum(t * pages + p, mb - 1)]
            for n, (hbm, vmem) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                out.append((page_live(t, p), pltpu.make_async_copy(
                    hbm.at[layer, page, :, pl.ds(lane, d)],
                    vmem.at[buf, pl.ds(p * bl, bl)],
                    sems.at[buf, n],
                )))
        return out

    def run(t, buf, what):
        for live, copy in copies(t, buf):
            pl.when(live)(getattr(copy, what))

    @pl.when((s == 0) & (pl.program_id(1) == 0))
    def _clear():
        # Rows no copy has written must hold finite values: a masked
        # column's weight is exactly 0, and 0 x garbage may be NaN.
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_tiles > 0)
    def _first():
        run(0, 0, "start")

    def fold(buf, base, t):
        k = k_buf[buf]
        v = v_buf[buf]
        # The tile's picks spread from pages to rows: (C, block_kv).
        start = pl.multiple_of(t * pages, pages)
        rows = pick_ref[pl.ds(start, pages), :]                       # (pages, C)
        page_of = jax.lax.broadcasted_iota(jnp.int32, (pages, block_kv), 0) * bl
        col = jax.lax.broadcasted_iota(jnp.int32, (pages, block_kv), 1)
        spread = ((col >= page_of) & (col < page_of + bl)).astype(rows.dtype)
        chosen = jax.lax.dot_general(
            rows, spread, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) > 0.5
        seen = chosen & ((
            jax.lax.broadcasted_iota(jnp.int32, (c, block_kv), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (c, block_kv), 0)
        ) <= pos - base)

        def one(h, carry):
            q = q_ref[:, pl.ds(pl.multiple_of(h * d, _LANES), d)]
            s_ij = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            s_ij = jnp.where(seen, s_ij, _NEG_INF)
            m_prev = m_ref[h, :, 0:1]
            l_prev = l_ref[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s_ij, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(seen, jnp.exp(s_ij - m_new), 0.0)
            m_ref[h] = jnp.broadcast_to(m_new, (c, _LANES))
            l_ref[h] = jnp.broadcast_to(
                l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), (c, _LANES))
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, hb, one, None)

    def tile(t, carry):
        buf = t % 2

        @pl.when(t + 1 < n_tiles)
        def _next():
            run(t + 1, 1 - buf, "start")

        run(t, buf, "wait")
        pl.when(tile_live(t))(lambda: fold(buf, t * block_kv, t))
        return carry

    jax.lax.fori_loop(0, n_tiles, tile, None)
    for h in range(hb):
        o_ref[:, h * d:(h + 1) * d] = (
            acc_ref[h] / jnp.maximum(l_ref[h, :, 0:1], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_prefill(q, k_pages, v_pages, block_table, positions, valid, pick,
                   layer=0, *, interpret: bool = False):
    """Block-sparse GQA attention of one chunk a slot against the paged
    pool: ``q`` (S, C, Hq, D); ``k_pages`` / ``v_pages`` (L, NB, BL, Hkv*D)
    with the chunk's own rows already written; ``block_table`` (S, MB);
    ``positions`` / ``valid`` (S,); ``pick`` (S, Hkv, C, MB) bool — row
    ``i`` of K/V head ``g`` attends the pages it picked, keys at or before
    ``positions[s] + i``. Returns (S, C, Hq * D) in ``q``'s dtype; padded
    query rows give finite garbage. Shapes must pass
    :func:`sparse_prefill_supported`."""
    s, c, hq, d = q.shape
    _, _, bl, lanes = k_pages.shape
    h_kv = lanes // d
    g = hq // h_kv
    mb = block_table.shape[1]
    block_kv = _kv._tile_rows(bl, mb * bl)
    hb = _kv._heads_block(g, d)
    picks = jnp.swapaxes(pick, 2, 3).astype(jnp.bfloat16)          # (S, Hkv, MB, C)
    live = jnp.any(pick, axis=2).astype(jnp.int32)                 # (S, Hkv, MB)

    def q_map(i, j, *prefetched):
        del prefetched
        return (i, 0, j)

    def pick_map(i, j, *prefetched):
        del prefetched
        return (i, j // (g // hb), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s, hq // hb),
        in_specs=[
            pl.BlockSpec((None, c, hb * d), q_map),
            pl.BlockSpec((None, None, mb, c), pick_map),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((None, c, hb * d), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, block_kv, d), k_pages.dtype),
            pltpu.VMEM((2, block_kv, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hb, c, _LANES), jnp.float32),   # running max
            pltpu.VMEM((hb, c, _LANES), jnp.float32),   # running denom
            pltpu.VMEM((hb, c, d), jnp.float32),        # unnormalized out
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, block_kv=block_kv, bl=bl, mb=mb, hb=hb, blocks=g // hb,
            h_kv=h_kv, d=d, scale=1.0 / math.sqrt(d),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, c, hq * d), q.dtype),
        # One step after another: the tile buffers are cleared at the first.
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_kv._VMEM_LIMIT,
        ),
        interpret=interpret,
        name="sparse_prefill",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_table.reshape(-1).astype(jnp.int32),
      jnp.asarray(positions, jnp.int32), jnp.asarray(valid, jnp.int32),
      live.reshape(-1), q.reshape(s, c, hq * d), picks, k_pages, v_pages)
