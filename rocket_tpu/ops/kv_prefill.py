"""Chunk attention against the paged K/V pool — one flash-style Pallas TPU
kernel, ``kv_prefill``.

A prefill chunk of ``C`` query rows against a LONG block table (more
scores than ``paged_attention._CHUNK_SCORES_MAX``) walks the slot's live
context in tiles. In XLA (``paged_attention._attend_chunk_live``, the
portable path) every tile gathers its pages to a contiguous context and
writes its float32 ``(S, Hkv, G, C, tile)`` scores, probabilities and
accumulator to HBM and reads them back: at the codeagent cell's widths
(48 query heads over 8 K/V heads of 128, chunks of 1,024 rows) 403 MB a
2,048-row tile. Here they never leave VMEM: only ``q``, the live pages and
the output cross HBM.

Grid = (slots, head blocks): a head block is ``hb`` query heads of ONE
K/V head (all of its group, or a part of it). Inside a grid step the
kernel loops over the slot's LIVE key tiles, ``cdiv(positions[s] +
valid[s], block_kv)`` of them, a traced count, so one compiled kernel
serves every chunk of every prompt and a dead tile costs nothing. The
pool stays in HBM in its stored ``(L, NB, BL, Hkv*D)`` layout: a tile is
``block_kv / BL`` pages of the K/V head's ``D`` lanes, copied by
``make_async_copy`` from where the prefetched block table says they lie
into one of two VMEM buffers while the other is folded (as
``paged_attention._decode_kernel`` does). Tiles wholly before the chunk
(every query row sees all of them) fold with no mask; the tiles that
reach past ``positions[s]`` carry the causal diagonal and are masked, and
where the context ends in a tile's first half only that half is copied
and folded.

Same precision at every point as the loop: scores ``q . k`` in float32
scaled by ``1/sqrt(D)``, float32 running maximum, sum and accumulator,
probabilities cast to ``v``'s dtype for ``P . V``.

VMEM budget at the codeagent widths (1,024 query rows, 6 heads of 128 a
step, 1,024-row tiles, bfloat16): q and out blocks, double-buffered, 6 MB;
the K and V tile buffers 1 MB; float32 scratch (maximum and sum lane-
broadcast, accumulator) 9 MB; a head's score tile, probabilities and mask
some 14 MB. ``_VMEM_LIMIT`` asks for 48 MB of the core's 128 MiB (the
default of 16 is too little).

Inference only (no custom VJP — serving never differentiates).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocket_tpu.ops import paged_attention as _paged

__all__ = ["kv_prefill", "kv_prefill_supported"]

_NEG_INF = -1e30
_LANES = 128

#: Most query rows a call may bring: the whole chunk is ONE query tile, so
#: a K/V tile is copied once for every row of it; the float32 score tile
#: ``(rows, block_kv)``, statistics and accumulator grow with it.
_Q_ROWS_MAX = 1024
#: Rows of context a tile holds (fewer where the table is shorter or no
#: multiple of it), and the most lanes of query heads a grid step holds
#: (``hb * D``): 6 heads of 128 at the codeagent widths, 4 of 256 at the
#: longchat widths. On a v5e (``benchmark/tools/kv_prefill_probe``, a
#: chunk of 1,024 rows against a table of 16,384; PERF.md §6) a call
#: over 2,048 / 8,192 / 15,360 live rows took 421 / 1,594 / 2,958 us at
#: codeagent's 6 heads x 1,024 rows, 452 / 1,654 / 3,052 at 3 x 1,024,
#: 475 / 1,737 / 3,220 at 1 x 1,024 and 827 / 3,170 / 5,910 at 6 x 512,
#: where the XLA loop took 1,766 / 6,901 / 13,806 and the causal floor is
#: 196 / 981 / 1,897; longchat's 4 x 1,024 beat 2 x 1,024 and 4 x 512 by
#: 4-16 %.
_TILE_ROWS = 1024
_HEAD_LANES = 1024
#: The scoped VMEM the kernel asks for (module docstring).
_VMEM_LIMIT = 48 << 20


def _heads_block(g: int, d: int) -> int:
    """Query heads of a grid step: the largest divisor of the group ``g``
    whose lanes stay within ``_HEAD_LANES`` (0: there is none)."""
    for hb in range(g, 0, -1):
        if g % hb == 0 and hb * d <= _HEAD_LANES:
            return hb
    return 0


def _tile_rows(block_len: int, max_len: int) -> int:
    """Key tile: whole pages, a divisor of the table's length, at most
    ``_TILE_ROWS`` rows (at least one page)."""
    rows = max(block_len, _TILE_ROWS - _TILE_ROWS % block_len)
    while rows > block_len and (max_len % rows or rows % block_len):
        rows -= block_len
    return rows


def kv_prefill_supported(c: int, hq: int, h_kv: int, d: int, block_len: int,
                         max_len: int, itemsize: int) -> bool:
    """Shape gate of the kernel: a K/V head's ``D`` lanes are whole
    128-lane tiles (a page's copy slices the pool's lane axis there), the
    pages and the chunk whole sublane tiles, the chunk at most
    ``_Q_ROWS_MAX`` rows, the table a whole number of pages and of tiles.
    ``tests/test_tpu_compile.py`` compiles the kernel for a v5e at the
    codeagent and longchat cells' widths."""
    sub = _paged._SUBLANE.get(itemsize, 8)
    return (
        c % sub == 0 and 1 < c <= _Q_ROWS_MAX
        and d % _LANES == 0 and h_kv > 0 and hq % h_kv == 0
        and block_len % sub == 0 and max_len % block_len == 0
        and _heads_block(hq // h_kv, d) > 0
    )


def _prefill_kernel(layer_ref, table_ref, pos_ref, valid_ref, q_ref, k_hbm,
                    v_hbm, o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref,
                    *, block_kv, bl, mb, hb, blocks, d, scale):
    """One (slot, head block) step; see the module docstring. ``q_ref`` /
    ``o_ref`` (C, hb * D); ``k_buf`` / ``v_buf`` (2, block_kv, D) in the
    pool's dtype; scratch ``m``, ``l`` (hb, C, 128) lane-broadcast and
    ``acc`` (hb, C, D), float32. All ops stay 2D per head."""
    s = pl.program_id(0)
    lane = (pl.program_id(1) // blocks) * d           # the K/V head's lanes
    layer = layer_ref[0]
    pos = pos_ref[s]
    c = q_ref.shape[0]
    n_ctx = pos + jnp.maximum(valid_ref[s], 1)        # keys any real row sees
    n_tiles = pl.cdiv(n_ctx, block_kv)
    n_plain = (pos + 1) // block_kv     # tiles every row sees whole: no mask
    pages = block_kv // bl
    half = block_kv // 2 if pages % 2 == 0 else 0

    def copies(t, buf):
        """The page copies of tile ``t`` into buffer ``buf``, each with
        the condition under which it is live (None: always)."""
        out = []
        for p in range(pages):
            page = table_ref[s * mb + jnp.minimum(t * pages + p, mb - 1)]
            live = None if not half or p < pages // 2 else \
                n_ctx - t * block_kv > half
            for n, (hbm, vmem) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                out.append((live, pltpu.make_async_copy(
                    hbm.at[layer, page, :, pl.ds(lane, d)],
                    vmem.at[buf, pl.ds(p * bl, bl)],
                    sems.at[buf, n],
                )))
        return out

    def run(t, buf, what):
        for live, copy in copies(t, buf):
            op = getattr(copy, what)
            if live is None:
                op()
            else:
                pl.when(live)(op)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    run(0, 0, "start")

    def fold(buf, n, base, masked):
        """Fold the first ``n`` rows of buffer ``buf`` (keys ``base ..``)."""
        k = k_buf[buf, :n, :]
        v = v_buf[buf, :n, :]
        if masked:
            # Key base + col against query pos + row.
            seen = (
                jax.lax.broadcasted_iota(jnp.int32, (c, n), 1)
                - jax.lax.broadcasted_iota(jnp.int32, (c, n), 0)
            ) <= pos - base

        def head(h, carry):
            # A loop, not an unrolled one: each head's body costs the
            # compiler some 3 s, the run nothing measurable.
            q = q_ref[:, pl.ds(pl.multiple_of(h * d, _LANES), d)]
            s_ij = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                   # (C, n) f32
            if masked:
                s_ij = jnp.where(seen, s_ij, _NEG_INF)
            m_prev = m_ref[h, :, 0:1]                   # (C, 1)
            l_prev = l_ref[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s_ij, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # Key 0 is seen by every row, so after tile 0 the maximum is
            # a real score and a masked column's weight is exactly 0.
            p = jnp.exp(s_ij - m_new)
            m_ref[h] = jnp.broadcast_to(m_new, (c, _LANES))
            l_ref[h] = jnp.broadcast_to(
                l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                (c, _LANES),
            )
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32,
            )
            return carry

        jax.lax.fori_loop(0, hb, head, None)

    def tile(masked):
        def body(t, carry):
            buf = t % 2

            @pl.when(t + 1 < n_tiles)
            def _next():
                run(t + 1, 1 - buf, "start")

            run(t, buf, "wait")
            base = t * block_kv
            if masked and half:
                # Where the context ends in the tile's first half only that
                # half was copied: fold it alone.
                live = n_ctx - base
                pl.when(live > half)(lambda: fold(buf, block_kv, base, True))
                pl.when(live <= half)(lambda: fold(buf, half, base, True))
            else:
                fold(buf, block_kv, base, masked)
            return carry
        return body

    jax.lax.fori_loop(0, n_plain, tile(False), None)
    jax.lax.fori_loop(n_plain, n_tiles, tile(True), None)
    for h in range(hb):
        o_ref[:, h * d:(h + 1) * d] = (
            acc_ref[h] / l_ref[h, :, 0:1]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_kv", "heads", "interpret")
)
def kv_prefill(q, k_pages, v_pages, block_table, positions, valid, layer=0,
               *, block_kv: Optional[int] = None, heads: Optional[int] = None,
               interpret: bool = False):
    """Causal GQA attention of one chunk a slot against the paged pool:
    ``q`` (S, C, Hq, D) rotated; ``k_pages`` / ``v_pages`` (L, NB, BL,
    Hkv*D) with the chunk's own rows already written
    (``paged_attention.write_kv_pages``); ``block_table`` (S, MB) int32;
    ``positions`` / ``valid`` (S,) int32; ``layer`` an int or a traced
    scalar. Query row ``i`` of slot ``s`` sees key positions ``<=
    positions[s] + i``; query head ``j`` reads K/V head ``j // (Hq /
    Hkv)``. Returns (S, C, Hq * D) in ``q``'s dtype. Pages past
    ``positions[s] + valid[s]`` rounded up to a tile are never read; padded
    query rows (``i >= valid[s]``) give finite garbage the callers ignore.

    ``block_kv`` / ``heads`` (the key tile and the query heads of a grid
    step) default to what the shapes give; shapes must pass
    :func:`kv_prefill_supported`. Jitted, so the layers of a Python-loop
    model share ONE traced and lowered body, and named: a device trace
    shows ``kv_prefill`` custom-calls."""
    s, c, hq, d = q.shape
    _, _, bl, lanes = k_pages.shape
    h_kv = lanes // d
    g = hq // h_kv
    mb = block_table.shape[1]
    block_kv = block_kv or _tile_rows(bl, mb * bl)
    hb = heads or _heads_block(g, d)
    if block_kv % bl or (mb * bl) % block_kv or g % hb:
        raise ValueError(
            f"kv_prefill: block_kv={block_kv} must be whole pages of {bl} "
            f"rows dividing the table's {mb * bl}, heads={hb} divide the "
            f"group of {g}"
        )

    def q_map(i, j, *prefetched):
        del prefetched
        return (i, 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s, hq // hb),
        in_specs=[
            pl.BlockSpec((None, c, hb * d), q_map),
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
            _prefill_kernel, block_kv=block_kv, bl=bl, mb=mb, hb=hb,
            blocks=g // hb, d=d, scale=1.0 / math.sqrt(d),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, c, hq * d), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="kv_prefill",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_table.reshape(-1).astype(jnp.int32),
      jnp.asarray(positions, jnp.int32), jnp.asarray(valid, jnp.int32),
      q.reshape(s, c, hq * d), k_pages, v_pages)
