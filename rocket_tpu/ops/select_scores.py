"""The unit scores of block-sparse attention's page choice — one Pallas TPU
kernel, ``select_scores``.

``paged_attention.select_pages`` ranks the blocks a query row attends by
the scores of the compressed keys (``kc``, one unit every ``stride``
positions, the mean of ``kernel`` keys): per K/V head the softmax over the
complete units (``j * stride + kernel - 1 <= pos``) of ``q_h . Kc /
sqrt(D)``, summed over the group's query heads, -1 where a unit is
incomplete. In XLA (``paged_attention._unit_scores``, the portable path
and the specification) a loop over the group's heads re-reads every unit a
slot could hold once a head, one query row at a time against the MXU.
Here a grid step takes ALL of a group's heads for a block of query rows
and one (slot, K/V head), so each key tile is copied and multiplied once
a step, as one ``(heads x rows, D) . (tile, D)^T`` product.

Grid = (slots, K/V heads, blocks of query rows). Inside a step the kernel
walks the block's LIVE units only: those up to the last one complete for
its last row, a traced count (0 for a slot that does not run), in tiles of
``tile`` units copied out of ``kc`` where it lies (``(layers, slots,
units, Hkv * D)`` in HBM; the layer and each row's slot are prefetched
scalars) by double-buffered copies, as
``paged_attention._sparse_decode_kernel`` copies pages. The step's float32
logits stay in VMEM (``_LOGITS_BYTES`` bounds the rows a step takes), so
the keys are read ONCE: the walk keeps the logits and each (row, head)'s
running maximum; a second sweep over the kept logits turns them into
``exp(l - m_h)`` and sums them; a third adds ``exp(l - m_h) / Z_h`` up
over the heads into the ``(rows, units)`` output. The maximum and the sum
are kept a lane apart (``(rows x heads, 128)``) and reduced across lanes
once, not once a tile, and the tiles that every row of the block sees
whole are not masked. Units past the live ones read -1 without a copy.

Same precision as the loop: ``q`` and ``kc`` in their own dtypes, logits
``preferred_element_type`` float32, softmax statistics and the sum over
heads in float32 (heads added in the loop's order). Only the order of the
softmax's own sums differs.

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

__all__ = ["select_scores", "select_scores_supported"]

_NEG_INF = -1e30
_LANES = 128

#: Units a copy brings (fewer where a slot holds fewer, or no multiple).
_TILE_UNITS = 512
#: The most float32 logits a grid step keeps in VMEM: heads x rows x units
#: x 4 bytes. A decode row at the longdoc cell's widths (16 heads, 4,096
#: units) keeps 256 KB; a chunk's step 16 rows, 4 MB.
_LOGITS_BYTES = 4 << 20


def _rows_block(c: int, g: int, units: int) -> int:
    """Query rows of a grid step: a decode row alone (C = 1), else the
    largest power of two from 8 that divides ``C`` and whose logits fit
    ``_LOGITS_BYTES`` (0: none does)."""
    if c == 1:
        return 1 if g * units * 4 <= _LOGITS_BYTES else 0
    rows, best = 8, 0
    while c % rows == 0 and g * rows * units * 4 <= _LOGITS_BYTES:
        best, rows = rows, rows * 2
    return best


def _tile_units(units: int) -> int:
    """Units a tile: whole lane tiles, a divisor of ``units``, at most
    ``_TILE_UNITS``."""
    tile = min(_TILE_UNITS, units)
    while units % tile:
        tile -= _LANES
    return tile


def select_scores_supported(c: int, hq: int, h_kv: int, d: int, units: int) -> bool:
    """Shape gate: a K/V head's ``D`` lanes whole 128-lane tiles (a copy
    slices ``kc``'s lane axis there), the units whole lane tiles (the
    logits and the output keep them on the lanes) and a block of rows
    whose logits fit. ``tests/test_tpu_compile.py`` compiles the kernel
    for a v5e at the longdoc cell's widths."""
    return (
        d % _LANES == 0 and h_kv > 0 and hq % h_kv == 0
        and units % _LANES == 0 and _rows_block(c, hq // h_kv, units) > 0
    )


def _kernel(layer_ref, slot_ref, pos_ref, live_ref, q_ref, kc_hbm, o_ref,
            k_buf, sems, logit_ref, m_ref, l_ref, *, rows, g, d, tile,
            stride, size, scale):
    """One (slot, K/V head, block of rows) step; see the module docstring.
    ``q_ref`` (g * rows, D), head-major: row ``h * rows + r`` is head ``h``
    of query row ``r``; ``o_ref`` (rows, units); ``logit_ref`` (tiles, g *
    rows, tile) float32; ``m_ref`` / ``l_ref`` (g * rows, 128): the running
    maximum and sum of each (row, head) kept a lane apart, so that no tile
    reduces across lanes."""
    s, h, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    layer = layer_ref[0]
    slot = slot_ref[s]
    height = g * rows
    first = pos_ref[s] + b * rows                     # the block's first row

    def complete(p):
        """Units complete at position ``p``: ``j * stride + size - 1 <= p``."""
        return jnp.maximum((p + 1 + stride - size) // stride, 0)

    row = jax.lax.broadcasted_iota(jnp.int32, (height, 1), 0)
    row = row & (rows - 1) if rows > 1 else jnp.zeros_like(row)
    count = complete(first + row)                     # (g * rows, 1)
    n_units = jnp.minimum(live_ref[s], complete(first + rows - 1))
    n_tiles = pl.cdiv(n_units, tile)
    # Tiles every row of the block sees whole: no mask.
    n_plain = jnp.minimum(complete(first), n_units) // tile

    def copy(t, buf):
        return pltpu.make_async_copy(
            kc_hbm.at[layer, slot, pl.ds(t * tile, tile), pl.ds(h * d, d)],
            k_buf.at[buf],
            sems.at[buf],
        )

    def seen(t):
        """(g * rows, tile): unit ``t * tile + col`` is complete for the
        row."""
        col = jax.lax.broadcasted_iota(jnp.int32, (height, tile), 1)
        return col < count - t * tile

    def lanes(x, fold):
        """``fold`` of the tile's 128-lane columns, elementwise."""
        out = x[:, :_LANES]
        for k in range(1, tile // _LANES):
            out = fold(out, x[:, k * _LANES:(k + 1) * _LANES])
        return out

    o_ref[...] = jnp.full_like(o_ref, -1.0)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(n_tiles > 0)
    def _first():
        copy(0, 0).start()

    def score(masked):
        def body(t, carry):
            buf = t % 2

            @pl.when(t + 1 < n_tiles)
            def _next():
                copy(t + 1, 1 - buf).start()

            copy(t, buf).wait()
            x = jax.lax.dot_general(
                q_ref[...], k_buf[buf], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                     # (g * rows, tile)
            if masked:
                x = jnp.where(seen(t), x, _NEG_INF)
            logit_ref[t] = x
            m_ref[...] = jnp.maximum(m_ref[...], lanes(x, jnp.maximum))
            return carry
        return body

    jax.lax.fori_loop(0, n_plain, score(False), None)
    jax.lax.fori_loop(n_plain, n_tiles, score(True), None)
    m = jnp.max(m_ref[...], axis=1, keepdims=True)    # (g * rows, 1)

    def weigh(masked):
        def body(t, carry):
            e = jnp.exp(logit_ref[t] - m)
            if masked:
                e = jnp.where(seen(t), e, 0.0)
            logit_ref[t] = e
            l_ref[...] = l_ref[...] + lanes(e, jnp.add)
            return carry
        return body

    jax.lax.fori_loop(0, n_plain, weigh(False), None)
    jax.lax.fori_loop(n_plain, n_tiles, weigh(True), None)
    total = jnp.sum(l_ref[...], axis=1, keepdims=True)
    inv = jnp.where(total > 0, 1.0 / jnp.where(total > 0, total, 1.0), 0.0)

    def fold(masked):
        def body(t, carry):
            p = logit_ref[t] * inv
            acc = p[0:rows]
            for head in range(1, g):
                acc = acc + p[head * rows:(head + 1) * rows]
            if masked:
                acc = jnp.where(seen(t)[0:rows], acc, -1.0)
            o_ref[:, pl.ds(pl.multiple_of(t * tile, _LANES), tile)] = acc
            return carry
        return body

    jax.lax.fori_loop(0, n_plain, fold(False), None)
    jax.lax.fori_loop(n_plain, n_tiles, fold(True), None)


@functools.partial(
    jax.jit, static_argnames=("stride", "size", "tile", "rows", "interpret"))
def select_scores(q, kc, layer, slots, positions, valid, *, stride: int,
                  size: int, tile: Optional[int] = None,
                  rows: Optional[int] = None, interpret: bool = False):
    """Unit scores ``(S, C, Hkv, units)`` float32 of a chunk's query rows:
    ``q`` (S, C, Hq, D), row ``i`` of slot row ``s`` at position
    ``positions[s] + i``; ``kc`` (layers, max_slots, units, Hkv * D) read
    at ``(layer, slots[s])``; ``valid`` (S,) — a slot row with ``valid``
    0 does not run and reads -1 everywhere. Query head ``j`` scores
    against K/V head ``j // (Hq / Hkv)``; units of ``stride`` positions
    pooled over ``size``. ``tile`` (units a copy) and ``rows`` (query rows
    a grid step: 1 for a decode row, else a power of two from 8 dividing
    ``C``) default to what the shapes give; shapes must pass
    :func:`select_scores_supported`. Jitted
    and named: the layers share ONE lowered body, and a device trace
    shows ``select_scores`` custom-calls."""
    s, c, hq, d = q.shape
    _, _, units, lanes = kc.shape
    h_kv = lanes // d
    g = hq // h_kv
    rows = rows or _rows_block(c, g, units)
    tile = tile or _tile_units(units)
    blocky = rows == c == 1 or (rows >= 8 and rows & (rows - 1) == 0 and c % rows == 0)
    if not blocky or units % tile or tile % _LANES:
        raise ValueError(
            f"select_scores: rows={rows} is no block of {c} query rows (1 "
            f"for a decode row, else a power of two from 8 dividing it), or "
            f"tile={tile} is no divisor of the {units} units in whole lane "
            f"tiles")
    blocks = c // rows
    # (S, Hkv, blocks, g * rows, D), head-major inside a block.
    q_rows = q.reshape(s, blocks, rows, h_kv, g, d).transpose(0, 3, 1, 4, 2, 5)
    q_rows = q_rows.reshape(s, h_kv, blocks, g * rows, d)
    last = jnp.asarray(positions, jnp.int32) + (c - 1)
    live = jnp.clip((last - (size - 1)) // stride + 1, 0, units)
    live = jnp.where(jnp.asarray(valid) > 0, live, 0).astype(jnp.int32)

    def q_map(i, k, j, *prefetched):
        del prefetched
        return (i, k, j, 0, 0)

    def o_map(i, k, j, *prefetched):
        del prefetched
        return (i, k, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s, h_kv, blocks),
        in_specs=[pl.BlockSpec((None, None, None, g * rows, d), q_map),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((None, None, rows, units), o_map),
        scratch_shapes=[
            pltpu.VMEM((2, tile, d), kc.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((units // tile, g * rows, tile), jnp.float32),
            pltpu.VMEM((g * rows, _LANES), jnp.float32),   # running max, by lane
            pltpu.VMEM((g * rows, _LANES), jnp.float32),   # sum of exp, by lane
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, rows=rows, g=g, d=d, tile=tile, stride=stride, size=size,
            scale=1.0 / math.sqrt(d),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h_kv, c, units), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="select_scores",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(slots, jnp.int32), jnp.asarray(positions, jnp.int32), live,
      q_rows, kc)
    return jnp.moveaxis(out, 1, 2)
