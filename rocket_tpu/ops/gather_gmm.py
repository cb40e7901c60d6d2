"""Gather-GMM — grouped matmul with in-kernel token routing.

The round-5 dropless-MoE measurement (docs/performance.md "The dropless
removal attempt") found the sort-based dispatch losing NOT on the expert
matmuls (tuned megablox gmm runs within ~4% of dense per row) but on the
GLUE: the materialized ``x[sorted_token]`` row gather and the follow-up
scatter ran at the platform's ~30 GB/s random-row bandwidth and ate the
capacity-padding savings. This kernel is the structural answer the tuner
can now measure (tune kernel ``moe_gmm``, axis ``impl="fused"``): the
grouped matmul reads its lhs rows STRAIGHT from the unsorted token array
by index — each m-tile DMAs its ``tile_m`` routed rows from HBM into
VMEM scratch while the MXU works, so the (NK, D) sorted copy never
exists and the gather rides the kernel's own pipeline instead of a
separate bandwidth-bound pass.

Group layout contract (``padded_group_layout`` builds it): rows are
sorted by expert and each expert's segment is PADDED up to a multiple of
``tile_m``, so every m-tile belongs to exactly one expert — the rhs
block index is a scalar-prefetch lookup, no masked multi-group tiles.
Pad rows carry row id 0 (a real row — harmless: their outputs are never
gathered back). Static shapes throughout: the padded row count is the
worst case ``NK + E * tile_m`` rounded to ``tile_m``, data-dependent
group sizes are runtime VALUES.

Accumulation is fp32 in the dot (operand-dtype output), matching the
megablox gmm contract (RKT401). The backward runs the reference
composition (gather + grouped matmul, `nn/moe._grouped_matmul`) via
``jax.vjp`` — on TPU that is the tuned megablox path; the fused forward
is the candidate the tuner times. A fused backward (tgmm with in-kernel
scatter) is the noted follow-up.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "gather_gmm",
    "gather_gmm_supported",
    "padded_group_layout",
]


def _interpret_default() -> bool:
    return jax.devices()[0].platform == "cpu"


def gather_gmm_supported(k: int, n: int, tile_n: int) -> bool:
    """Shape gate for the fused kernel: the whole contraction dim rides
    in VMEM per tile (no k-tiling — MoE widths fit) and the rhs tiles
    the lane dim."""
    return k % 8 == 0 and n % tile_n == 0 and tile_n % 128 == 0


def padded_group_layout(counts, sorted_token, tile_m: int, nk: int,
                        sorted_expert=None):
    """Tile-aligned padded layout for ``gather_gmm``.

    ``counts`` (E,) int32 per-expert row counts summing to ``nk``;
    ``sorted_token`` (NK,) the source-row index of each sorted row;
    ``sorted_expert`` (NK,) each sorted row's expert id when the caller
    already has it (the MoE dispatch does — passing it skips a
    searchsorted over NK rows), else derived here.
    Returns ``(row_ids (M,), group_sizes (E,), padded_pos (NK,), m)``
    where ``M = m`` is the STATIC padded row count (every group padded
    to a ``tile_m`` multiple, worst case pre-allocated), ``group_sizes``
    are the padded per-expert counts with the final group inflated to
    cover the unused tail (every one of the ``M`` rows belongs to a
    group, all tile-aligned), and ``padded_pos`` maps sorted row ->
    padded row (the inverse gather after the matmuls).
    """
    e = counts.shape[0]
    m = ((nk + tile_m - 1) // tile_m + e) * tile_m  # static worst case
    padded = ((counts + tile_m - 1) // tile_m) * tile_m
    pofs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)[:-1]]
    )
    ofs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]]
    )
    if sorted_expert is None:
        sorted_expert = jnp.searchsorted(
            jnp.cumsum(counts), jnp.arange(nk, dtype=jnp.int32),
            side="right",
        ).astype(jnp.int32)
    rank = jnp.arange(nk, dtype=jnp.int32) - ofs[sorted_expert]
    padded_pos = pofs[sorted_expert] + rank
    row_ids = (
        jnp.zeros((m,), jnp.int32).at[padded_pos].set(
            sorted_token.astype(jnp.int32)
        )
    )
    # The unused tail joins the last group so all M rows are covered —
    # tile-aligned by construction (m and every padded count are).
    group_sizes = padded.astype(jnp.int32).at[e - 1].add(
        jnp.int32(m) - jnp.sum(padded).astype(jnp.int32)
    )
    return row_ids, group_sizes, padded_pos, m


def _expert_per_tile(group_sizes, tile_m: int, m: int):
    """(m // tile_m,) int32: which expert each m-tile computes."""
    e = group_sizes.shape[0]
    starts = jnp.arange(m // tile_m, dtype=jnp.int32) * tile_m
    return jnp.clip(
        jnp.searchsorted(jnp.cumsum(group_sizes), starts, side="right"),
        0, e - 1,
    ).astype(jnp.int32)


def _gather_gmm_kernel(ids_ref, ept_ref, x_ref, rhs_ref, o_ref,
                       lhs_ref, sems, *, tile_m):
    """One (m-tile, n-tile) grid step. At each new m-tile (j == 0) the
    tile's rows are DMA'd from the HBM-resident token array into VMEM
    scratch by index — a two-deep rolling pipeline so row r+1 is in
    flight while row r lands; n-tiles then reuse the gathered block."""
    del ept_ref  # consumed by the rhs BlockSpec index map
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _gather():
        def dma(r, slot):
            return pltpu.make_async_copy(
                x_ref.at[ids_ref[i * tile_m + r]],
                lhs_ref.at[r],
                sems.at[slot],
            )

        dma(0, 0).start()

        def body(r, _):
            @pl.when(r + 1 < tile_m)
            def _prefetch():
                dma(r + 1, (r + 1) % 2).start()

            dma(r, r % 2).wait()
            return 0

        jax.lax.fori_loop(0, tile_m, body, 0)

    o_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _run_gather_gmm(x, rhs, row_ids, expert_per_tile, *, tile_m, tile_n,
                    m, interpret):
    _, k = x.shape
    _, _, n_out = rhs.shape

    def rhs_map(i, j, ids_ref, ept_ref):
        del ids_ref
        return (ept_ref[i], 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // tile_m, n_out // tile_n),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),          # x stays in HBM
            pl.BlockSpec((1, k, tile_n), rhs_map),
        ],
        out_specs=pl.BlockSpec(
            (tile_m, tile_n), lambda i, j, ids, ept: (i, j)
        ),
        scratch_shapes=[
            pltpu.VMEM((tile_m, k), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_gather_gmm_kernel, tile_m=tile_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n_out), x.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(row_ids, expert_per_tile, x, rhs)


def gather_gmm(
    x,
    rhs,
    row_ids,
    group_sizes,
    *,
    tile_m: int = 512,
    tile_n: int = 512,
    interpret: Optional[bool] = None,
):
    """``out[r] = x[row_ids[r]] @ rhs[expert_of(r)]`` in one fused pallas
    program — the gather never materializes.

    ``x`` (N, K) the UNSORTED token rows (HBM-resident); ``rhs``
    (E, K, N_out) stacked expert weights; ``row_ids`` (M,) int32 source
    rows in group-sorted, tile-aligned order; ``group_sizes`` (E,) int32
    padded per-expert counts — every group a ``tile_m`` multiple,
    summing to M (:func:`padded_group_layout` builds both). Returns
    (M, N_out) in the operand dtype with fp32 accumulation.
    """
    m = int(row_ids.shape[0])
    _, k = x.shape
    e, k2, n_out = rhs.shape
    if k != k2:
        raise ValueError(f"gather_gmm: K mismatch {k} != {k2}")
    tile_m = min(int(tile_m), m)
    tile_n = min(int(tile_n), n_out)
    if m % tile_m or not gather_gmm_supported(k, n_out, tile_n):
        raise ValueError(
            f"gather_gmm: shape (M={m}, K={k}, N={n_out}) does not tile "
            f"(tile_m={tile_m}, tile_n={tile_n})"
        )
    if interpret is None:
        interpret = _interpret_default()
    ept = _expert_per_tile(group_sizes, tile_m, m)
    ids = row_ids.astype(jnp.int32)

    @jax.custom_vjp
    def fused(x, rhs):
        return _run_gather_gmm(
            x, rhs, ids, ept, tile_m=tile_m, tile_n=tile_n, m=m,
            interpret=interpret,
        )

    # Backward through the reference composition (explicit gather +
    # grouped matmul): gradients are the proven path's; the fused
    # forward is what the tuner times.
    def _reference(x, rhs):
        from rocket_tpu.nn.moe import _grouped_matmul

        return _grouped_matmul(jnp.take(x, ids, axis=0), rhs, group_sizes)

    def _fwd(x, rhs):
        return fused(x, rhs), (x, rhs)

    def _bwd(res, dy):
        x, rhs = res
        _, vjp = jax.vjp(_reference, x, rhs)
        return vjp(dy)

    fused.defvjp(_fwd, _bwd)
    return fused(x, rhs)
