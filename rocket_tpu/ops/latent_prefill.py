"""Latent (MLA) prefill attention — one flash-style Pallas TPU kernel.

A prefill chunk of ``nn.attention.LatentAttention`` attends NON-absorbed:
the slot's cached latent rows ``[c_kv | k_rope | 0]`` are up-projected to
``k_nope | v`` per head, scored against ``q_nope | q_rope`` and folded into
a running softmax. In XLA (``LatentAttention._attend_chunk``, the portable
path) every step of that loop writes its float32 scores, probabilities,
up-projected tile and accumulator to HBM and reads them back: at the docqa
cell's widths some 0.7-1 GB a step, 1.16 ms where the MXU needs 0.2
(PERF.md, PR 38). Here they never leave VMEM: only ``q``, the latent rows,
``W_ukv`` and the output cross HBM.

Grid = (slots, head blocks, key tiles); the key tiles are the reduction.
One grid step takes ONE tile of ``block_kv`` latent rows and, per head of
its block, up-projects it on the MXU (``rows[tile, rank] . W_ukv[rank,
nope + v]``), makes the two score products (contraction ``nope`` and
``rope``), masks by position, and folds ``P . V`` into float32 scratch
(running maximum, sum and accumulator, as the loop's carry). Same
precision at every point as the loop.

**The live context alone.** ``positions`` rides in as a prefetched
scalar; slot ``s`` has ``cdiv(positions[s] + C, block_kv)`` live tiles, a
traced value, so one compiled kernel serves every chunk of every prompt.
The rows' index map clamps a dead tile to the last live one — the
pipeline copies nothing for a block index that does not change — and the
body is under ``pl.when``: a dead tile costs a grid step's fixed cost and
its rows are never read.

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

__all__ = ["mla_prefill", "mla_prefill_supported"]

_NEG_INF = -1e30
_LANES = 128

#: Most query rows a call may bring: the whole chunk is ONE query tile
#: (the up-projected key tile is made once and used by every row), and the
#: float32 score tile ``(rows, block_kv)``, the statistics and the
#: accumulator of a head block grow with it.
_Q_ROWS_MAX = 512
#: Rows of context a grid step folds in (fewer where the table is shorter
#: or no multiple of it), and the heads it holds. On a v5e at the docqa
#: cell's widths (512 query rows, 128 heads of 128 + 64 / 128 over a
#: 512-lane latent, bfloat16, a table of 8192 rows; ``benchmark/tools/
#: mla_prefill_probe``, PERF.md, PR 38) a call over 512 / 2048 / 6144
#: live rows took 420 / 1147 / 3212 us at 4 heads x 1024 rows, 436 / 1368
#: / 3852 at 4 x 512, 391 / 1273 / 3627 at 8 x 512 and 476 / 1184 / 3315
#: at 2 x 1024, where the MXU's part is 218 a 512 rows and the XLA loop
#: took 1307 / 4872 / 14413. A dead tile's grid step costs 0.35 us and a
#: head's statistics cost the same whatever the tile, so a taller tile
#: wins twice; the half-tile fold keeps it from losing where a context
#: ends in a tile's first half. 8 heads x 1024 rows (twice the code, 34
#: MB of VMEM) was 6 % faster than 4 x 1024 before the half-tile fold and
#: 18 % slower in the engine with it: not taken.
_TILE_ROWS = 1024
_HEADS = 4
#: The scoped VMEM the kernel asks for. At those widths the q, rows,
#: ``W_ukv`` and out blocks, double-buffered, are 7.1 MB, the float32
#: scratch 3 MB, a head's score tile, probabilities and up-projected
#: rows 6 MB more (a v5e core has 128 MiB; the default of 16 is too
#: little).
_VMEM_LIMIT = 32 << 20


def _heads_block(h: int, rope: int) -> int:
    """Heads of a grid step: at most ``_HEADS``, a divisor of ``h`` whose
    ``q_rope`` block is whole 128-lane tiles (0: there is none)."""
    for hb in range(min(_HEADS, h), 0, -1):
        if h % hb == 0 and (hb * rope) % _LANES == 0:
            return hb
    return 0


def mla_prefill_supported(c: int, h: int, rank: int, nope: int, rope: int,
                          v_dim: int, max_len: int, itemsize: int) -> bool:
    """Shape gate of the kernel: every lane slice it takes is whole
    128-lane tiles (the latent, ``k_nope`` and ``v`` of a head, a key
    tile's scores), the rotary part a 64-lane half tile, the chunk a
    multiple of the dtype's sublane tile and at most ``_Q_ROWS_MAX`` rows.
    ``tests/test_tpu_compile.py`` compiles it for a v5e at the docqa
    cell's widths."""
    sub = _paged._SUBLANE.get(itemsize, 8)
    return (
        c % sub == 0 and c <= _Q_ROWS_MAX
        and rank % _LANES == 0 and nope % _LANES == 0 and v_dim % _LANES == 0
        and rope % 64 == 0
        and max_len % _LANES == 0
        and _heads_block(h, rope) > 0
    )


def _prefill_kernel(pos_ref, qn_ref, qr_ref, rows_ref, w_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, block_kv, hb, rank, nope, rope,
                    v_dim, scale):
    """One (slot, head block, key tile) step; see the module docstring.
    ``qn_ref`` (C, hb * nope), ``qr_ref`` (C, hb * rope), ``rows_ref``
    (block_kv, lanes), ``w_ref`` (rank, hb * (nope + v)), ``o_ref``
    (C, hb * v); scratch ``m``, ``l`` (hb, C, 128) lane-broadcast and
    ``acc`` (hb, C, v), float32. All ops stay 2D per head."""
    s, j = pl.program_id(0), pl.program_id(2)
    c = qn_ref.shape[0]
    pos = pos_ref[s]
    nt = nope + v_dim

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(n):
        """Fold the tile's first ``n`` rows in."""
        c_kv = rows_ref[:n, :rank]
        k_rope = rows_ref[:n, rank:rank + rope]
        # Key j * block_kv + col against query pos + row.
        seen = (
            jax.lax.broadcasted_iota(jnp.int32, (c, n), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (c, n), 0)
        ) <= pos - j * block_kv
        nt_dims = (((1,), (1,)), ((), ()))
        for h in range(hb):
            kv = jnp.dot(
                c_kv, w_ref[:, h * nt:(h + 1) * nt],
                preferred_element_type=jnp.float32,
            ).astype(c_kv.dtype)                        # (n, nope + v)
            s_ij = (
                jax.lax.dot_general(
                    qn_ref[:, h * nope:(h + 1) * nope], kv[:, :nope], nt_dims,
                    preferred_element_type=jnp.float32)
                + jax.lax.dot_general(
                    qr_ref[:, h * rope:(h + 1) * rope], k_rope, nt_dims,
                    preferred_element_type=jnp.float32)
            ) * scale                                   # (C, n) f32
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
                p.astype(kv.dtype), kv[:, nope:],
                preferred_element_type=jnp.float32,
            )

    # Row i sees keys <= pos + i, so the furthest row sees the tile's first
    # ``live`` rows. A tall tile pays a head's statistics (two reductions
    # across lanes, the accumulator's rescale) once for twice the keys;
    # where the context ends in its first half only that half is folded.
    live = pos + c - j * block_kv
    half = block_kv // 2
    if half % _LANES:
        pl.when(live > 0)(lambda: fold(block_kv))
    else:
        pl.when(live > half)(lambda: fold(block_kv))
        pl.when((live > 0) & (live <= half))(lambda: fold(half))

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        for h in range(hb):
            o_ref[:, h * v_dim:(h + 1) * v_dim] = (
                acc_ref[h] / l_ref[h, :, 0:1]
            ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_kv", "heads", "interpret")
)
def mla_prefill(q_nope, q_rope, rows, w_ukv, positions, *, scale: float,
                block_kv: Optional[int] = None, heads: Optional[int] = None,
                interpret: bool = False):
    """Non-absorbed causal attention of one chunk a slot over the slot's
    latent rows: ``q_nope`` (S, C, H, nope), ``q_rope`` (S, C, H, rope)
    rotated; ``rows`` (S, T, lanes) the slot's table gathered to a dense
    context, row ``t`` its global position ``t``, ``[c_kv | k_rope | ..]``
    (the chunk's own rows already in it); ``w_ukv`` (rank, H, nope + v);
    ``positions`` (S,) int32 — query row ``i`` of slot ``s`` sees key
    positions ``<= positions[s] + i``. Returns (S, C, H * v) in ``q``'s
    dtype. Rows past ``positions[s] + C`` rounded up to a tile are never
    read.

    ``block_kv`` / ``heads`` (the key tile and the heads of a grid step)
    default to what the shapes give; shapes must pass
    :func:`mla_prefill_supported`. Jitted, so the layers of a Python-loop
    model share ONE traced and lowered body, and named: a device trace
    shows ``mla_prefill`` custom-calls."""
    s, c, h, nope = q_nope.shape
    rope = q_rope.shape[-1]
    rank = w_ukv.shape[0]
    v_dim = w_ukv.shape[2] - nope
    t, lanes = rows.shape[1], rows.shape[2]
    block_kv = block_kv or math.gcd(t, _TILE_ROWS)
    hb = heads or _heads_block(h, rope)
    if t % block_kv or h % hb:
        raise ValueError(
            f"mla_prefill: block_kv={block_kv} must divide the context "
            f"{t} and heads={hb} the head count {h}"
        )
    nk = t // block_kv

    def q_map(i, g, j, pos_ref):
        return (i, 0, g)

    def rows_map(i, g, j, pos_ref):
        # A dead tile names the last live one: no copy for it.
        return (i, jnp.minimum(j, (pos_ref[i] + c - 1) // block_kv), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s, h // hb, nk),
        in_specs=[
            pl.BlockSpec((None, c, hb * nope), q_map),
            pl.BlockSpec((None, c, hb * rope), q_map),
            pl.BlockSpec((None, block_kv, lanes), rows_map),
            pl.BlockSpec((rank, hb * (nope + v_dim)),
                         lambda i, g, j, pos_ref: (0, g)),
        ],
        out_specs=pl.BlockSpec((None, c, hb * v_dim), q_map),
        scratch_shapes=[
            pltpu.VMEM((hb, c, _LANES), jnp.float32),   # running max
            pltpu.VMEM((hb, c, _LANES), jnp.float32),   # running denom
            pltpu.VMEM((hb, c, v_dim), jnp.float32),    # unnormalized out
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _prefill_kernel, block_kv=block_kv, hb=hb, rank=rank, nope=nope,
            rope=rope, v_dim=v_dim, scale=scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, c, h * v_dim), q_nope.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="mla_prefill",
    )(jnp.asarray(positions, jnp.int32),
      q_nope.reshape(s, c, h * nope), q_rope.reshape(s, c, h * rope),
      rows, w_ukv.reshape(rank, h * (nope + v_dim)))
