"""Ring attention — sequence parallelism for long contexts.

The sequence axis is sharded over a mesh axis; each device holds a local
block of Q, K, V. K/V blocks rotate around the ring with ``ppermute`` (ICI
neighbor exchange — bandwidth-optimal, no all-gather), and each device
accumulates its Q-block's attention over every K/V block with the
flash-attention online-softmax recurrence, so the full (T, T) score matrix is
never materialized and memory stays O(T/n * T/n) per step.

This is the blockwise ring formulation (Liu et al.'s Ring Attention shape):
communication overlaps with the block computation under XLA's async
collective scheduling. Exposed both as a raw op (``ring_attention``) and via
``MultiHeadAttention``-compatible plumbing in the long-context example.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["ring_attention", "ring_attention_sharded"]

_NEG_BIG = -1e30  # mask value: large-negative, not -inf (NaN-safe recurrence)


def _block_attend(q, k, v, q_offset, kv_offset, causal, m, l, o):
    """One online-softmax accumulation step of q against a (k, v) block.

    q: (B, H, Tq, D); k/v: (B, H, Tk, D); m/l: (B, H, Tq); o like q.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        t_q, t_k = q.shape[-2], k.shape[-2]
        q_pos = q_offset + jnp.arange(t_q)
        kv_pos = kv_offset + jnp.arange(t_k)
        mask = q_pos[:, None] >= kv_pos[None, :]
        logits = jnp.where(mask, logits, _NEG_BIG)

    m_block = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m, m_block)
    correction = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None])
    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, o_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    vary_axes: tuple = (),
) -> jax.Array:
    """Per-shard body: local blocks (B, H, T_loc, D); call inside shard_map
    with the sequence axis sharded over ``axis_name``."""
    n = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    t_loc = q.shape[-2]

    b, h, _, d = q.shape
    m = jnp.full((b, h, t_loc), _NEG_BIG, jnp.float32)
    l = jnp.zeros((b, h, t_loc), jnp.float32)
    o = jnp.zeros((b, h, t_loc, d), jnp.float32)
    # The accumulators become device-varying after one loop step; mark the
    # initial constants as varying over the ring axis so the carry types
    # match (jax >= 0.8 vma checking).
    from rocket_tpu.parallel.collectives import pvary_compat

    axes = (axis_name,) + tuple(vary_axes)
    m, l, o = (pvary_compat(x, axes) for x in (m, l, o))

    q_offset = rank * t_loc
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        k_blk, v_blk, m, l, o = carry
        # After `step` rotations this device holds block (rank - step) mod n.
        kv_rank = (rank - step) % n
        kv_offset = kv_rank * t_loc
        m, l, o = _block_attend(q, k_blk, v_blk, q_offset, kv_offset, causal, m, l, o)
        # Rotate K/V to the next device; the final rotation is harmless and
        # keeps the loop shape uniform (XLA overlaps it with the epilogue).
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, o

    _, _, m, l, o = jax.lax.fori_loop(0, n, body, (k, v, m, l, o))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    seq_axis: str = "seq",
    data_axis: Optional[str] = "data",
    causal: bool = True,
) -> jax.Array:
    """Global-view entry: (B, H, T, D) arrays with T sharded over
    ``seq_axis`` (and batch optionally over ``data_axis``)."""
    batch = data_axis if (data_axis and data_axis in mesh.shape) else None
    spec = P(batch, None, seq_axis, None)
    # check_vma=False for the same reason as the pipeline shard_maps: the
    # ppermute rotation inside the fori_loop carry trips jax's
    # replication-rule table on some releases ("Scan carry ... mismatched
    # replication types"), and the out_specs already pin the replication
    # contract we rely on.
    fn = shard_map(
        functools.partial(
            ring_attention,
            axis_name=seq_axis,
            causal=causal,
            vary_axes=(batch,) if batch else (),
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
