"""Gated DeltaNet mixer (Qwen3-Next's linear-attention layer; the gated
delta rule of arXiv 2412.06464) with the MATRIX per-slot state the serving
engine carries beside its pages.

Per token ``t`` of hidden ``x_t``::

    [q, k, v, z] = in_proj_qkvz(x)        # Hk x dk, Hk x dk, Hv x dv, Hv x dv
    [b, a]       = in_proj_ba(x)          # Hv each
    [q | k | v]  = silu(conv1d([q | k | v]))   # depthwise, causal, d_conv taps
    beta         = sigmoid(b)
    g            = -exp(a_log) * softplus(a + dt_bias)        # float32
    q, k         = q / |q| * dk^-0.5, k / |k|                 # per head
    S_t          = exp(g_t) * S_{t-1}                         # per value head
    S_t          = S_t + k_t (x) (beta_t * (v_t - S_t^T k_t))
    o_t          = S_t^T q_t
    out          = out_proj(RMSNorm(o) * w * silu(z))         # per head

Each q/k head serves ``Hv / Hk`` value heads. **What a sequence carries**
from one call to the next is ``S`` — ``dk x dv`` (key x value) a value
head, **float32** — and the last ``d_conv - 1`` inputs of the convolution:
a fixed size whatever the context, where attention caches a row a token.
(A Mamba mixer's state is a vector a channel, ``nn/ssm.py``; this one is a
matrix a head: 2 MB a layer a slot at 32 heads of 128 x 128.)

Three entries, one rule (:func:`_advance` is the literal recurrence;
:func:`_wy_block` the same rule over a block of rows, in the chunked WY
form of the delta rule, the chunk kernel's body):

* :meth:`GatedDeltaNet.apply` — the whole sequence from a zero state;
* :meth:`GatedDeltaNet.apply_state` with ``C`` rows a slot — a prefill
  chunk that starts from the slot's carried ``(S, conv)`` with the first
  ``valid`` rows real; rows past ``valid`` advance nothing;
* the same with ``C = 1`` — the decode wave's one-token step over every
  slot; a slot with ``valid`` 0 keeps its state bitwise.

The serving state lives in two arrays indexed by SLOT (``serve/kv_pool.py``):
``S`` ``(state layers, max_slots, Hv, dk, dv)`` float32 and ``conv``
``(state layers, max_slots, (d_conv - 1) * channels)`` in the activation
dtype. Both are handed over whole with a ``layer`` coordinate and updated
in place under donation, like the pages.

The rule runs under two jitted functions whose names the profiler's events
carry, each ONE Pallas kernel on a TPU:

* ``gdn_step`` (a wave): the grid walks the RUNNING slots only (their ids
  compacted to the front of a prefetched list; the steps behind them name
  the last running slot again, which costs no copy), ``S`` of a slot and a
  group of heads is read once and written once where it lies
  (``input_output_aliases``); the update itself is elementwise on
  ``(dk, dv)`` tiles, the key and the query spread over lanes by the MXU.
* ``gdn_chunk`` (a chunk): the grid is (slot, value head, block of ``bt``
  rows); ``S`` stays in VMEM over a head's blocks and every block is
  :func:`_wy_block` — ``T = (I + tril(diag(beta) K K^T * decay, -1))^-1`` by
  doubling, ``U = T diag(beta) (V - decay K S)``, ``O = decay Q S + (Q K^T *
  decay) U``, ``S' = decay S + (decay K)^T U`` — matrix products the MXU
  takes, the state touched once a block. A block past ``valid`` costs
  nothing.

Elsewhere (the CPU, shapes the kernels do not take, and anything that is
differentiated) a ``lax.scan`` over tokens computes the same numbers.
"""

from __future__ import annotations

import functools
import math
import warnings
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocket_tpu.nn.layers import Dense, gated_rms_norm
from rocket_tpu.nn.module import Layer
from rocket_tpu.nn.ssm import causal_conv

__all__ = ["GatedDeltaNetConfig", "GatedDeltaNet", "gdn_chunk", "gdn_step",
           "gdn_kernel_supported"]

_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


@dataclass(frozen=True)
class GatedDeltaNetConfig:
    """Sizes of a Gated DeltaNet mixer: the published
    ``linear_num_key_heads``, ``linear_num_value_heads``,
    ``linear_key_head_dim``, ``linear_value_head_dim`` and
    ``linear_conv_kernel_dim``."""

    num_k_heads: int
    num_v_heads: int
    head_k_dim: int
    head_v_dim: int
    d_conv: int = 4

    @property
    def key_dim(self) -> int:
        return self.num_k_heads * self.head_k_dim

    @property
    def value_dim(self) -> int:
        return self.num_v_heads * self.head_v_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``[q | k | v]``."""
        return 2 * self.key_dim + self.value_dim

    def state_shapes(self, dtype) -> tuple:
        """What ONE slot carries through one layer: ``((shape, dtype),
        ...)`` — ``S`` in float32, the convolution's tail in ``dtype``."""
        return (
            ((self.num_v_heads, self.head_k_dim, self.head_v_dim), "float32"),
            (((self.d_conv - 1) * self.conv_dim,), str(jnp.dtype(dtype))),
        )

    def make_mixer(self, features: int, *, norm_eps: float = 1e-6):
        return GatedDeltaNet(features, self, norm_eps=norm_eps)


# -- the rule -----------------------------------------------------------------

def _advance(s, q, k, v, g, beta):
    """One token of the rule, for any leading shape: ``s`` (..., dk, dv)
    float32; ``q``, ``k`` (..., dk); ``v`` (..., dv); ``g``, ``beta``
    (...). Returns ``(s', o (..., dv))``."""
    s = jnp.exp(g)[..., None, None] * s
    r = jnp.einsum("...kv,...k->...v", s, k, precision=_HIGHEST)
    u = beta[..., None] * (v - r)
    s = s + k[..., :, None] * u[..., None, :]
    return s, jnp.einsum("...kv,...k->...v", s, q, precision=_HIGHEST)


def _cols(x, n: int):
    """The first ``n`` lanes of a column spread over lanes (``(rows,
    lanes)`` with every lane alike)."""
    return x[..., :n]


def _wy_block(s0, q, k, v, g_col, g_row, b_col):
    """``bt`` successive tokens of the rule at once (the chunked WY form):
    ``s0`` (dk, dv) float32; ``q``, ``k`` (bt, dk); ``v`` (bt, dv);
    ``g_col`` (bt, lanes) and ``g_row`` (1, bt) the running sum of ``g``
    over the block's rows, as a column spread over the lanes and as a row;
    ``b_col`` (bt, lanes) ``beta``. Returns ``(o (bt, dv) float32, s1)``. Two-
    dimensional operations only: the body of the chunk kernel as it is.
    Every product with a float32 operand (``S``, ``T``, ``U``) runs at the
    highest precision; ``K K^T`` and ``Q K^T`` take the activations as they
    come (exact in bfloat16). (With every operand rounded to bfloat16 a
    v5e measured a quarter less time and 19 times the state's error:
    PERF.md, PR 37.)

    With ``gamma_i = exp(sum_{j<=i} g_j)`` and ``u_i`` what token ``i``
    writes (``S_i = exp(g_i) S_{i-1} + k_i u_i^T``): ``(I + diag(beta) A)
    U = diag(beta) (V - diag(gamma) K S0)``, ``A[i, j] = gamma_i / gamma_j
    (k_i . k_j)`` below the diagonal; the inverse of that unit lower
    triangle, ``N = -diag(beta) A`` nilpotent, is the product of ``(I +
    N^(2^i))``."""
    bt, dk = k.shape
    dv = v.shape[1]
    f32 = jnp.float32
    exact = dict(preferred_element_type=f32, precision=_HIGHEST)

    def mm(a, b):
        return jnp.dot(a, b, **exact)

    def mm_nt(a, b):        # a @ b.T
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)

    row = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    diff = _cols(g_col, bt) - g_row                         # g_i - g_j

    def decay(mask):
        return jnp.where(mask, jnp.exp(jnp.where(mask, diff, 0.0)), 0.0)

    n = -(_cols(b_col, bt) * mm_nt(k, k) * decay(row > col))
    t = jnp.where(row == col, 1.0, 0.0) + n
    m = n
    for _ in range(max(0, math.ceil(math.log2(bt)) - 1)):
        m = mm(m, m)
        t = t + mm(t, m)
    gamma = jnp.exp(g_col)
    kf = k.astype(f32)
    rhs = _cols(b_col, dv) * (v.astype(f32) - _cols(gamma, dv) * mm(kf, s0))
    u = mm(t, rhs)
    o = _cols(gamma, dv) * mm(q.astype(f32), s0) \
        + mm(mm_nt(q, k) * decay(row >= col), u)
    g_last = g_col[bt - 1:bt]
    kd = kf * jnp.exp(_cols(g_last, dk) - _cols(g_col, dk))
    s1 = jnp.exp(_cols(g_last, dv)) * s0 + jax.lax.dot_general(
        kd, u, (((0,), (0,)), ((), ())), **exact)
    return o, s1


def _block_sums(g, bt: int):
    """The running sum of ``g`` (S, C, H) within blocks of ``bt`` rows."""
    s, c, h = g.shape
    return jnp.cumsum(g.reshape(s, c // bt, bt, h), axis=2).reshape(s, c, h)


def _rule_scan(s_all, q, k, v, g, beta, layer, slots, fresh):
    """The portable rule: a ``lax.scan`` over the chunk's rows. ``q``,
    ``k`` (S, C, Hv, dk) — already one a value head; ``v`` (S, C, Hv, dv);
    ``g``, ``beta`` (S, C, Hv), zero in the rows that are not real."""
    f32 = lambda a: jnp.moveaxis(a, 1, 0).astype(jnp.float32)

    def step(s, xs):
        return _advance(s, *xs)

    s0 = s_all[layer, slots]
    s0 = jnp.where(fresh[:, None, None, None], jnp.zeros_like(s0), s0)
    s1, o = jax.lax.scan(step, s0, (f32(q), f32(k), f32(v), f32(g), f32(beta)))
    return jnp.moveaxis(o, 0, 1), s_all.at[layer, slots].set(s1)


# -- the kernels ----------------------------------------------------------------

#: Most rows of a chunk the kernel folds into the state at once: at most
#: a lane tile (a v5e measured 128 ahead of 64: PERF.md, PR 37).
_CHUNK_ROWS = 128


def _chunk_rows(rows: int) -> int:
    """Rows in a block of the chunk kernel: the largest multiple of 8, at
    most ``_CHUNK_ROWS``, that divides the chunk's ``rows`` (0: none)."""
    return next((bt for bt in range(min(_CHUNK_ROWS, rows) // 8 * 8, 0, -8)
                 if rows % bt == 0), 0)


def gdn_kernel_supported(cfg: GatedDeltaNetConfig, rows: int,
                         wave: bool = False) -> bool:
    """Shape gate of the Pallas kernels: heads of exactly 128 x 128 (a
    head is one lane tile of every operand) and, for a chunk, rows that
    whole blocks divide (:func:`_chunk_rows`)."""
    return (cfg.head_k_dim == _LANES and cfg.head_v_dim == _LANES
            and cfg.num_v_heads % cfg.num_k_heads == 0
            and (wave or _chunk_rows(rows) > 0))


def _chunk_kernel(layer_ref, slot_ref, valid_ref, fresh_ref, q_ref, k_ref,
                  v_ref, gcol_ref, grow_ref, bcol_ref, s_in_ref, o_ref,
                  s_out_ref, s_scr, *, bt: int):
    """One (slot, value head, block of ``bt`` rows) of a chunk: ``S`` is
    loaded into VMEM at the head's first block (zeros where the slot starts
    afresh), advanced a block at a time by :func:`_wy_block` while the
    block holds a real row, and stored after the last."""
    s, blk = pl.program_id(0), pl.program_id(2)
    del layer_ref, slot_ref  # used by the index maps

    @pl.when(blk == 0)
    def _load():
        s0 = s_in_ref[...]
        s_scr[...] = jnp.where(fresh_ref[s] > 0, jnp.zeros_like(s0), s0)

    live = blk * bt < valid_ref[s]

    @pl.when(live)
    def _block():
        o, s1 = _wy_block(s_scr[...], q_ref[...], k_ref[...], v_ref[...],
                          gcol_ref[...], grow_ref[...], bcol_ref[...])
        o_ref[...] = o
        s_scr[...] = s1

    @pl.when(jnp.logical_not(live))
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(blk == pl.num_programs(2) - 1)
    def _store():
        s_out_ref[...] = s_scr[...]


def _chunk_pallas(s_all, q, k, v, g, beta, layer, slots, valid, fresh, *,
                  cfg: GatedDeltaNetConfig, interpret: bool):
    """The chunk kernel's call: ``q``, ``k`` (S, C, Hk * dk); ``v`` (S, C,
    Hv * dv); ``g``, ``beta`` (S, C, Hv) float32, zero in rows that are
    not real."""
    s, c, _ = q.shape
    hv, dk, dv = cfg.num_v_heads, cfg.head_k_dim, cfg.head_v_dim
    ratio = hv // cfg.num_k_heads
    bt = _chunk_rows(c)
    nt = c // bt
    lanes = lambda a: jnp.broadcast_to(                      # (S, Hv, C, 128)
        jnp.moveaxis(a, 2, 1)[..., None], (s, hv, c, _LANES))
    gc = _block_sums(g, bt)
    g_row = jnp.moveaxis(gc, 2, 1).reshape(s, hv, nt, 1, bt)

    def key_rows(i, h, t, *_):
        return (i, t, h // ratio)

    def value_rows(i, h, t, *_):
        return (i, t, h)

    def columns(i, h, t, *_):
        return (i, h, t, 0)

    def state(i, h, t, layer_ref, slot_ref, *_):
        return (layer_ref[0], slot_ref[i], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s, hv, nt),
        in_specs=[
            pl.BlockSpec((None, bt, dk), key_rows),                  # q
            pl.BlockSpec((None, bt, dk), key_rows),                  # k
            pl.BlockSpec((None, bt, dv), value_rows),                # v
            pl.BlockSpec((None, None, bt, _LANES), columns),         # g column
            pl.BlockSpec((None, None, None, 1, bt),
                         lambda i, h, t, *_: (i, h, t, 0, 0)),       # g row
            pl.BlockSpec((None, None, bt, _LANES), columns),         # beta
            pl.BlockSpec((None, None, None, dk, dv), state),         # S in
        ],
        out_specs=[
            pl.BlockSpec((None, bt, dv), value_rows),                # o
            pl.BlockSpec((None, None, None, dk, dv), state),         # S out
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )
    o, s_all = pl.pallas_call(
        functools.partial(_chunk_kernel, bt=bt),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, c, hv * dv), jnp.float32),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype)],
        # The state array is read and written where it lies: operand 10
        # (after the four prefetched scalars) is output 1.
        input_output_aliases={10: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="gdn_chunk",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      valid.astype(jnp.int32), fresh.astype(jnp.int32),
      q, k, v, lanes(gc), g_row, lanes(beta), s_all)
    return o, s_all


#: Value heads a step of the wave kernel holds: 8 x 64 KB of state in,
#: as much out, both double-buffered = 2 MB of VMEM.
_STEP_HEADS = 8


def _step_kernel(layer_ref, order_ref, n_ref, fresh_ref, q_ref, k_ref, v_ref,
                 a_ref, b_ref, s_in_ref, o_ref, s_out_ref, *, heads: int,
                 ratio: int):
    """One (group of ``heads`` value heads, running slot) of a wave. Grid
    step ``i`` is the ``i``-th RUNNING slot, ``order[i]``; the steps past
    the ``n`` running ones name the last of them again, so they copy
    nothing in or out and do nothing here. With nothing running the one
    block visited is handed back as it came.

    ``S`` is (key on sublanes, value on lanes). The key and the query come
    as rows; what the update needs is each as a COLUMN spread over the
    lanes, which the MXU makes: ``diag(k) @ ones``."""
    i = pl.program_id(1)
    live = i < n_ref[0]
    del layer_ref  # used by the index maps

    @pl.when(live)
    def _update():
        fresh = fresh_ref[order_ref[i]] > 0
        dk = s_in_ref.shape[1]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
        dt = k_ref.dtype
        ones = jnp.ones((dk, _LANES), dt)

        def spread(row):        # (1, dk) -> (dk, lanes), row[j] along row j
            # (The select runs in float32: a 16-bit one would ask for the
            # mask in another layout.)
            diag = jnp.where(eye, row.astype(jnp.float32), 0.0).astype(dt)
            return jnp.dot(diag, ones, preferred_element_type=jnp.float32)

        for hk in range(heads // ratio):
            k, q = k_ref[hk], q_ref[hk]                       # (1, dk)
            kc, qc = spread(k), spread(q)
            kq = jnp.sum(k.astype(jnp.float32) * q.astype(jnp.float32),
                         axis=1, keepdims=True)
            for h in range(hk * ratio, (hk + 1) * ratio):
                s0 = s_in_ref[h]
                s0 = jnp.where(fresh, jnp.zeros_like(s0), s0) * a_ref[h]
                r = jnp.sum(s0 * kc, axis=0, keepdims=True)   # (1, dv)
                p = jnp.sum(s0 * qc, axis=0, keepdims=True)
                u = b_ref[h] * (v_ref[h].astype(jnp.float32) - r)
                s_out_ref[h] = s0 + kc * u
                o_ref[h] = p + kq * u

    @pl.when(jnp.logical_not(live) & (i == 0))
    def _nothing_runs():
        s_out_ref[...] = s_in_ref[...]


def _step_pallas(s_all, q, k, v, g, beta, layer, valid, fresh, *,
                 cfg: GatedDeltaNetConfig, interpret: bool):
    """The wave kernel's call: ``q``, ``k`` (S, Hk, dk); ``v`` (S, Hv,
    dv); ``g``, ``beta`` (S, Hv) float32. Row ``s`` is slot ``s``."""
    s = q.shape[0]
    hk, hv, dk, dv = cfg.num_k_heads, cfg.num_v_heads, cfg.head_k_dim, cfg.head_v_dim
    ratio = hv // hk
    # The largest group of whole q/k heads' value heads within _STEP_HEADS.
    heads = max(n for n in range(ratio, hv + 1, ratio)
                if hv % n == 0 and n <= max(_STEP_HEADS, ratio))
    run = valid > 0
    n = jnp.sum(run.astype(jnp.int32))
    by_running = jnp.argsort(jnp.logical_not(run), stable=True).astype(jnp.int32)
    order = by_running[jnp.minimum(jnp.arange(s, dtype=jnp.int32),
                                   jnp.maximum(n - 1, 0))]
    rows = lambda a: a[:, :, None, :]                            # (S, H, 1, d)
    lanes = lambda a: jnp.broadcast_to(a[:, :, None, None], (s, hv, 1, dv))

    def key_rows(j, i, layer_ref, order_ref, *_):
        return (order_ref[i], j, 0, 0)

    def state(j, i, layer_ref, order_ref, *_):
        return (layer_ref[0], order_ref[i], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(hv // heads, s),
        in_specs=[
            pl.BlockSpec((None, heads // ratio, 1, dk), key_rows),   # q
            pl.BlockSpec((None, heads // ratio, 1, dk), key_rows),   # k
            pl.BlockSpec((None, heads, 1, dv), key_rows),            # v
            pl.BlockSpec((None, heads, 1, dv), key_rows),            # exp(g)
            pl.BlockSpec((None, heads, 1, dv), key_rows),            # beta
            pl.BlockSpec((None, None, heads, dk, dv), state),        # S in
        ],
        out_specs=[
            pl.BlockSpec((None, heads, 1, dv), key_rows),            # o
            pl.BlockSpec((None, None, heads, dk, dv), state),        # S out
        ],
    )
    o, s_all = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads, ratio=ratio),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, hv, 1, dv), jnp.float32),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype)],
        # Operand 9 (after the four prefetched scalars) is output 1.
        input_output_aliases={9: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="gdn_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, n.reshape(1),
      fresh.astype(jnp.int32), rows(q), rows(k), rows(v),
      lanes(jnp.exp(g)), lanes(beta), s_all)
    # A slot that does not run was not visited: its rows are whatever the
    # buffer held.
    return jnp.where(run[:, None, None], o[:, :, 0], 0.0), s_all


def _per_value_head(a, cfg: GatedDeltaNetConfig):
    """``a`` (..., Hk * dk) as (..., Hv, dk): each q/k head once for every
    value head it serves."""
    a = a.reshape(a.shape[:-1] + (cfg.num_k_heads, cfg.head_k_dim))
    return jnp.repeat(a, cfg.num_v_heads // cfg.num_k_heads, axis=-2)


@functools.partial(jax.jit, static_argnames=("cfg", "kernel", "interpret"))
def gdn_chunk(s_all, q, k, v, g, beta, layer, slots, valid, fresh, *,
              cfg: GatedDeltaNetConfig, kernel: bool = False,
              interpret: bool = False):
    """The rule over a CHUNK of rows, from each slot's carried ``S``.

    ``s_all`` ``(state layers, max_slots, Hv, dk, dv)`` float32, the whole
    state array, read and written at ``(layer, slots[s])``; ``q``, ``k``
    ``(S, C, Hk * dk)`` (normalised, ``q`` scaled); ``v`` ``(S, C, Hv *
    dv)``; ``g``, ``beta`` ``(S, C, Hv)`` float32; ``valid`` ``(S,)`` —
    the first ``valid[s]`` rows advance ``S``, the rest nothing; ``fresh``
    ``(S,)`` bool — start from zeros. Returns ``(o (S, C, Hv * dv)
    float32, s_all')``; ``o`` past ``valid`` is garbage. ``kernel``: the
    Pallas kernel (a TPU; interpreted with ``interpret``), else a
    ``lax.scan`` over the rows."""
    s, c, _ = q.shape
    real = (jnp.arange(c, dtype=jnp.int32)[None, :] < valid[:, None])[..., None]
    g = jnp.where(real, g, 0.0)
    beta = jnp.where(real, beta, 0.0)
    if kernel:
        return _chunk_pallas(s_all, q, k, v, g, beta, layer, slots, valid,
                             fresh, cfg=cfg, interpret=interpret)
    o, s_all = _rule_scan(
        s_all, _per_value_head(q, cfg), _per_value_head(k, cfg),
        v.reshape(s, c, cfg.num_v_heads, cfg.head_v_dim), g, beta, layer,
        slots, fresh)
    return o.reshape(s, c, -1), s_all


@functools.partial(jax.jit, static_argnames=("cfg", "kernel", "interpret"))
def gdn_step(s_all, q, k, v, g, beta, layer, valid, fresh, *,
             cfg: GatedDeltaNetConfig, kernel: bool = False,
             interpret: bool = False):
    """:func:`gdn_chunk` for a decode WAVE: one row for every slot, row
    ``s`` being slot ``s`` — ``q``, ``k`` ``(S, Hk * dk)``, ``v`` ``(S,
    Hv * dv)``, ``g``, ``beta`` ``(S, Hv)``. A slot with ``valid`` 0
    keeps its ``S`` bitwise and gets ``o`` 0."""
    s = q.shape[0]
    heads = lambda a, n: a.reshape(s, n, -1)
    if kernel:
        o, s_all = _step_pallas(
            s_all, heads(q, cfg.num_k_heads), heads(k, cfg.num_k_heads),
            heads(v, cfg.num_v_heads), g, beta, layer, valid, fresh, cfg=cfg,
            interpret=interpret)
        return o.reshape(s, -1), s_all
    run = valid > 0
    s0 = s_all[layer]
    f32 = lambda a: a.astype(jnp.float32)
    s1, o = _advance(
        jnp.where(fresh[:, None, None, None], jnp.zeros_like(s0), s0),
        f32(_per_value_head(q, cfg)), f32(_per_value_head(k, cfg)),
        f32(heads(v, cfg.num_v_heads)), g, beta)
    s_all = s_all.at[layer].set(
        jnp.where(run[:, None, None, None], s1, s0))
    return jnp.where(run[:, None, None], o, 0.0).reshape(s, -1), s_all


# -- the layer ----------------------------------------------------------------

def _l2norm(x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


class GatedDeltaNet(Layer):
    """The mixer of the module docstring. Parameters: ``in_proj_qkvz``
    ``{w (D, 2 Hk dk + 2 Hv dv)}`` (columns ``[q | k | v | z]``, each head
    by head), ``in_proj_ba`` ``{w (D, 2 Hv)}`` (``[b | a]``), ``conv``
    ``{w (d_conv, 2 Hk dk + Hv dv)}`` (no bias), ``dt_bias`` ``(Hv,)``,
    ``a_log`` ``(Hv,)``, ``norm`` ``{scale (dv,)}`` (one weight for every
    head), ``out_proj`` ``{w (Hv dv, D)}``."""

    def __init__(self, features: int, config: GatedDeltaNetConfig, *,
                 norm_eps: float = 1e-6):
        c = config
        if c.num_v_heads % c.num_k_heads:
            raise ValueError(
                f"GatedDeltaNet: {c.num_v_heads} value heads over "
                f"{c.num_k_heads} key heads")
        self.features = features
        self.config = c
        self.norm_eps = norm_eps
        self.in_proj_qkvz = Dense(features, 2 * c.key_dim + 2 * c.value_dim,
                                  use_bias=False)
        self.in_proj_ba = Dense(features, 2 * c.num_v_heads, use_bias=False)
        self.out_proj = Dense(c.value_dim, features, use_bias=False)

    def init_params(self, key):
        c = self.config
        ks = jax.random.split(key, 6)
        dense = lambda layer, k: layer.init(k)["params"]
        # dt_bias: softplus^-1 of a step log-uniform in [1e-3, 1e-1];
        # a_log: log of a rate uniform in (0, 16) — the source's own.
        dt = jnp.exp(jax.random.uniform(ks[3], (c.num_v_heads,), jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return {
            "in_proj_qkvz": dense(self.in_proj_qkvz, ks[0]),
            "in_proj_ba": dense(self.in_proj_ba, ks[1]),
            "conv": {
                "w": jax.random.normal(ks[2], (c.d_conv, c.conv_dim), jnp.float32)
                * c.d_conv ** -0.5,
            },
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(
                ks[4], (c.num_v_heads,), jnp.float32, 1e-3, 16.0)),
            "norm": {"scale": jnp.ones((c.head_v_dim,), jnp.float32)},
            "out_proj": dense(self.out_proj, ks[5]),
        }

    def _sub(self, layer, p, x):
        return layer.apply({"params": p, "state": {}}, x)[0]

    def apply(self, variables, x, *, mode="train", rng=None):
        """The whole sequence ``x`` (B, T, D) from a zero state: a chunk
        of ``T`` rows with nothing carried in or out. Always the
        ``lax.scan`` recurrence."""
        c = self.config
        b, t, _ = x.shape
        state = tuple(
            jnp.zeros((1, b) + shape, dtype)
            for shape, dtype in c.state_shapes(x.dtype)
        )
        # The state is zeros already: any position but 0 leaves it be.
        y, _ = self.apply_state(
            variables["params"], x, state, jnp.ones((b,), jnp.int32),
            jnp.full((b,), t, jnp.int32), kernel=False,
        )
        return y, variables["state"]

    def apply_state(self, params, x, state, positions, valid, *, layer=0,
                    slots=None, kernel: Optional[bool] = None,
                    interpret: bool = False):
        """A chunk of each slot's sequence from its carried state.

        ``x`` (S, C, D); ``state`` ``(s_all, conv_all)`` — the WHOLE state
        arrays (module docstring), read and written at ``(layer,
        slots[s])`` (``slots`` None: slot ``s`` is row ``s``);
        ``positions`` (S,) — a slot whose chunk starts at position 0 and
        has a real row starts from zeros, whatever its arrays hold;
        ``valid`` (S,) — the rows that are real. Returns ``(out (S, C, D),
        state')``; ``out`` rows past ``valid`` are garbage the caller
        ignores. ``kernel`` None: the Pallas kernels wherever they run (a
        TPU and :func:`gdn_kernel_supported`)."""
        p, c = params, self.config
        s_all, conv_all = state
        s, t, _ = x.shape
        kd, vd = c.key_dim, c.value_dim
        wave = slots is None and t == 1
        slots = jnp.arange(s, dtype=jnp.int32) if slots is None else slots
        fresh = (positions == 0) & (valid > 0)

        with jax.named_scope("gdn/in_proj"):
            qkvz = self._sub(self.in_proj_qkvz, p["in_proj_qkvz"], x)
            mixed, z = qkvz[..., :c.conv_dim], qkvz[..., c.conv_dim:]
            ba = self._sub(self.in_proj_ba, p["in_proj_ba"], x).astype(jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :c.num_v_heads])
            g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
                ba[..., c.num_v_heads:] + p["dt_bias"].astype(jnp.float32))
        with jax.named_scope("gdn/conv"):
            mixed, conv_all = causal_conv(
                conv_all, mixed, p["conv"]["w"], layer, slots, valid, fresh)
            mixed = jax.nn.silu(mixed).astype(x.dtype)
        with jax.named_scope("gdn/rule"):
            heads = lambda a, n: a.reshape(s, t, n, -1)
            q = (_l2norm(heads(mixed[..., :kd], c.num_k_heads))
                 * c.head_k_dim ** -0.5).astype(x.dtype).reshape(s, t, kd)
            k = _l2norm(heads(mixed[..., kd:2 * kd], c.num_k_heads)) \
                .astype(x.dtype).reshape(s, t, kd)
            v = mixed[..., 2 * kd:]
            if kernel is None:
                kernel = not _on_cpu() or interpret
            fits = gdn_kernel_supported(c, s if wave else t, wave)
            if kernel and not fits and not _on_cpu():
                # Many times the kernel's cost, and the trace then holds
                # no ``gdn_chunk`` / ``gdn_step`` kernel event.
                warnings.warn(
                    f"GatedDeltaNet: the Pallas kernels do not take heads of "
                    f"{c.head_k_dim} x {c.head_v_dim} or {t} rows (no "
                    f"multiple of 8 divides them): the rule runs as a "
                    "lax.scan over tokens", stacklevel=2)
            how = dict(cfg=c, kernel=bool(kernel) and fits,
                       interpret=bool(interpret) or _on_cpu())
            if wave:
                o, s_all = gdn_step(s_all, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], layer, valid, fresh, **how)
                o = o[:, None]
            else:
                o, s_all = gdn_chunk(s_all, q, k, v, g, beta, layer, slots,
                                     valid, fresh, **how)
        with jax.named_scope("gdn/norm_gate"):
            o = gated_rms_norm(
                heads(o, c.num_v_heads), heads(z, c.num_v_heads),
                p["norm"]["scale"], self.norm_eps,
            ).astype(x.dtype).reshape(s, t, vd)
        with jax.named_scope("gdn/out"):
            out = self._sub(self.out_proj, p["out_proj"], o)
        return out, (s_all, conv_all)

    def __repr__(self):
        c = self.config
        return (f"GatedDeltaNet(d={self.features}, k={c.num_k_heads}x"
                f"{c.head_k_dim}, v={c.num_v_heads}x{c.head_v_dim})")
