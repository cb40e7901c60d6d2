"""Lightning attention (MiniCPM-SALA's linear-attention layer, MiniMax's
``lightning_attn``): linear attention with a FIXED decay a head and the
MATRIX per-slot state the serving engine carries beside its pages.

Per token ``t`` of the normed hidden ``x_t`` at position ``p_t``::

    [q, k, v, g] = in_proj(x)                   # H heads of dh each
    q, k         = RoPE(RMSNorm_h(q)), RoPE(RMSNorm_h(k))   # per head, at p_t
    S_t          = lambda_h S_{t-1} + k_t^T v_t          # per head, float32
    o_t          = (q_t / sqrt(dh)) S_t
    out          = out_proj(RMSNorm(concat_h o_t) * sigmoid(g))

with ``lambda_h = exp(-slope_h * (1 - l / (L - 1) + 1e-5))``: ``slope_h``
the ALiBi slopes of ``H`` heads (``2^(-h/4)``, ``h = 1 .. 32``, at 32
heads) and ``l`` the layer's index in a stack of ``L`` (the PUBLISHED
index and depth: a cut of the stack keeps its layers' decays). There is no
delta rule and no convolution: what a sequence carries from one call to
the next is ``S`` alone, ``dh x dh`` float32 a head (2 MB a layer a slot
at 32 heads of 128), and ``S`` is zero at position 0.

Three entries, one rule:

* :meth:`LightningAttention.apply` — the whole sequence from a zero state;
* :meth:`LightningAttention.apply_state` with ``C`` rows a slot — a
  prefill chunk from the slot's carried ``S``, the first ``valid`` rows
  real; rows past ``valid`` advance nothing;
* the same with ``C = 1`` — the decode wave's one-token step over every
  slot; a slot with ``valid`` 0 keeps its state bitwise.

The serving state is ONE array indexed by SLOT (``serve/kv_pool.py``),
``(state layers, max_slots, H, dh, dh)`` float32, handed over whole with a
``layer`` coordinate and updated in place under donation. Arrays of the
per-slot state that the layer does not own (a sparse layer's compressed
keys) pass through it untouched.

Two jitted functions carry the rule, each ONE Pallas kernel on a TPU,
under the names the profiler's events carry (the grid layouts of
``nn/gdn.py``'s ``gdn_step`` / ``gdn_chunk``):

* ``lightning_step`` (a wave): the grid walks the RUNNING slots only, ``S``
  of a slot and a group of heads read once and written once where it lies;
  ``S' = lambda S + k^T v`` and ``o = q S' = q (lambda S) + (q . k) v``,
  elementwise on ``(dh, dh)`` tiles with the key and the query spread over
  lanes by the MXU;
* ``lightning_chunk`` (a chunk): the grid is (slot, head, block of ``bt``
  rows), ``S`` in VMEM over a head's blocks, every block the chunked form
  ``O = ((Q K^T) * D) V + (lambda^(i+1) Q) S0``, ``S' = lambda^bt S0 +
  (lambda^(bt-1-j) K)^T V`` with ``D_ij = lambda^(i-j)`` (``i >= j``) —
  matrix products the MXU takes. A block past ``valid`` costs nothing.

Elsewhere (the CPU, heads that are no lane tile) a ``lax.scan`` over
tokens computes the same numbers.
"""

from __future__ import annotations

import functools
import math
import warnings
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rocket_tpu.nn.attention import apply_rope_offsets
from rocket_tpu.nn.gdn import _block_sums, _chunk_rows, _cols, _on_cpu
from rocket_tpu.nn.layers import Dense
from rocket_tpu.nn.module import Layer

__all__ = ["LightningConfig", "LightningAttention", "lightning_chunk",
           "lightning_step", "alibi_slopes"]

_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def alibi_slopes(heads: int) -> np.ndarray:
    """The ALiBi / MiniMax slopes of ``heads`` heads: ``2^(-8 i / n)``, ``i
    = 1 .. n``, for the power of two ``n`` at or under ``heads``, and every
    other of the next power's for the heads beyond it."""
    def power_of_two(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    n = 2 ** int(math.floor(math.log2(heads)))
    slopes = power_of_two(n)
    if n < heads:
        slopes += power_of_two(2 * n)[0::2][:heads - n]
    return np.asarray(slopes, np.float64)


@dataclass(frozen=True)
class LightningConfig:
    """Sizes of a lightning-attention mixer: ``num_heads`` heads of
    ``head_dim`` (q, k, v and the gate alike), the rotary base, and where
    the serving stack sits in the published one: its layer ``i`` is the
    published layer ``first_layer + i`` of ``published_layers`` — the
    decay's ``l`` and ``L``."""

    num_heads: int
    head_dim: int
    published_layers: int
    first_layer: int = 0
    rope_base: float = 10000.0

    @property
    def width(self) -> int:
        return self.num_heads * self.head_dim

    def log_decay(self, layer: int) -> np.ndarray:
        """``log lambda`` of each head at PUBLISHED index ``layer``."""
        depth = 1.0 - layer / (self.published_layers - 1) + 1e-5
        return -alibi_slopes(self.num_heads) * depth

    def state_shapes(self, dtype) -> tuple:
        """What ONE slot carries through one layer: ``S`` in float32."""
        del dtype
        return (((self.num_heads, self.head_dim, self.head_dim), "float32"),)

    def make_mixer(self, features: int, *, norm_eps: float = 1e-6,
                   layer: int = 0):
        """The mixer of the stack's layer ``layer``."""
        return LightningAttention(features, self, norm_eps=norm_eps,
                                  layer=self.first_layer + layer)


# -- the rule -----------------------------------------------------------------

def _linear_block(s0, q, k, v, g_col, g_row):
    """``bt`` successive tokens at once: ``s0`` (dk, dv) float32; ``q``,
    ``k`` (bt, dk); ``v`` (bt, dv), zero in rows that are not real; ``g_col``
    (bt, lanes) and ``g_row`` (1, bt) the running sum of ``log lambda``
    over the block's real rows, as a column spread over the lanes and as a
    row. Returns ``(o (bt, dv) float32, s1)``. Two-dimensional operations
    only: the body of the chunk kernel as it is, and with ``gamma_i =
    lambda^(i+1)`` (``exp`` of the running sum) ``o = gamma Q S0 + (Q K^T *
    gamma_i / gamma_j) V``, ``s1 = gamma_last S0 + (gamma_last / gamma_j
    K)^T V``. Products with a float32 operand run at the highest precision;
    ``Q K^T`` takes the activations as they come."""
    bt, dk = k.shape
    dv = v.shape[1]
    f32 = jnp.float32
    exact = dict(preferred_element_type=f32, precision=_HIGHEST)
    row = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    lower = row >= col
    diff = _cols(g_col, bt) - g_row                         # G_i - G_j
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32) * decay
    vf = v.astype(f32)
    o = _cols(jnp.exp(g_col), dv) * jnp.dot(q.astype(f32), s0, **exact) \
        + jnp.dot(scores, vf, **exact)
    g_last = g_col[bt - 1:bt]
    kd = k.astype(f32) * jnp.exp(_cols(g_last, dk) - _cols(g_col, dk))
    s1 = jnp.exp(_cols(g_last, dv)) * s0 + jax.lax.dot_general(
        kd, vf, (((0,), (0,)), ((), ())), **exact)
    return o, s1


def _rule_scan(s_all, q, k, v, g, layer, slots, fresh):
    """The portable rule: a ``lax.scan`` over the chunk's rows. ``q``,
    ``k``, ``v`` (S, C, H, dh); ``g`` (S, C, H) ``log lambda``, zero (and
    ``v`` zero) in rows that are not real."""
    f32 = lambda a: jnp.moveaxis(a, 1, 0).astype(jnp.float32)

    def step(s, xs):
        qt, kt, vt, gt = xs
        s = jnp.exp(gt)[..., None, None] * s + kt[..., :, None] * vt[..., None, :]
        return s, jnp.einsum("...kv,...k->...v", s, qt, precision=_HIGHEST)

    s0 = s_all[layer, slots]
    s0 = jnp.where(fresh[:, None, None, None], jnp.zeros_like(s0), s0)
    s1, o = jax.lax.scan(step, s0, (f32(q), f32(k), f32(v), f32(g)))
    return jnp.moveaxis(o, 0, 1), s_all.at[layer, slots].set(s1)


# -- the kernels ----------------------------------------------------------------

def lightning_kernel_supported(cfg: LightningConfig, rows: int,
                               wave: bool = False) -> bool:
    """Shape gate of the Pallas kernels: heads of exactly 128 lanes and,
    for a chunk, rows that whole blocks divide."""
    return cfg.head_dim == _LANES and (wave or _chunk_rows(rows) > 0)


def _chunk_kernel(layer_ref, slot_ref, valid_ref, fresh_ref, q_ref, k_ref,
                  v_ref, gcol_ref, grow_ref, s_in_ref, o_ref, s_out_ref,
                  s_scr, *, bt: int):
    """One (slot, head, block of ``bt`` rows) of a chunk: ``S`` loaded into
    VMEM at the head's first block (zeros where the slot starts afresh),
    advanced a block at a time by :func:`_linear_block` while the block
    holds a real row, and stored after the last."""
    s, blk = pl.program_id(0), pl.program_id(2)
    del layer_ref, slot_ref  # used by the index maps

    @pl.when(blk == 0)
    def _load():
        s0 = s_in_ref[...]
        s_scr[...] = jnp.where(fresh_ref[s] > 0, jnp.zeros_like(s0), s0)

    live = blk * bt < valid_ref[s]

    @pl.when(live)
    def _block():
        o, s1 = _linear_block(s_scr[...], q_ref[...], k_ref[...], v_ref[...],
                              gcol_ref[...], grow_ref[...])
        o_ref[...] = o
        s_scr[...] = s1

    @pl.when(jnp.logical_not(live))
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(blk == pl.num_programs(2) - 1)
    def _store():
        s_out_ref[...] = s_scr[...]


def _chunk_pallas(s_all, q, k, v, g, layer, slots, valid, fresh, *,
                  cfg: LightningConfig, interpret: bool):
    """The chunk kernel's call: ``q``, ``k``, ``v`` (S, C, H * dh); ``g``
    (S, C, H) float32, zero in rows that are not real."""
    s, c, _ = q.shape
    h, dh = cfg.num_heads, cfg.head_dim
    bt = _chunk_rows(c)
    nt = c // bt
    gc = _block_sums(g, bt)
    g_col = jnp.broadcast_to(jnp.moveaxis(gc, 2, 1)[..., None], (s, h, c, _LANES))
    g_row = jnp.moveaxis(gc, 2, 1).reshape(s, h, nt, 1, bt)

    def rows(i, j, t, *_):
        return (i, t, j)

    def columns(i, j, t, *_):
        return (i, j, t, 0)

    def state(i, j, t, layer_ref, slot_ref, *_):
        return (layer_ref[0], slot_ref[i], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s, h, nt),
        in_specs=[
            pl.BlockSpec((None, bt, dh), rows),                      # q
            pl.BlockSpec((None, bt, dh), rows),                      # k
            pl.BlockSpec((None, bt, dh), rows),                      # v
            pl.BlockSpec((None, None, bt, _LANES), columns),         # g column
            pl.BlockSpec((None, None, None, 1, bt),
                         lambda i, j, t, *_: (i, j, t, 0, 0)),       # g row
            pl.BlockSpec((None, None, None, dh, dh), state),         # S in
        ],
        out_specs=[
            pl.BlockSpec((None, bt, dh), rows),                      # o
            pl.BlockSpec((None, None, None, dh, dh), state),         # S out
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
    )
    o, s_all = pl.pallas_call(
        functools.partial(_chunk_kernel, bt=bt),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, c, h * dh), jnp.float32),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype)],
        # The state array is read and written where it lies: operand 9
        # (after the four prefetched scalars) is output 1.
        input_output_aliases={9: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="lightning_chunk",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      valid.astype(jnp.int32), fresh.astype(jnp.int32),
      q, k, v, g_col, g_row, s_all)
    return o, s_all


#: Heads a step of the wave kernel holds: 8 x 64 KB of state in, as much
#: out, both double-buffered = 2 MB of VMEM (``gdn_step``'s).
_STEP_HEADS = 8


def _step_kernel(layer_ref, order_ref, n_ref, fresh_ref, q_ref, k_ref, v_ref,
                 a_ref, s_in_ref, o_ref, s_out_ref, *, heads: int):
    """One (group of ``heads`` heads, running slot) of a wave, as
    ``gdn_step``'s body walks them: grid step ``i`` is the ``i``-th
    RUNNING slot, ``order[i]``; the steps past the ``n`` running ones name
    the last of them again and do nothing. ``S`` is (key on sublanes,
    value on lanes); the key and the query, which come as rows, are spread
    over the lanes as columns by the MXU (``diag(k) @ ones``)."""
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
            diag = jnp.where(eye, row.astype(jnp.float32), 0.0).astype(dt)
            return jnp.dot(diag, ones, preferred_element_type=jnp.float32)

        for h in range(heads):
            k, q = k_ref[h], q_ref[h]                         # (1, dk)
            kq = jnp.sum(k.astype(jnp.float32) * q.astype(jnp.float32),
                         axis=1, keepdims=True)
            s0 = s_in_ref[h]
            s0 = jnp.where(fresh, jnp.zeros_like(s0), s0) * a_ref[h]
            v = v_ref[h].astype(jnp.float32)                  # (1, dv)
            p = jnp.sum(s0 * spread(q), axis=0, keepdims=True)
            s_out_ref[h] = s0 + spread(k) * v
            o_ref[h] = p + kq * v

    @pl.when(jnp.logical_not(live) & (i == 0))
    def _nothing_runs():
        s_out_ref[...] = s_in_ref[...]


def _step_pallas(s_all, q, k, v, decay, layer, valid, fresh, *,
                 cfg: LightningConfig, interpret: bool):
    """The wave kernel's call: ``q``, ``k``, ``v`` (S, H, dh); ``decay``
    (H,) ``lambda`` float32. Row ``s`` is slot ``s``."""
    s = q.shape[0]
    h, dh = cfg.num_heads, cfg.head_dim
    heads = max(n for n in range(1, h + 1) if h % n == 0 and n <= _STEP_HEADS)
    run = valid > 0
    n = jnp.sum(run.astype(jnp.int32))
    by_running = jnp.argsort(jnp.logical_not(run), stable=True).astype(jnp.int32)
    order = by_running[jnp.minimum(jnp.arange(s, dtype=jnp.int32),
                                   jnp.maximum(n - 1, 0))]
    rows = lambda a: a[:, :, None, :]                            # (S, H, 1, d)

    def key_rows(j, i, layer_ref, order_ref, *_):
        return (order_ref[i], j, 0, 0)

    def state(j, i, layer_ref, order_ref, *_):
        return (layer_ref[0], order_ref[i], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(h // heads, s),
        in_specs=[
            pl.BlockSpec((None, heads, 1, dh), key_rows),            # q
            pl.BlockSpec((None, heads, 1, dh), key_rows),            # k
            pl.BlockSpec((None, heads, 1, dh), key_rows),            # v
            pl.BlockSpec((heads, 1, dh), lambda j, i, *_: (j, 0, 0)),  # lambda
            pl.BlockSpec((None, None, heads, dh, dh), state),        # S in
        ],
        out_specs=[
            pl.BlockSpec((None, heads, 1, dh), key_rows),            # o
            pl.BlockSpec((None, None, heads, dh, dh), state),        # S out
        ],
    )
    o, s_all = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, h, 1, dh), jnp.float32),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype)],
        # Operand 8 (after the four prefetched scalars) is output 1.
        input_output_aliases={8: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="lightning_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, n.reshape(1),
      fresh.astype(jnp.int32), rows(q), rows(k), rows(v),
      jnp.broadcast_to(decay.astype(jnp.float32)[:, None, None], (h, 1, dh)),
      s_all)
    # A slot that does not run was not visited: its rows are whatever the
    # buffer held.
    return jnp.where(run[:, None, None], o[:, :, 0], 0.0), s_all


@functools.partial(jax.jit, static_argnames=("cfg", "kernel", "interpret"))
def lightning_chunk(s_all, q, k, v, log_decay, layer, slots, valid, fresh, *,
                    cfg: LightningConfig, kernel: bool = False,
                    interpret: bool = False):
    """The rule over a CHUNK of rows, from each slot's carried ``S``.

    ``s_all`` ``(state layers, max_slots, H, dh, dh)`` float32, the whole
    state array, read and written at ``(layer, slots[s])``; ``q``, ``k``,
    ``v`` ``(S, C, H * dh)`` (normed and rotated, ``q`` scaled);
    ``log_decay`` ``(H,)`` float32; ``valid`` ``(S,)`` — the first
    ``valid[s]`` rows advance ``S``, the rest nothing; ``fresh`` ``(S,)``
    bool — start from zeros. Returns ``(o (S, C, H * dh) float32,
    s_all')``; ``o`` past ``valid`` is garbage. ``kernel``: the Pallas
    kernel (a TPU; interpreted with ``interpret``), else a ``lax.scan``."""
    s, c, _ = q.shape
    h = cfg.num_heads
    real = jnp.arange(c, dtype=jnp.int32)[None, :] < valid[:, None]
    g = jnp.where(real[..., None], log_decay.astype(jnp.float32)[None, None, :], 0.0)
    v = jnp.where(real[..., None], v, jnp.zeros_like(v))
    if kernel:
        return _chunk_pallas(s_all, q, k, v, g, layer, slots, valid, fresh,
                             cfg=cfg, interpret=interpret)
    heads = lambda a: a.reshape(s, c, h, -1)
    o, s_all = _rule_scan(s_all, heads(q), heads(k), heads(v), g, layer,
                          slots, fresh)
    return o.reshape(s, c, -1), s_all


@functools.partial(jax.jit, static_argnames=("cfg", "kernel", "interpret"))
def lightning_step(s_all, q, k, v, log_decay, layer, valid, fresh, *,
                   cfg: LightningConfig, kernel: bool = False,
                   interpret: bool = False):
    """:func:`lightning_chunk` for a decode WAVE: one row for every slot,
    row ``s`` being slot ``s`` — ``q``, ``k``, ``v`` ``(S, H * dh)``. A slot
    with ``valid`` 0 keeps its ``S`` bitwise and gets ``o`` 0."""
    s = q.shape[0]
    h = cfg.num_heads
    heads = lambda a: a.reshape(s, h, -1)
    decay = jnp.exp(log_decay.astype(jnp.float32))
    if kernel:
        o, s_all = _step_pallas(s_all, heads(q), heads(k), heads(v), decay,
                                layer, valid, fresh, cfg=cfg,
                                interpret=interpret)
        return o.reshape(s, -1), s_all
    run = valid > 0
    s0 = s_all[layer]
    f32 = lambda a: heads(a).astype(jnp.float32)
    s1 = decay[None, :, None, None] * jnp.where(
        fresh[:, None, None, None], jnp.zeros_like(s0), s0) \
        + f32(k)[..., :, None] * f32(v)[..., None, :]
    o = jnp.einsum("shkv,shk->shv", s1, f32(q), precision=_HIGHEST)
    s_all = s_all.at[layer].set(jnp.where(run[:, None, None, None], s1, s0))
    return jnp.where(run[:, None, None], o, 0.0).reshape(s, -1), s_all


# -- the layer ----------------------------------------------------------------

class LightningAttention(Layer):
    """The mixer of the module docstring at PUBLISHED layer index
    ``layer``. Parameters: ``in_proj`` ``{w (D, 4 H dh)}`` (columns ``[q |
    k | v | g]``, each head by head), ``q_norm`` / ``k_norm`` ``{scale
    (dh,)}`` (one weight for every head), ``norm`` ``{scale (H dh,)}`` (the
    output norm over every head at once), ``out_proj`` ``{w (H dh, D)}``."""

    def __init__(self, features: int, config: LightningConfig, *,
                 norm_eps: float = 1e-6, layer: int = 0):
        c = config
        if c.head_dim % 2:
            raise ValueError("LightningAttention: rope needs an even head_dim")
        self.features = features
        self.config = c
        self.norm_eps = norm_eps
        self.layer = int(layer)
        #: ``log lambda`` of each head (float32 on the device).
        self.log_decay = c.log_decay(self.layer).astype(np.float32)
        self.in_proj = Dense(features, 4 * c.width, use_bias=False)
        self.out_proj = Dense(c.width, features, use_bias=False)

    def init_params(self, key):
        c = self.config
        k1, k2 = jax.random.split(key)
        ones = lambda n: {"scale": jnp.ones((n,), jnp.float32)}
        return {
            "in_proj": self.in_proj.init(k1)["params"],
            "q_norm": ones(c.head_dim), "k_norm": ones(c.head_dim),
            "norm": ones(c.width),
            "out_proj": self.out_proj.init(k2)["params"],
        }

    def _rms(self, x, scale):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return xf * jax.lax.rsqrt(ms + self.norm_eps) * scale.astype(jnp.float32)

    def apply(self, variables, x, *, mode="train", rng=None):
        """The whole sequence ``x`` (B, T, D) from a zero state. Always the
        ``lax.scan`` recurrence."""
        b, t, _ = x.shape
        state = tuple(
            jnp.zeros((1, b) + shape, dtype)
            for shape, dtype in self.config.state_shapes(x.dtype)
        )
        y, _ = self.apply_state(
            variables["params"], x, state, jnp.zeros((b,), jnp.int32),
            jnp.full((b,), t, jnp.int32), kernel=False,
        )
        return y, variables["state"]

    def apply_state(self, params, x, state, positions, valid, *, layer=0,
                    slots=None, kernel: Optional[bool] = None,
                    interpret: bool = False):
        """A chunk of each slot's sequence from its carried state.

        ``x`` (S, C, D) at positions ``positions[s] ..``; ``state`` the
        WHOLE per-slot state arrays, of which the first is this layer's
        ``S`` (read and written at ``(layer, slots[s])``; ``slots`` None:
        slot ``s`` is row ``s``) and the others pass through; a slot whose
        chunk starts at position 0 with a real row starts from zeros;
        ``valid`` (S,) the rows that are real. Returns ``(out (S, C, D),
        state')``; ``out`` rows past ``valid`` are garbage the caller
        ignores. ``kernel`` None: the Pallas kernels wherever they run."""
        p, c = params, self.config
        s_all, rest = state[0], tuple(state[1:])
        s, t, _ = x.shape
        w, dh = c.width, c.head_dim
        wave = slots is None and t == 1
        slots = jnp.arange(s, dtype=jnp.int32) if slots is None else slots
        fresh = (positions == 0) & (valid > 0)

        with jax.named_scope("lightning/in_proj"):
            proj = self.in_proj.apply({"params": p["in_proj"], "state": {}}, x)[0]
            heads = lambda a: a.reshape(s, t, c.num_heads, dh)
            q = self._rms(heads(proj[..., :w]), p["q_norm"]["scale"])
            k = self._rms(heads(proj[..., w:2 * w]), p["k_norm"]["scale"])
            turn = functools.partial(apply_rope_offsets, offsets=positions,
                                     base=c.rope_base)
            q = (turn(q) * dh ** -0.5).astype(x.dtype).reshape(s, t, w)
            k = turn(k).astype(x.dtype).reshape(s, t, w)
            v, gate = proj[..., 2 * w:3 * w], proj[..., 3 * w:]
        with jax.named_scope("lightning/rule"):
            if kernel is None:
                kernel = not _on_cpu() or interpret
            fits = lightning_kernel_supported(c, s if wave else t, wave)
            if kernel and not fits and not _on_cpu():
                warnings.warn(
                    f"LightningAttention: the Pallas kernels do not take heads "
                    f"of {dh} or {t} rows: the rule runs as a lax.scan over "
                    "tokens", stacklevel=2)
            how = dict(cfg=c, kernel=bool(kernel) and fits,
                       interpret=bool(interpret) or _on_cpu())
            log_decay = jnp.asarray(self.log_decay)
            if wave:
                o, s_all = lightning_step(s_all, q[:, 0], k[:, 0], v[:, 0],
                                          log_decay, layer, valid, fresh, **how)
                o = o[:, None]
            else:
                o, s_all = lightning_chunk(s_all, q, k, v, log_decay, layer,
                                           slots, valid, fresh, **how)
        with jax.named_scope("lightning/norm_gate"):
            o = self._rms(o, p["norm"]["scale"]) * jax.nn.sigmoid(
                gate.astype(jnp.float32))
        with jax.named_scope("lightning/out"):
            out = self.out_proj.apply(
                {"params": p["out_proj"], "state": {}}, o.astype(x.dtype))[0]
        return out, (s_all,) + rest

    def __repr__(self):
        c = self.config
        return (f"LightningAttention(d={self.features}, h={c.num_heads}x"
                f"{c.head_dim}, layer={self.layer})")
