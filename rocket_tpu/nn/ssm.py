"""Mamba-1 selective state-space mixer (arXiv 2312.00752, §3) with the
per-slot recurrent state the serving engine carries beside its pages.

Per token ``t`` of hidden ``x_t``::

    [u, z]    = in_proj(x)                      # 2 x d_inner
    u         = silu(conv1d(u))                 # depthwise, causal, d_conv taps
    [dt, B, C] = x_proj(u)                      # dt_rank, d_state, d_state
    dt, B, C  = RMSNorm(dt), RMSNorm(B), RMSNorm(C)      # Jamba's addition
    delta     = softplus(dt_proj(dt))           # d_inner, float32
    h_t       = exp(delta_t (x) A) * h_{t-1} + (delta_t * u_t) (x) B_t
    y_t       = h_t . C_t + D * u_t
    out       = out_proj(y * silu(z))

with ``A = -exp(a_log)`` in float32. **What a sequence carries** from one
call to the next is ``h`` (``d_state x d_inner``, float32) and the last
``d_conv - 1`` inputs of the convolution — a fixed size whatever the
context, where attention caches a row a token.

Three entries, one recurrence (:func:`_advance`, the only place the update
is written):

* :meth:`MambaMixer.apply` — the whole sequence from a zero state (eval,
  training, ``generate()``);
* :meth:`MambaMixer.apply_state` with ``C`` rows a slot — a prefill chunk
  that starts from the slot's carried ``(h, conv)`` with the first
  ``valid`` rows real; rows past ``valid`` advance nothing;
* the same with ``C = 1`` — the decode wave's one-token step over every
  slot; a slot with ``valid`` 0 keeps its state bitwise.

The serving state lives in two arrays indexed by SLOT, not by block
(``serve/kv_pool.py``): ``h`` ``(state layers, max_slots, d_state,
d_inner)`` float32 — ``d_inner`` on the lane axis, so no lane is padding —
and ``conv`` ``(state layers, max_slots, (d_conv - 1) * d_inner)`` in the
activation dtype, the taps side by side on the lane axis. Both are handed
over whole with a ``layer`` coordinate and updated in place under
donation, like the pages.

The recurrence runs under two jitted functions whose names the profiler's
events carry: ``ssm_scan`` (a chunk) and ``ssm_step`` (a wave). On a TPU
both are ONE Pallas kernel (:func:`_ssm_kernel`): the grid is (slot,
``d_inner`` block, time block), ``h`` stays in VMEM over a slot's time
blocks and is read from and written to the state array where it lies
(``input_output_aliases``), the time loop runs as far as ``valid`` says.
Elsewhere (the CPU, shapes the kernel does not take, and anything that is
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

from rocket_tpu.nn.layers import Dense, RMSNorm
from rocket_tpu.nn.module import Layer

__all__ = ["SSMConfig", "MambaMixer", "ssm_scan", "ssm_step",
           "ssm_kernel_supported"]

_LANES = 128


def _on_cpu() -> bool:
    """Whether this process's default backend is the CPU, where the
    kernel can only run interpreted."""
    return jax.devices()[0].platform == "cpu"


@dataclass(frozen=True)
class SSMConfig:
    """Sizes of a Mamba-1 mixer; ``conv_bias`` and ``proj_bias`` are the
    published ``mamba_conv_bias`` and ``mamba_proj_bias``."""

    d_inner: int
    dt_rank: int
    d_state: int = 16
    d_conv: int = 4
    conv_bias: bool = True
    proj_bias: bool = False

    def state_shapes(self, dtype) -> tuple:
        """What ONE slot carries through one layer: ``((shape, dtype),
        ...)`` — ``h`` in float32, the convolution's tail in ``dtype``."""
        return (
            ((self.d_state, self.d_inner), "float32"),
            (((self.d_conv - 1) * self.d_inner,), str(jnp.dtype(dtype))),
        )

    def make_mixer(self, features: int, *, norm_eps: float = 1e-6):
        return MambaMixer(features, self, norm_eps=norm_eps)


def _advance(h, dt, u, b, c, a):
    """One token of the recurrence, for any leading shape: ``h`` (...,
    N, W) float32; ``dt``, ``u`` (..., 1, W); ``b``, ``c`` (..., N, 1) or
    (..., N, W); ``a`` (N, W). Returns ``(h', y (..., 1, W))`` — ``y``
    without the ``D * u`` skip."""
    h = jnp.exp(dt * a) * h + (dt * u) * b
    return h, jnp.sum(h * c, axis=-2, keepdims=True)


# -- the kernel ---------------------------------------------------------------

_GROUP = 8  # rows a loop step advances: one sublane tile of float32


def _time_block(t: int) -> int:
    return 128 if t % 128 == 0 else t


def ssm_kernel_supported(d_inner: int, d_state: int, rows: int,
                         wave: bool = False) -> bool:
    """Shape gate of the Pallas kernel: whole 128-lane tiles of
    ``d_inner``, whole sublane tiles of ``d_state``, and ``rows`` in whole
    groups of 8 — the rows of a chunk (one time block of at most 512, or
    several of 128), or the slots of a ``wave``."""
    return (d_inner % _LANES == 0 and d_state % 8 == 0
            and rows % _GROUP == 0 and (wave or _time_block(rows) <= 512))


def _ssm_kernel(layer_ref, slot_ref, valid_ref, fresh_ref, delta_ref, u_ref,
                bx_ref, cx_ref, a_ref, h_in_ref, y_ref, h_out_ref, h_scr, *,
                tb: int, wave: bool):
    """One (sequence, ``d_inner`` block, time block) of the recurrence.

    A CHUNK (``wave`` False): the block's rows are ``tb`` successive rows
    of ONE slot. Its ``h`` is loaded into VMEM scratch at the slot's first
    time block (zeros where the slot starts afresh), advanced 8 rows a
    loop step over the rows that are real — ``valid`` of the slot less the
    rows of earlier time blocks — and stored after the last. Rows past
    ``valid`` cost no loop step, move nothing and give ``y`` 0.

    A WAVE (``wave`` True): the block's 8 rows are 8 SLOTS, one row each,
    every one with an ``h`` of its own; a slot whose ``valid`` is 0 gets
    its ``h`` back as it was.

    Every vector operation is on ``(d_state, 128)`` tiles: ``B`` and ``C``
    come in already spread over 128 lanes (``bx``, ``cx``), so nothing is
    transposed or broadcast along lanes in here."""
    s, tblk = pl.program_id(0), pl.program_id(2)
    del layer_ref, slot_ref  # used by the index maps

    @pl.when(tblk == 0)
    def _load():
        for i in range(h_scr.shape[0]):
            h0 = h_in_ref[i]
            fresh = fresh_ref[s * _GROUP + i if wave else s] > 0
            h_scr[i] = jnp.where(fresh, jnp.zeros_like(h0), h0)

    n = tb if wave else jnp.clip(valid_ref[s] - tblk * tb, 0, tb)
    y_ref[...] = jnp.zeros_like(y_ref)
    chunks = h_scr.shape[2] // _LANES
    row = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, _LANES), 0)

    def body(g, carry):
        r0 = pl.multiple_of(g * _GROUP, _GROUP)
        dt = delta_ref[pl.ds(r0, _GROUP), :]
        ut = u_ref[pl.ds(r0, _GROUP), :]
        for j in range(chunks):
            lanes = slice(j * _LANES, (j + 1) * _LANES)
            a = a_ref[:, lanes]
            tile = jnp.zeros((_GROUP, _LANES), jnp.float32)
            h = None if wave else h_scr[0, :, lanes]
            for i in range(_GROUP):
                if wave:
                    h = h_scr[i, :, lanes]
                    live = valid_ref[s * _GROUP + i] > 0
                else:
                    live = r0 + i < n
                h2, y = _advance(h, dt[i:i + 1, lanes], ut[i:i + 1, lanes],
                                 bx_ref[r0 + i], cx_ref[r0 + i], a)
                h = jnp.where(live, h2, h)
                tile = jnp.where((row == i) & live, y, tile)
                if wave:
                    h_scr[i, :, lanes] = h
            if not wave:
                h_scr[0, :, lanes] = h
            y_ref[pl.ds(r0, _GROUP), lanes] = tile
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n, _GROUP), body, None)

    @pl.when(tblk == pl.num_programs(2) - 1)
    def _store():
        h_out_ref[...] = h_scr[...]


def _selective_pallas(h_all, delta, u, b, c, a, layer, slots, valid, fresh,
                      *, interpret: bool, name: str):
    """The kernel's call. A chunk (``slots`` given): sequence ``s`` is slot
    ``slots[s]``, ``C`` rows. A wave (``slots`` None, ``C`` = 1): the
    slots are taken 8 at a time as the 8 rows of one sequence, so the
    operands are the chunk's with (slots / 8, 8) for (slots, rows)."""
    s, t, di = delta.shape
    n = b.shape[-1]
    wave = slots is None
    if wave:
        seqs, t = s // _GROUP, _GROUP
        rows_of = lambda m: m.reshape((seqs, t) + m.shape[2:])
        delta, u, b, c = rows_of(delta), rows_of(u), rows_of(b), rows_of(c)
        slots = jnp.arange(seqs, dtype=jnp.int32)   # of 8 slots each
        tb, bd = t, next(w for w in (1280, 1024, 512, 256, 128) if di % w == 0)
    else:
        seqs, tb = s, _time_block(t)
        bd = next(w for w in (512, 256, 128) if di % w == 0)
    held = _GROUP if wave else 1            # h's a block holds
    spread = lambda m: jnp.broadcast_to(m[..., None], (seqs, t, n, _LANES))

    def rows(i, j, k, *_):
        return (i, k, j)

    def spread_rows(i, j, k, *_):
        return (i, k, 0, 0)

    def state(i, j, k, layer_ref, slot_ref, *_):
        return (layer_ref[0], slot_ref[i], 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(seqs, di // bd, t // tb),
        in_specs=[
            pl.BlockSpec((None, tb, bd), rows),                 # delta
            pl.BlockSpec((None, tb, bd), rows),                 # u
            pl.BlockSpec((None, tb, n, _LANES), spread_rows),   # bx
            pl.BlockSpec((None, tb, n, _LANES), spread_rows),   # cx
            pl.BlockSpec((n, bd), lambda i, j, k, *_: (0, j)),  # a
            pl.BlockSpec((None, held, n, bd), state),           # h in
        ],
        out_specs=[
            pl.BlockSpec((None, tb, bd), rows),                 # y
            pl.BlockSpec((None, held, n, bd), state),           # h out
        ],
        scratch_shapes=[pltpu.VMEM((held, n, bd), jnp.float32)],
    )
    y, h_all = pl.pallas_call(
        functools.partial(_ssm_kernel, tb=tb, wave=wave),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(delta.shape, jnp.float32),
                   jax.ShapeDtypeStruct(h_all.shape, h_all.dtype)],
        # The state array is read and written where it lies: operand 9
        # (after the four prefetched scalars) is output 1.
        input_output_aliases={9: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      valid.astype(jnp.int32), fresh.astype(jnp.int32),
      delta, u, spread(b), spread(c), a, h_all)
    return y.reshape(s, -1, di), h_all


def _selective_xla(h_all, delta, u, b, c, a, layer, slots, valid, fresh):
    """The portable recurrence: a ``lax.scan`` over the chunk's rows."""
    s, t, _ = delta.shape
    slots = jnp.arange(s, dtype=jnp.int32) if slots is None else slots
    h0 = h_all[layer, slots]                                  # (S, N, Di)
    h0 = jnp.where(fresh[:, None, None], jnp.zeros_like(h0), h0)
    live = jnp.arange(t, dtype=jnp.int32)[:, None] < valid[None, :]  # (T, S)

    def step(h, xs):
        dt, ut, bt, ct, on = xs
        h2, y = _advance(h, dt[:, None], ut[:, None], bt[..., None],
                         ct[..., None], a)
        on = on[:, None, None]
        return jnp.where(on, h2, h), jnp.where(on, y, 0.0)[:, 0]

    time_major = lambda m: jnp.moveaxis(m, 1, 0)
    h_t, ys = jax.lax.scan(
        step, h0, (time_major(delta), time_major(u), time_major(b),
                   time_major(c), live),
    )
    return jnp.moveaxis(ys, 0, 1), h_all.at[layer, slots].set(h_t)


def _selective(h_all, delta, u, b, c, a, layer, slots, valid, fresh, *,
               kernel: bool, interpret: bool, name: str):
    if kernel:
        return _selective_pallas(h_all, delta, u, b, c, a, layer, slots,
                                 valid, fresh, interpret=interpret, name=name)
    return _selective_xla(h_all, delta, u, b, c, a, layer, slots, valid, fresh)


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"))
def ssm_scan(h_all, delta, u, b, c, a, layer, slots, valid, fresh, *,
             kernel: bool = False, interpret: bool = False):
    """The recurrence over a CHUNK of rows, from each slot's carried ``h``.

    ``h_all`` ``(state layers, max_slots, N, Di)`` float32, the whole state
    array, read and written at ``(layer, slots[s])``; ``delta``, ``u``
    ``(S, C, Di)`` float32; ``b``, ``c`` ``(S, C, N)`` float32; ``a``
    ``(N, Di)``; ``valid`` ``(S,)`` int32 — the first ``valid[s]`` rows
    advance ``h``, the rest nothing; ``fresh`` ``(S,)`` bool — start from
    zeros. Returns ``(y (S, C, Di) float32, h_all')``. ``kernel``: the
    Pallas kernel (a TPU; interpreted with ``interpret``), else a
    ``lax.scan``."""
    return _selective(h_all, delta, u, b, c, a, layer, slots, valid, fresh,
                      kernel=kernel, interpret=interpret, name="ssm_scan")


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"))
def ssm_step(h_all, delta, u, b, c, a, layer, valid, fresh, *,
             kernel: bool = False, interpret: bool = False):
    """:func:`ssm_scan` for a decode WAVE: one row (``C`` = 1) for every
    slot, row ``s`` being slot ``s``."""
    return _selective(h_all, delta, u, b, c, a, layer, None, valid, fresh,
                      kernel=kernel, interpret=interpret, name="ssm_step")


# -- the layer ----------------------------------------------------------------

def causal_conv(conv_all, u, w, layer, slots, valid, fresh):
    """The depthwise causal convolution of a chunk ``u`` (S, C, W) behind
    each slot's carried tail, shared by the state mixers (``nn/gdn.py``
    too): ``conv_all`` ``(state layers, max_slots, taps * W)`` holds every
    slot's last ``taps = len(w) - 1`` inputs side by side, read and written
    at ``(layer, slots[s])`` (zeros where ``fresh``). Returns ``(the sums
    (S, C, W) float32 — before any bias or activation — and conv_all')``:
    a slot's new tail is its last ``taps`` inputs before row ``valid[s]``
    (with nothing real in the chunk, the tail as it was)."""
    s, t, width = u.shape
    taps = w.shape[0] - 1
    tail = conv_all[layer, slots]                               # (S, taps*W)
    tail = jnp.where(fresh[:, None], jnp.zeros_like(tail), tail)
    window = jnp.concatenate(
        [tail[:, None, k * width:(k + 1) * width] for k in range(taps)]
        + [u.astype(tail.dtype)], axis=1)                       # (S, taps+C, W)
    w = w.astype(jnp.float32)
    out = sum(w[k] * window[:, k:k + t] for k in range(taps + 1))
    tail = jax.vmap(
        lambda rows, v: jax.lax.dynamic_slice_in_dim(rows, v, taps)
    )(window, valid).reshape(s, taps * width)
    return out, conv_all.at[layer, slots].set(tail)


class MambaMixer(Layer):
    """The mixer of the module docstring. Parameters: ``in_proj`` ``{w (D,
    2 Di)}``, ``conv`` ``{w (d_conv, Di), b (Di,)}``, ``x_proj`` ``{w (Di,
    dt_rank + 2 N)}``, ``dt_norm`` / ``b_norm`` / ``c_norm`` ``{scale}``,
    ``dt_proj`` ``{w (dt_rank, Di), b (Di,)}``, ``a_log`` ``(N, Di)``
    (``d_inner`` on the lane axis, like the state), ``d`` ``(Di,)``,
    ``out_proj`` ``{w (Di, D)}``."""

    def __init__(self, features: int, config: SSMConfig, *,
                 norm_eps: float = 1e-6):
        c = config
        self.features = features
        self.config = c
        self.in_proj = Dense(features, 2 * c.d_inner, use_bias=c.proj_bias)
        self.x_proj = Dense(c.d_inner, c.dt_rank + 2 * c.d_state, use_bias=False)
        self.dt_proj = Dense(c.dt_rank, c.d_inner, use_bias=True)
        self.out_proj = Dense(c.d_inner, features, use_bias=c.proj_bias)
        self.norms = {
            "dt_norm": RMSNorm(c.dt_rank, eps=norm_eps),
            "b_norm": RMSNorm(c.d_state, eps=norm_eps),
            "c_norm": RMSNorm(c.d_state, eps=norm_eps),
        }

    def init_params(self, key):
        c = self.config
        ks = jax.random.split(key, 6)
        dense = lambda layer, k: layer.init(k)["params"]
        # dt_proj's bias: softplus^-1 of a step drawn log-uniform in
        # [1e-3, 1e-1]; a_log: log(1..N) for every channel (Mamba's own).
        dt = jnp.exp(jax.random.uniform(ks[4], (c.d_inner,), jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        dt_proj = dense(self.dt_proj, ks[3])
        dt_proj["b"] = dt + jnp.log(-jnp.expm1(-dt))
        params = {
            "in_proj": dense(self.in_proj, ks[0]),
            "conv": {
                "w": jax.random.normal(ks[1], (c.d_conv, c.d_inner), jnp.float32)
                * c.d_conv ** -0.5,
            },
            "x_proj": dense(self.x_proj, ks[2]),
            "dt_proj": dt_proj,
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, c.d_state + 1, dtype=jnp.float32))[:, None],
                (c.d_state, c.d_inner)),
            "d": jnp.ones((c.d_inner,), jnp.float32),
            "out_proj": dense(self.out_proj, ks[5]),
        }
        if c.conv_bias:
            params["conv"]["b"] = jnp.zeros((c.d_inner,), jnp.float32)
        for name, norm in self.norms.items():
            params[name] = norm.init_params(None)
        return params

    def _sub(self, layer, p, x):
        return layer.apply({"params": p, "state": {}}, x)[0]

    def apply(self, variables, x, *, mode="train", rng=None):
        """The whole sequence ``x`` (B, T, D) from a zero state: a chunk
        of ``T`` rows with nothing carried in or out. Always the
        ``lax.scan`` recurrence (it is differentiated in training)."""
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

        ``x`` (S, C, D); ``state`` ``(h_all, conv_all)`` — the WHOLE state
        arrays (module docstring), read and written at ``(layer,
        slots[s])`` (``slots`` None: slot ``s`` is row ``s``);
        ``positions`` (S,) — a slot whose chunk starts at position 0 and
        has a real row starts from zeros, whatever its arrays hold;
        ``valid`` (S,) — the rows that are real. Returns ``(out (S, C, D),
        state')``; ``out`` rows past ``valid`` are garbage the caller
        ignores. ``kernel`` None: the Pallas kernel wherever it runs (a
        TPU and :func:`ssm_kernel_supported`)."""
        p, c = params, self.config
        h_all, conv_all = state
        s, t, _ = x.shape
        di, n = c.d_inner, c.d_state
        wave = slots is None and t == 1
        slots = jnp.arange(s, dtype=jnp.int32) if slots is None else slots
        fresh = (positions == 0) & (valid > 0)

        with jax.named_scope("ssm/in_proj"):
            uz = self._sub(self.in_proj, p["in_proj"], x)
            u, z = uz[..., :di], uz[..., di:]
        with jax.named_scope("ssm/conv"):
            u, conv_all = causal_conv(
                conv_all, u, p["conv"]["w"], layer, slots, valid, fresh)
            if c.conv_bias:
                u = u + p["conv"]["b"].astype(jnp.float32)
            u = jax.nn.silu(u).astype(x.dtype)
        with jax.named_scope("ssm/scan"):
            dbc = self._sub(self.x_proj, p["x_proj"], u)
            parts = {"dt_norm": dbc[..., :c.dt_rank],
                     "b_norm": dbc[..., c.dt_rank:c.dt_rank + n],
                     "c_norm": dbc[..., c.dt_rank + n:]}
            for name, norm in self.norms.items():
                parts[name] = self._sub(norm, p[name], parts[name])
            delta = jax.nn.softplus(self._sub(
                self.dt_proj, p["dt_proj"], parts["dt_norm"]
            ).astype(jnp.float32))
            if kernel is None:
                kernel = not _on_cpu() or interpret
            fits = ssm_kernel_supported(di, n, s if wave else t, wave)
            if kernel and not fits and not _on_cpu():
                # Several times the kernel's cost, and the trace then
                # holds no ``ssm_scan`` / ``ssm_step`` kernel event.
                warnings.warn(
                    f"MambaMixer: the Pallas kernel does not take d_inner "
                    f"{di}, d_state {n}, {s if wave else t} "
                    f"{'slots' if wave else 'rows'}: the recurrence runs as "
                    "a lax.scan over tokens", stacklevel=2)
            kernel = bool(kernel) and fits
            operands = (
                h_all, delta, u.astype(jnp.float32),
                parts["b_norm"].astype(jnp.float32),
                parts["c_norm"].astype(jnp.float32),
                -jnp.exp(p["a_log"].astype(jnp.float32)), layer,
            )
            how = dict(kernel=kernel, interpret=bool(interpret) or _on_cpu())
            if wave:
                y, h_all = ssm_step(*operands, valid, fresh, **how)
            else:
                y, h_all = ssm_scan(*operands, slots, valid, fresh, **how)
            y = y + p["d"].astype(jnp.float32) * u
        with jax.named_scope("ssm/out"):
            y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
            out = self._sub(self.out_proj, p["out_proj"], y)
        return out, (h_all, conv_all)

    def __repr__(self):
        c = self.config
        return (f"MambaMixer(d={self.features}, inner={c.d_inner}, "
                f"state={c.d_state}, conv={c.d_conv})")
