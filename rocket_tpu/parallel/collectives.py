"""Overlapped collective matmuls for tensor parallelism (+ shims).

``parallel/sharding.py`` declares WHERE params live and lets XLA GSPMD
insert the communication. That program is correct but synchronous: on
the TP layouts every layer pays a full-activation ``all-reduce`` that
blocks the MXU (sched_audit priced the unoverlapped tp_1x8 step at
~120 us of exposed comm — 14.2 MB of fp32 collectives; the bench
``overlap_summary`` re-measures the on/off diff every run). This module
makes the TP communication explicit so it can

* **restructure**: the Megatron-style all-reduce pairs become an
  all-gather into the column-parallel matmul and a reduce-scatter out of
  the row-parallel one, with the residual stream kept SEQUENCE-SHARDED
  over the TP axis between blocks (norms/residual adds run on 1/n of the
  tokens, and each collective moves half an all-reduce's bytes);
* **pipeline**: above a chunk-size threshold the gather/scatter runs as
  a ``ppermute`` ring fused chunk-by-chunk into the matmul
  (``ops/ring.py`` owns the index math) — each ICI hop overlaps the
  previous chunk's partial product, which is what hides the remaining
  bytes behind compute on real hardware;
* **compress**: backward-pass rings carry *gradients*, and gradients
  tolerate a narrower wire: they cross ICI in ``ROCKET_TPU_OVERLAP_WIRE``
  (bf16 by default) while params stay fp32 masters. The narrowing is
  DELIBERATE and visible: wire casts sit under a ``ring_wire`` named
  scope so ``prec_audit`` RKT403 sees them, and the audited steps certify
  them via ``@certify_collectives`` instead of suppressing the rule.

Numerics contract (pinned in ``tests/test_collectives.py``):

* fp32 ``all_gather_matmul`` is **bitwise identical** to
  gather-then-matmul in both ring and bulk modes (chunk re-ordering is a
  pure gather — no arithmetic is reassociated);
* bulk ``matmul_reduce_scatter`` is **bitwise identical** to the
  einsum+psum reference (XLA's reduce-scatter and all-reduce share the
  reduction order); the ring form reassociates the cross-device sum and
  is allclose;
* ``ROCKET_TPU_OVERLAP=0`` disables every path here, restoring the
  exact pre-overlap GSPMD program.

The context (:func:`tp_overlap`) is installed by ``core/module.py`` when
the model's ``param_sharding`` rule set carries the ``tp_axis`` marker
(``gpt2_tp_rules`` sets it); layers consult :func:`current_tp` at trace
time and fall back to the plain GSPMD path whenever the context is
absent, disabled, or the shapes don't divide.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass, replace
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from rocket_tpu.ops import ring as ring_lib

__all__ = [
    "pvary_compat",
    "OverlapSpec",
    "overlap_enabled",
    "grad_wire_dtype",
    "tp_overlap",
    "current_tp",
    "all_gather_matmul",
    "matmul_reduce_scatter",
    "embed_lookup_sharded",
    "seq_all_gather",
    "seq_shard",
]

P = jax.sharding.PartitionSpec


def pvary_compat(x, axes):
    """Mark ``x`` as device-varying over ``axes`` (vma typing for scan
    carries inside shard_map). Idempotent: axes the value already varies
    over are skipped (pcast rejects varying->varying)."""
    current = set(jax.typeof(x).vma)
    for axis in axes:
        if axis not in current:
            x = jax.lax.pcast(x, axis, to="varying")
    return x


# -- the overlap context -----------------------------------------------------


def overlap_enabled() -> bool:
    """``ROCKET_TPU_OVERLAP=0`` is the operational escape hatch: it
    restores the exact pre-overlap GSPMD program (read at trace time)."""
    return os.environ.get("ROCKET_TPU_OVERLAP", "1") != "0"


def grad_wire_dtype():
    """Wire dtype for gradient-carrying collectives, from
    ``ROCKET_TPU_OVERLAP_WIRE`` (default bf16; ``fp32``/``off`` disable
    the compression). Forward activations NEVER compress — only values
    flowing into gradients cross narrow."""
    name = os.environ.get("ROCKET_TPU_OVERLAP_WIRE", "bfloat16").lower()
    if name in ("fp32", "f32", "float32", "off", "none", ""):
        return None
    return jnp.dtype(name)


@dataclass(frozen=True)
class OverlapSpec:
    """One activated TP-overlap configuration (hashable: it is a
    ``custom_vjp`` nondiff argument).

    ``axis`` is the TP mesh axis (``gpt2_tp_rules``' model axis);
    ``data_axes`` the batch axes the leading activation dim is sharded
    over; ``wire`` the gradient wire dtype name (forward activations
    always cross at their own dtype); ``mode``/``min_ring_bytes`` pick
    ring vs bulk per collective (``ops.ring.use_ring``).
    """

    mesh: jax.sharding.Mesh
    axis: str
    data_axes: Tuple[str, ...] = ("data",)
    wire: Optional[str] = "bfloat16"
    mode: str = "auto"
    min_ring_bytes: int = 1 << 20
    vocab_sharded_embed: bool = False

    @property
    def tp_size(self) -> int:
        return int(self.mesh.shape[self.axis])

    def wire_dtype(self):
        return None if self.wire is None else jnp.dtype(self.wire)

    def batch_axes_for(self, dim0: int) -> Tuple[str, ...]:
        """Data axes to put on the leading dim — only those present in
        the mesh and dividing it (else the dim stays unsharded)."""
        axes = tuple(a for a in self.data_axes if a in self.mesh.shape)
        n = int(np.prod([self.mesh.shape[a] for a in axes])) if axes else 1
        return axes if n > 1 and dim0 % n == 0 else ()

    def seq_divisible(self, t: int) -> bool:
        return t % self.tp_size == 0


_ACTIVE = threading.local()


@contextmanager
def tp_overlap(
    mesh: jax.sharding.Mesh,
    axis: str = "model",
    data_axes: Tuple[str, ...] = ("data",),
    wire: Optional[str] = "__env__",
    mode: str = "auto",
    min_ring_bytes: int = 1 << 20,
    vocab_sharded_embed: bool = False,
):
    """Activate the overlapped-collective context for the enclosed trace.

    A no-op (plain GSPMD program) when ``ROCKET_TPU_OVERLAP=0``, when
    ``axis`` is missing from the mesh or has size 1, or when tracing
    already inside a ``shard_map`` binding mesh axes (a pipeline stage
    body — nesting would be an error)."""
    if (
        not overlap_enabled()
        or axis not in mesh.shape
        or int(mesh.shape[axis]) <= 1
    ):
        yield None
        return
    if wire == "__env__":
        wd = grad_wire_dtype()
        wire = None if wd is None else str(wd)
    spec = OverlapSpec(
        mesh=mesh, axis=axis, data_axes=tuple(data_axes), wire=wire,
        mode=mode, min_ring_bytes=min_ring_bytes,
        vocab_sharded_embed=vocab_sharded_embed,
    )
    prev = getattr(_ACTIVE, "spec", None)
    _ACTIVE.spec = spec
    try:
        yield spec
    finally:
        _ACTIVE.spec = prev


def current_tp() -> Optional[OverlapSpec]:
    """The active :class:`OverlapSpec`, or None. Re-checks the kill
    switch and the manual-axes guard at every use so a context installed
    around an outer trace never leaks into a nested shard_map body."""
    spec = getattr(_ACTIVE, "spec", None)
    if spec is None or not overlap_enabled():
        return None
    from rocket_tpu.ops.flash_attention import in_manual_axes

    if in_manual_axes(spec.mesh.axis_names):
        return None
    return spec


# -- spec plumbing -----------------------------------------------------------


def _bspec(spec: OverlapSpec, dim0: int, *rest):
    """PartitionSpec with the leading dim over the data axes (when they
    divide) and ``rest`` on the remaining dims."""
    axes = spec.batch_axes_for(dim0)
    return P(axes if axes else None, *rest)


def _numel(shape) -> int:
    n = 1
    for dim in shape or ():
        n *= dim
    return n


def _cast(x, dtype):
    return x if dtype is None or x.dtype == dtype else x.astype(dtype)


def _wire_narrow(spec: OverlapSpec, x, scope: str = "ring_wire"):
    """Cast a gradient-carrying value to the wire dtype under the named
    scope prec_audit certifications key on. Never widens."""
    wd = spec.wire_dtype()
    if wd is None or jnp.dtype(x.dtype).itemsize <= wd.itemsize:
        return x, x.dtype
    with jax.named_scope(scope):
        return x.astype(wd), x.dtype


def _wire_pack(spec: OverlapSpec, x, scope: str = "ring_wire"):
    """Narrow a gradient payload to the wire dtype AND bit-pack it into
    the same-width unsigned integer for the collective itself.

    The pack matters on two axes: the compiled HLO moves a 2-byte buffer
    on EVERY backend (the CPU fake mesh's float-normalization pass
    silently widens bf16 *float* collectives back to f32 — an audit over
    that HLO would never see the compression), and an integer payload
    can never be "helpfully" reassociated by a backend's collective
    rewrites. Returns ``(packed, orig_dtype, wire_dtype_or_None)``.
    ``grad_sync`` shares these helpers with its ``grad_buckets`` scope —
    ONE copy of the wire protocol.
    """
    wired, orig = _wire_narrow(spec, x, scope)
    if wired.dtype == orig:
        return wired, orig, None
    wd = wired.dtype
    carrier = jnp.dtype(f"uint{8 * wd.itemsize}")
    return jax.lax.bitcast_convert_type(wired, carrier), orig, wd


def _wire_unpack(packed, orig, wd, accum=None):
    """Inverse of :func:`_wire_pack`: bit-unpack and widen to ``accum``
    (default: the original dtype)."""
    if wd is None:
        return _cast(packed, accum or packed.dtype)
    return jax.lax.bitcast_convert_type(packed, wd).astype(accum or orig)


def _use_ring(spec: OverlapSpec, shard_bytes: int) -> bool:
    return ring_lib.use_ring(shard_bytes, spec.mode, spec.min_ring_bytes)


def _ring_gather_chunks(spec: OverlapSpec, chunk, on_chunk):
    """Drive the all-gather ring: call ``on_chunk(s, chunk)`` for every
    hop step (chunk held at step ``s`` is global chunk ``(d-s) % n``)."""
    n = spec.tp_size
    for s in range(n):
        on_chunk(s, chunk)
        if s < n - 1:
            chunk = jax.lax.ppermute(
                chunk, spec.axis, ring_lib.fwd_perm(n)
            )


def _reorder_to_global(spec: OverlapSpec, arrival_stack):
    """Arrival-order (n, ...) stack -> global chunk order. A pure gather
    (no arithmetic), so fused results stay bitwise."""
    d = jax.lax.axis_index(spec.axis)
    order = ring_lib.gather_order(d, spec.tp_size)
    return jnp.take(arrival_stack, order, axis=0)


def _merge_seq(stacked):
    """(n, B, Tc, F) global-ordered chunk stack -> (B, n*Tc, F)."""
    n, b, tc, f = stacked.shape
    return jnp.moveaxis(stacked, 0, 1).reshape(b, n * tc, f)


def _ring_reduce_scatter(spec: OverlapSpec, chunks, acc_dtype,
                         wire: bool = True):
    """Ring reduce-scatter over the chunk axis of ``chunks`` ((B, n,
    Tc, F), global order): returns this device's summed chunk.

    With ``wire=True`` (gradient rings) the accumulator crosses each hop
    bit-packed at the wire dtype but ACCUMULATES at ``acc_dtype`` on
    device — the fp32-master-side precision is spent only on the wire,
    not in the adds."""
    n = spec.tp_size
    d = jax.lax.axis_index(spec.axis)
    acc = jnp.take(chunks, ring_lib.rs_seed_index(d, n), axis=1)
    acc = _cast(acc, acc_dtype)
    wspec = spec if wire else replace(spec, wire=None)
    for s in range(1, n):
        packed, orig, wd = _wire_pack(wspec, acc)
        packed = jax.lax.ppermute(packed, spec.axis, ring_lib.fwd_perm(n))
        acc = _wire_unpack(packed, orig, wd, acc_dtype) + _cast(
            jnp.take(chunks, ring_lib.rs_chunk_index(d, s, n), axis=1),
            acc_dtype,
        )
    return acc


def _bulk_reduce_scatter(spec: OverlapSpec, chunks, wire: bool):
    """One bulk reduce-scatter over the chunk axis ((B, n, Tc, F) ->
    (B, Tc, F)).

    ``wire=False`` (forward activations): a ``psum_scatter`` at the
    operand dtype — bitwise-identical to ``psum`` (XLA's reduce-scatter
    and all-reduce share the reduction order). ``wire=True`` (gradient
    reductions): the chunks cross as a bit-packed all-to-all at the wire
    dtype and the sum runs LOCALLY at the operand dtype — same bytes as
    a reduce-scatter, wire-compressed payload, full-precision adds."""
    if not wire:
        return jax.lax.psum_scatter(
            chunks, spec.axis, scatter_dimension=1, tiled=False
        )
    out_dtype = chunks.dtype
    stacked = jnp.moveaxis(chunks, 1, 0)            # (n, B, Tc, F)
    packed, orig, wd = _wire_pack(spec, stacked)
    recv = jax.lax.all_to_all(
        packed, spec.axis, split_axis=0, concat_axis=0, tiled=False
    )
    vals = _wire_unpack(recv, orig, wd, out_dtype)
    return jnp.sum(vals, axis=0)


# -- all_gather_matmul -------------------------------------------------------
#
# y_i = all_gather_seq(x) @ w_i for one or more right-hand sides sharing
# ONE gather. x: (B, T, K) sequence-sharded over spec.axis; w_i: (K, F_i)
# column-sharded. Outputs (B, T, F_i) column-sharded. The backward runs
# the transposed ring: dx = reduce_scatter_seq(sum_i dy_i @ w_i^T) with
# the gradient crossing at the wire dtype, dw_i local (the gathered x is
# saved from forward).


def _agmm_fwd_sm(spec: OverlapSpec, x, ws):
    n = spec.tp_size
    b, t, k = x.shape
    # Threshold on the PER-DEVICE chunk (the batch dim is sharded over
    # the data axes inside the manual region) — the same basis every
    # backward uses, so fwd and bwd of one matmul agree on the mode.
    daxes = spec.batch_axes_for(b)
    b_local = b // int(np.prod([spec.mesh.shape[a] for a in daxes])) \
        if daxes else b
    shard_bytes = (b_local * (t // n) * k * x.dtype.itemsize)
    ringy = _use_ring(spec, shard_bytes)

    def body(xl, *wls):
        if ringy:
            parts = [[] for _ in wls]
            xchunks = []

            def on_chunk(s, chunk):
                xchunks.append(chunk)
                for i, wl in enumerate(wls):
                    parts[i].append(chunk @ wl)

            _ring_gather_chunks(spec, xl, on_chunk)
            xg = _merge_seq(_reorder_to_global(spec, jnp.stack(xchunks)))
            ys = tuple(
                _merge_seq(_reorder_to_global(spec, jnp.stack(p)))
                for p in parts
            )
        else:
            xg = jax.lax.all_gather(xl, spec.axis, axis=1, tiled=True)
            ys = tuple(xg @ wl for wl in wls)
        return ys + (xg,)

    w_specs = tuple(P(None, spec.axis) for _ in ws)
    out_specs = tuple(_bspec(spec, b, None, spec.axis) for _ in ws)
    fn = shard_map(
        body, mesh=spec.mesh,
        in_specs=(_bspec(spec, b, spec.axis, None),) + w_specs,
        out_specs=out_specs + (_bspec(spec, b, None, None),),
        check_vma=False,
    )
    outs = fn(x, *ws)
    return tuple(outs[:-1]), outs[-1]


def _agmm_bwd_sm(spec: OverlapSpec, xg, ws, dys):
    n = spec.tp_size
    b, t, k = xg.shape

    def body(xgl, *wls_dyls):
        wls, dyls = wls_dyls[: len(ws)], wls_dyls[len(ws):]
        partial = None
        dwls = []
        for wl, dyl in zip(wls, dyls):
            term = dyl @ wl.T
            partial = term if partial is None else partial + term
            dwls.append(
                jnp.einsum("btk,btf->kf", xgl, dyl)
            )
        chunks = partial.reshape(partial.shape[0], n, t // n, k)
        shard_bytes = chunks.shape[0] * (t // n) * k * partial.dtype.itemsize
        if _use_ring(spec, shard_bytes):
            dx = _ring_reduce_scatter(spec, chunks, partial.dtype)
        else:
            dx = _bulk_reduce_scatter(spec, chunks, wire=True)
        # Weight grads were computed from this device's BATCH shard
        # only: sum over the data axes (the out_specs declare them
        # replicated there — without this psum a data-parallel TP mesh
        # would silently drop the other replicas' contributions).
        daxes = spec.batch_axes_for(b)
        if daxes:
            dwls = [jax.lax.psum(dw, daxes) for dw in dwls]
        return (dx,) + tuple(dwls)

    fn = shard_map(
        body, mesh=spec.mesh,
        in_specs=(_bspec(spec, b, None, None),)
        + tuple(P(None, spec.axis) for _ in ws)
        + tuple(_bspec(spec, b, None, spec.axis) for _ in ws),
        out_specs=(_bspec(spec, b, spec.axis, None),)
        + tuple(P(None, spec.axis) for _ in ws),
        check_vma=False,
    )
    outs = fn(xg, *ws, *dys)
    return outs[0], tuple(outs[1:])


def all_gather_matmul(spec: OverlapSpec, x, ws: Sequence):
    """``tuple(all_gather_seq(x) @ w for w in ws)`` with one shared
    gather — ring-pipelined above the chunk threshold, one bulk
    all-gather below it. Differentiable (custom_vjp: transposed ring,
    gradient wire compression)."""

    ws = tuple(ws)

    @jax.custom_vjp
    def _agmm(x, ws):
        ys, _xg = _agmm_fwd_sm(spec, x, ws)
        return ys

    def _fwd(x, ws):
        ys, xg = _agmm_fwd_sm(spec, x, ws)
        return ys, (xg, ws)

    def _bwd(res, dys):
        xg, ws = res
        dx, dws = _agmm_bwd_sm(spec, xg, ws, tuple(dys))
        return dx, dws

    _agmm.defvjp(_fwd, _bwd)
    return _agmm(x, ws)


# -- matmul_reduce_scatter ---------------------------------------------------
#
# y = reduce_scatter_seq(x @ w): x (B, T, K) column-sharded over
# spec.axis (a row-parallel layer's input — e.g. head-sharded attention
# output), w (K, D) row-sharded. Output (B, T, D) sequence-sharded. The
# forward reduction runs at the ACTIVATION dtype (never compressed); the
# backward gathers dy at the wire dtype and computes dx and dw from the
# one gathered copy.


def _mmrs_fwd_sm(spec: OverlapSpec, x, w, bias=None):
    n = spec.tp_size
    b, t, _k = x.shape
    d_out = w.shape[1]

    def body(xl, wl, *bl):
        partial = xl @ wl                       # (B, T, D) local partial
        chunks = partial.reshape(partial.shape[0], n, t // n, d_out)
        shard_bytes = (
            partial.shape[0] * (t // n) * d_out * partial.dtype.itemsize
        )
        if _use_ring(spec, shard_bytes):
            # Forward ring: accumulate AND cross at the activation dtype
            # (spec.wire applies to gradients only).
            out = _ring_reduce_scatter(
                spec, chunks, partial.dtype, wire=False
            )
        else:
            out = _bulk_reduce_scatter(spec, chunks, wire=False)
        if bl:
            # The bias is added AFTER the reduction (once, not n times)
            # on the local sequence shard — same math as bias-after-psum.
            out = out + bl[0]
        return out

    bias_args = () if bias is None else (bias,)
    fn = shard_map(
        body, mesh=spec.mesh,
        in_specs=(_bspec(spec, b, None, spec.axis), P(spec.axis, None))
        + ((P(None),) if bias is not None else ()),
        out_specs=_bspec(spec, b, spec.axis, None),
        check_vma=False,
    )
    return fn(x, w, *bias_args)


def _mmrs_bwd_sm(spec: OverlapSpec, x, w, dy):
    n = spec.tp_size
    b = x.shape[0]
    t = x.shape[1]

    def body(xl, wl, dyl):
        packed, orig, wd = _wire_pack(spec, dyl)
        shard_bytes = _numel(packed.shape) * packed.dtype.itemsize
        if _use_ring(spec, shard_bytes):
            parts = []
            chunks = []

            def on_chunk(s, chunk):
                chunk = _wire_unpack(chunk, orig, wd)
                chunks.append(chunk)
                parts.append(chunk @ wl.T)       # (B, Tc, K_l) rows

            _ring_gather_chunks(spec, packed, on_chunk)
            dxl = _merge_seq(_reorder_to_global(spec, jnp.stack(parts)))
            dy_full = _merge_seq(_reorder_to_global(spec, jnp.stack(chunks)))
        else:
            dy_full = _wire_unpack(
                jax.lax.all_gather(packed, spec.axis, axis=1, tiled=True),
                orig, wd,
            )
            dxl = dy_full @ wl.T
        dwl = jnp.einsum("btk,btd->kd", xl, dy_full)
        # The bias gradient is a local sum over the gathered dy —
        # gathered over the TP axis only, so like dw it still needs
        # the sum over the data axes (batch-shard contributions).
        dbl = jnp.einsum("btd->d", dy_full)
        daxes = spec.batch_axes_for(b)
        if daxes:
            dwl = jax.lax.psum(dwl, daxes)
            dbl = jax.lax.psum(dbl, daxes)
        return dxl, dwl, dbl

    fn = shard_map(
        body, mesh=spec.mesh,
        in_specs=(
            _bspec(spec, b, None, spec.axis),
            P(spec.axis, None),
            _bspec(spec, b, spec.axis, None),
        ),
        out_specs=(
            _bspec(spec, b, None, spec.axis),
            P(spec.axis, None),
            P(None),
        ),
        check_vma=False,
    )
    return fn(x, w, dy)


def matmul_reduce_scatter(spec: OverlapSpec, x, w, bias=None):
    """``reduce_scatter_seq(x @ w) (+ bias)`` — the row-parallel matmul
    fused with its output reduction. Bulk mode is bitwise-identical to
    einsum+psum; ring mode reassociates the cross-device sum (allclose).
    Passing the (replicated) ``bias`` through lets the backward compute
    its gradient from the already-gathered dy — locally, with no
    collective. Differentiable (custom_vjp: transposed gather ring,
    gradient wire compression)."""

    if bias is None:

        @jax.custom_vjp
        def _mmrs(x, w):
            return _mmrs_fwd_sm(spec, x, w)

        def _fwd(x, w):
            return _mmrs_fwd_sm(spec, x, w), (x, w)

        def _bwd(res, dy):
            x, w = res
            dx, dw, _db = _mmrs_bwd_sm(spec, x, w, dy)
            return dx, dw

        _mmrs.defvjp(_fwd, _bwd)
        return _mmrs(x, w)

    bias_dtype = bias.dtype

    @jax.custom_vjp
    def _mmrs_b(x, w, bias):
        return _mmrs_fwd_sm(spec, x, w, bias)

    def _fwd_b(x, w, bias):
        return _mmrs_fwd_sm(spec, x, w, bias), (x, w)

    def _bwd_b(res, dy):
        x, w = res
        dx, dw, db = _mmrs_bwd_sm(spec, x, w, dy)
        return dx, dw, db.astype(bias_dtype)

    _mmrs_b.defvjp(_fwd_b, _bwd_b)
    return _mmrs_b(x, w, bias)


# -- fused-QKV weight views --------------------------------------------------


def qkv_fused_views(spec: OverlapSpec, w, b, hw: int, kvw: int):
    """Head-aligned views of a fused ``[q | k | v]`` projection weight.

    The fused kernel is STORED contiguous (checkpoint layout) and
    sharded contiguous by ``gpt2_tp_rules`` — but the overlapped
    attention consumes per-head q/k/v slices, and global slicing makes
    GSPMD reshard every slice every step (~17 tiny collective-permutes
    per layer per direction, each paying launch latency). Here ONE
    all-gather rebuilds the full kernel per device (the bias rides as an
    extra row — no separate collective) and each device slices its
    heads' q/k/v columns locally; the backward scatters the head-aligned
    gradients straight back onto the contiguous shards with ONE
    reduce-scatter (each fused column has exactly one contributor, so
    the sum is exact placement, not arithmetic).

    Returns ``(wq, wk, wv, bq, bk, bv)`` — biases are None when ``b``
    is None.
    """
    n = spec.tp_size
    d_in = w.shape[0]
    fused = w if b is None else jnp.concatenate([w, b[None, :]], axis=0)
    rows = fused.shape[0]
    hq, hkv = hw // n, kvw // n

    def _fwd_sm(fused):
        def body(wl):
            d = jax.lax.axis_index(spec.axis)
            wf = jax.lax.all_gather(wl, spec.axis, axis=1, tiled=True)
            wq = jax.lax.dynamic_slice_in_dim(wf, d * hq, hq, 1)
            wk = jax.lax.dynamic_slice_in_dim(wf, hw + d * hkv, hkv, 1)
            wv = jax.lax.dynamic_slice_in_dim(
                wf, hw + kvw + d * hkv, hkv, 1
            )
            return wq, wk, wv

        return shard_map(
            body, mesh=spec.mesh,
            in_specs=P(None, spec.axis),
            out_specs=(P(None, spec.axis),) * 3,
            check_vma=False,
        )(fused)

    @jax.custom_vjp
    def _views(fused):
        return _fwd_sm(fused)

    def _fwd(fused):
        return _fwd_sm(fused), None

    def _bwd(_res, dviews):
        dwq, dwk, dwv = dviews

        def body(dq, dk, dv):
            d = jax.lax.axis_index(spec.axis)
            full = jnp.zeros((rows, hw + 2 * kvw), dq.dtype)
            full = jax.lax.dynamic_update_slice_in_dim(full, dq, d * hq, 1)
            full = jax.lax.dynamic_update_slice_in_dim(
                full, dk, hw + d * hkv, 1
            )
            full = jax.lax.dynamic_update_slice_in_dim(
                full, dv, hw + kvw + d * hkv, 1
            )
            chunks = full.reshape(rows, n, (hw + 2 * kvw) // n)
            out = jax.lax.psum_scatter(
                jnp.moveaxis(chunks, 1, 0), spec.axis,
                scatter_dimension=0, tiled=True,
            )
            return jnp.squeeze(out, 0)

        return (shard_map(
            body, mesh=spec.mesh,
            in_specs=(P(None, spec.axis),) * 3,
            out_specs=P(None, spec.axis),
            check_vma=False,
        )(dwq, dwk, dwv),)

    _views.defvjp(_fwd, _bwd)
    wq, wk, wv = _views(fused)
    if b is None:
        return wq, wk, wv, None, None, None
    return (wq[:-1], wk[:-1], wv[:-1], wq[-1], wk[-1], wv[-1])


# -- sequence-sharded embedding lookup ---------------------------------------


def embed_lookup_sharded(spec: OverlapSpec, table, tokens, compute_dtype=None):
    """Vocab-parallel embedding lookup emitting a SEQUENCE-SHARDED
    activation: each device gathers the rows of its vocab shard (misses
    masked to zero) and the partials reduce-scatter straight onto the
    sequence shards — half the wire bytes of the all-reduce GSPMD emits
    for gather-then-replicate, and the trunk downstream is already
    sequence-sharded.

    ``compute_dtype``: when the model computes in a narrower dtype the
    partials cross the wire in it (the table stays an fp32 master). That
    narrowing moves PARAM-origin values through a collective — exactly
    RKT403's target — and is certified per-path by the audited steps.
    """
    n = spec.tp_size
    b, t = tokens.shape
    v, _d = table.shape
    vl = v // n

    @jax.custom_vjp
    def _embed(table, tokens):
        return _fwd(table, tokens)[0]

    def _fwd(table, tokens):
        def body(tl, tok):
            dloc = jax.lax.axis_index(spec.axis)
            ids = tok - dloc * vl
            valid = (ids >= 0) & (ids < vl)
            rows = jnp.take(tl, jnp.clip(ids, 0, vl - 1), axis=0)
            rows = jnp.where(valid[..., None], rows, 0)
            if compute_dtype is not None:
                # Each row has exactly ONE nonzero contribution across
                # the axis, so reducing at the compute dtype equals
                # casting after the psum bitwise — but it narrows the
                # fp32 MASTER table on the wire: a deliberate,
                # certified compression (prec_audit RKT403 keys on the
                # embed_wire scope).
                with jax.named_scope("embed_wire"):
                    rows = rows.astype(compute_dtype)
            chunks = rows.reshape(rows.shape[0], n, t // n, rows.shape[-1])
            return jax.lax.psum_scatter(
                chunks, spec.axis, scatter_dimension=1, tiled=False
            )

        fn = shard_map(
            body, mesh=spec.mesh,
            in_specs=(P(spec.axis, None), _bspec(spec, b)),
            out_specs=_bspec(spec, b, spec.axis, None),
            check_vma=False,
        )
        return fn(table, tokens), (tokens,)

    def _vjp_fwd(table, tokens):
        y, res = _fwd(table, tokens)
        return y, res

    def _bwd(res, dy):
        (tokens,) = res

        def body(tok, dyl):
            dloc = jax.lax.axis_index(spec.axis)
            packed, orig, wd = _wire_pack(spec, dyl)
            dfull = jax.lax.all_gather(packed, spec.axis, axis=1, tiled=True)
            dfull = _wire_unpack(dfull, orig, wd, table.dtype)
            ids = tok - dloc * vl
            valid = (ids >= 0) & (ids < vl)
            upd = jnp.where(valid[..., None], dfull, 0)
            d_table = (
                jnp.zeros((vl, table.shape[1]), table.dtype)
                .at[jnp.clip(ids, 0, vl - 1).reshape(-1)]
                .add(upd.reshape(-1, table.shape[1]))
            )
            # Scatter covered this device's BATCH shard only — sum the
            # contributions over the data axes (dfull is gathered over
            # the TP axis alone).
            daxes = spec.batch_axes_for(b)
            if daxes:
                d_table = jax.lax.psum(d_table, daxes)
            return d_table

        fn = shard_map(
            body, mesh=spec.mesh,
            in_specs=(_bspec(spec, b), _bspec(spec, b, spec.axis, None)),
            out_specs=P(spec.axis, None),
            check_vma=False,
        )
        # Integer tokens take no cotangent; jax expects a float0 zero.
        return fn(tokens, dy), np.zeros(tokens.shape, jax.dtypes.float0)

    _embed.defvjp(_vjp_fwd, _bwd)
    return _embed(table, tokens)


# -- sequence-shard boundary helpers -----------------------------------------


def _sm_gather(spec: OverlapSpec, x, wire: bool):
    """shard_map: sequence-sharded -> full (a relayout, not a
    reduction). ``wire=True`` compresses the chunks crossing ICI (used
    on gradient-carrying relayouts only)."""
    b = x.shape[0]

    def body(xl):
        if wire:
            packed, orig, wd = _wire_pack(spec, xl)
            full = jax.lax.all_gather(packed, spec.axis, axis=1, tiled=True)
            return _wire_unpack(full, orig, wd)
        return jax.lax.all_gather(xl, spec.axis, axis=1, tiled=True)

    return shard_map(
        body, mesh=spec.mesh,
        in_specs=_bspec(spec, b, spec.axis, None),
        out_specs=_bspec(spec, b, None, None),
        check_vma=False,
    )(x)


def _sm_slice(spec: OverlapSpec, x):
    """shard_map: full (replicated over ``spec.axis``) -> sequence-
    sharded. Zero communication — each device keeps its rows."""
    b, t = x.shape[0], x.shape[1]
    n = spec.tp_size

    def body(xl):
        d = jax.lax.axis_index(spec.axis)
        return jax.lax.dynamic_slice_in_dim(xl, d * (t // n), t // n, 1)

    return shard_map(
        body, mesh=spec.mesh,
        in_specs=_bspec(spec, b, None, None),
        out_specs=_bspec(spec, b, spec.axis, None),
        check_vma=False,
    )(x)


def seq_all_gather(spec: OverlapSpec, x):
    """Gather a sequence-sharded activation back to full length (a
    boundary op for paths that need every token locally — MoE routing,
    the fused-loss scan). Globally this is a RELAYOUT: the transpose is
    the zero-communication slice, not a reduction (the cotangent is one
    global tensor, already aggregated)."""

    @jax.custom_vjp
    def _ag(x):
        return _sm_gather(spec, x, wire=False)

    def _fwd(x):
        return _ag(x), None

    def _bwd(_res, dy):
        return (_sm_slice(spec, dy),)

    _ag.defvjp(_fwd, _bwd)
    return _ag(x)


def seq_shard(spec: OverlapSpec, x):
    """Pin a (replicated-over-``spec.axis``) activation to the
    sequence-sharded layout — a zero-communication slice forward; the
    backward reassembles the gradient by an all-gather relayout at the
    wire dtype (each chunk crosses ICI once)."""

    @jax.custom_vjp
    def _shard(x):
        return _sm_slice(spec, x)

    def _fwd(x):
        return _shard(x), None

    def _bwd(_res, dy):
        return (_sm_gather(spec, dy, wire=True),)

    _shard.defvjp(_fwd, _bwd)
    return _shard(x)
