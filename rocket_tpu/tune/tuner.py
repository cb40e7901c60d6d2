"""The offline search loop: sweep legal configs, time them with compile
excluded, reject numerical-parity failures, persist winners.

``python -m rocket_tpu.tune`` drives this on a real accelerator. Per
:class:`TuneCase` (a kernel at one representative bench shape):

1. every LEGAL config from the kernel's TuneSpace is enumerated
   (``TuneSpace.candidates`` — illegal configs are never built, and the
   kernels themselves fail fast on e.g. causal ``block_q != block_k``);
2. the DEFAULT config runs first (passed explicitly, with every table
   lookup disabled for the whole sweep — an existing entry must not
   stand in for the default on a previously tuned device): its output is
   the parity reference and its time the speedup denominator;
3. each candidate is jit-compiled, warmed up (compile excluded), timed
   over ``iters`` calls with ``jax.block_until_ready`` at the window
   edges (calls run in order on the device, so the last one's outputs
   being ready means the window is done — and unlike a host fetch it
   copies nothing inside the window), and parity-checked
   against the default's outputs within dtype tolerance. **A faster
   wrong kernel is a rejected candidate** — parity failures never enter
   the ranking;
4. the best surviving candidate becomes a table entry only when its
   speedup over the default exceeds ``min_speedup`` (default 2%) — a
   within-noise "win" must not churn the checked-in table.

On hardware where the search finds no win the table simply carries no
entry for that (kernel, shape, device kind) and the runtime lookup falls
back to the default — behavior-identical to an untuned checkout.

CPU has no Mosaic: the pallas cases would run interpreted, orders of
magnitude off, so timing there is meaningless. ``--allow-cpu`` runs a
small smoke subset (interpret mode, 1 iteration) purely to exercise the
loop; ``--update-table`` is refused off-accelerator.
"""

from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass, field
from typing import Callable, Mapping, Optional

import jax
import numpy as np

from rocket_tpu.tune.space import TUNE_SPACES, canonical_dtype
from rocket_tpu.tune.table import tuning_disabled, write_table
from rocket_tpu.utils.perf import device_spec

__all__ = [
    "TuneCase", "CandidateResult", "CaseReport", "TUNE_CASES",
    "check_parity", "sweep_case", "run_cases", "entries_from_reports",
]

#: Parity tolerance per canonical dtype: |tuned - default| <=
#: atol + rtol * |default|, elementwise over every output leaf (fwd
#: outputs AND backward grads — both must match for a config to ship).
#: A kernel whose variants legitimately reassociate f32 reductions can
#: widen its own bound via ``TuneSpace.parity_tol`` (fused_conv does);
#: the defaults here stay tight for every launch-config sweep.
_PARITY_TOL = {
    "bfloat16": (2e-2, 2e-2),
    "float16": (2e-2, 2e-2),
    "float32": (1e-5, 1e-5),
}


@dataclass(frozen=True)
class TuneCase:
    """One kernel at one representative shape.

    ``build()`` returns ``run(config) -> pytree``: a closure over
    freshly-built operands that executes the kernel under the EXPLICIT
    ``config`` dict (the sweep always passes one — the baseline is the
    TuneSpace default, never ``None``-resolved through the table). The
    closure must compile each distinct config ONCE and reuse the
    compiled callable across calls (memoized ``jax.jit`` below), so
    ``_time_run``'s warmed iterations measure the kernel, not retracing.
    The returned pytree is both the parity surface and the timing
    payload.
    """

    name: str
    kernel: str
    shape: Mapping
    dtype: str
    build: Callable[[], Callable[[Optional[dict]], object]]
    #: small enough to run interpreted on CPU for the --allow-cpu smoke
    smoke: bool = False


@dataclass
class CandidateResult:
    config: dict
    mean_us: Optional[float] = None
    parity_ok: bool = True
    max_err: float = 0.0
    error: Optional[str] = None


@dataclass
class CaseReport:
    case: TuneCase
    device_kind: str
    default_config: dict = field(default_factory=dict)
    default_us: Optional[float] = None
    results: list = field(default_factory=list)
    winner: Optional[CandidateResult] = None

    @property
    def speedup(self) -> Optional[float]:
        if self.winner is None or not self.winner.mean_us or \
                not self.default_us:
            return None
        return self.default_us / self.winner.mean_us


def _time_run(fn, iters: int) -> float:
    """Mean microseconds per call, compile and warmup excluded."""
    jax.block_until_ready(fn())  # compile + first run
    jax.block_until_ready(fn())  # steady state
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def check_parity(reference, candidate, dtype: str,
                 tol: Optional[tuple] = None) -> tuple[bool, float]:
    """Elementwise parity of every output leaf within the dtype
    tolerance (or an explicit ``(atol, rtol)`` — the sweep passes the
    kernel's ``TuneSpace.parity_tol`` override when one is declared).
    Returns ``(ok, max_scaled_err)`` where the error is
    ``max |a - b| / (atol + rtol * |a|)`` (<= 1 passes)."""
    atol, rtol = tol or _PARITY_TOL.get(dtype, (1e-5, 1e-5))
    ref_leaves = jax.tree.leaves(reference)
    cand_leaves = jax.tree.leaves(candidate)
    if len(ref_leaves) != len(cand_leaves):
        return False, math.inf
    worst = 0.0
    for a, b in zip(ref_leaves, cand_leaves):
        a = np.asarray(a, dtype=np.float32)
        b = np.asarray(b, dtype=np.float32)
        if a.shape != b.shape:
            return False, math.inf
        denom = atol + rtol * np.abs(a)
        err = np.abs(a - b) / denom
        if not np.all(np.isfinite(b)):
            return False, math.inf
        worst = max(worst, float(err.max()) if err.size else 0.0)
    return worst <= 1.0, worst


def sweep_case(
    case: TuneCase,
    *,
    iters: int = 20,
    min_speedup: float = 1.02,
    device_kind: Optional[str] = None,
    log: Callable[[str], None] = lambda s: None,
) -> CaseReport:
    """Run the full search for one case on the local device.

    The whole sweep runs table-blind (:func:`tuning_disabled`): the
    baseline is the TuneSpace default passed EXPLICITLY, and no run —
    baseline or candidate — may resolve blocks through an existing
    table entry, or a previously tuned device would time its old winner
    as the "default" and every re-tune would self-contaminate.
    """
    kind = device_kind or jax.devices()[0].device_kind
    spec = device_spec(kind)
    space = TUNE_SPACES[case.kernel]
    report = CaseReport(case=case, device_kind=kind)
    with tuning_disabled():
        return _sweep_blind(case, space, spec, report, iters=iters,
                            min_speedup=min_speedup, log=log)


def _sweep_blind(case, space, spec, report, *, iters, min_speedup, log):
    run = case.build()

    default = space.default(case.shape)
    report.default_config = default
    reference = run(default)
    report.default_us = _time_run(lambda: run(default), iters)
    log(f"{case.name}: default {default} -> {report.default_us:.1f} us")

    best: Optional[CandidateResult] = None
    for config in space.candidates(case.shape, spec, case.dtype):
        if config == default:
            continue
        result = CandidateResult(config=config)
        report.results.append(result)
        try:
            out = run(config)
            result.parity_ok, result.max_err = check_parity(
                reference, out, case.dtype,
                tol=space.parity_tol.get(case.dtype),
            )
            if not result.parity_ok:
                # A faster wrong kernel is a rejected candidate.
                log(f"{case.name}: {config} REJECTED (parity "
                    f"err={result.max_err:.3g})")
                continue
            result.mean_us = _time_run(lambda: run(config), iters)
            log(f"{case.name}: {config} -> {result.mean_us:.1f} us")
        except Exception as exc:  # noqa: BLE001 — a candidate that fails
            # to compile/run is simply not a winner; the sweep continues.
            result.error = f"{type(exc).__name__}: {exc}"[:300]
            result.parity_ok = False
            log(f"{case.name}: {config} FAILED ({result.error[:80]})")
            continue
        if result.mean_us and (best is None or result.mean_us <
                               (best.mean_us or math.inf)):
            best = result

    if best is not None and best.mean_us and report.default_us and \
            report.default_us / best.mean_us >= min_speedup:
        report.winner = best
        log(f"{case.name}: winner {best.config} "
            f"({report.default_us / best.mean_us:.3f}x)")
    else:
        log(f"{case.name}: no candidate beat the default by >= "
            f"{(min_speedup - 1) * 100:.0f}% — no table entry")
    return report


def entries_from_reports(reports) -> dict[str, list]:
    """kernel -> table entries for every winning report (the
    ``--update-table`` payload)."""
    entries: dict[str, list] = {}
    for report in reports:
        if report.winner is None:
            continue
        space = TUNE_SPACES[report.case.kernel]
        entries.setdefault(report.case.kernel, []).append({
            "device_kind": report.device_kind,
            "dtype": report.case.dtype,
            "shape": dict(report.case.shape),
            "shape_bucket": space.bucket(report.case.shape),
            "config": dict(report.winner.config),
            "default_config": dict(report.default_config),
            "default_us": round(report.default_us, 3),
            "tuned_us": round(report.winner.mean_us, 3),
            "speedup": round(report.speedup, 4),
            "parity_max_err": round(report.winner.max_err, 6),
            "case": report.case.name,
        })
    return entries


def update_tables(reports, configs_dir: Optional[str] = None,
                  merge: bool = True) -> list:
    """Write winning entries into the per-kernel tables. With ``merge``
    (default) existing entries for OTHER (device kind, bucket, dtype)
    keys survive — re-tuning one device must not drop another's rows.
    Returns the written paths."""
    from rocket_tpu.tune.table import load_table

    new = entries_from_reports(reports)
    swept = {}
    for report in reports:
        space = TUNE_SPACES[report.case.kernel]
        swept.setdefault(report.case.kernel, set()).add((
            report.device_kind, space.bucket(report.case.shape),
            report.case.dtype,
        ))
    paths = []
    for kernel, keys in swept.items():
        kept = []
        if merge:
            table = load_table(kernel, configs_dir, use_cache=False)
            for entry in (table or {}).get("entries", []):
                key = (entry.get("device_kind"), entry.get("shape_bucket"),
                       entry.get("dtype"))
                if key not in keys:
                    kept.append(entry)
        paths.append(write_table(
            kernel, kept + new.get(kernel, []), configs_dir
        ))
    return paths


# -- the builtin case catalog -------------------------------------------------
#
# Shapes mirror the bench configs whose kernels the ROADMAP names as the
# low-MFU soft spots; operands are synthetic (parity is tuned-vs-default
# of the SAME operands, so data content is irrelevant).


def _flash_fwd_case(name, b, t, h, h_kv, d, dtype, smoke=False):
    shape = {"t": t, "d": d, "h": h, "h_kv": h_kv, "causal": True}

    def build():
        from rocket_tpu.ops.flash_native import flash_bthd, flash_fused

        key = jax.random.key(0)
        # One compiled callable per config (lru_cache keeps the jitted
        # function's identity stable, so repeat calls hit jax's own
        # executable cache instead of re-tracing every iteration).
        if h == h_kv:
            fused = (jax.random.normal(key, (b, t, 3 * h * d)) * 0.2) \
                .astype(dtype)

            @functools.lru_cache(maxsize=None)
            def compiled(bq, bk):
                return jax.jit(lambda f: flash_fused(
                    f, h, causal=True, block_q=bq, block_k=bk,
                ))

            def run(config):
                cfg = config or {}
                return compiled(cfg.get("block_q"), cfg.get("block_k"))(fused)
        else:
            kq, kk, kv = jax.random.split(key, 3)
            q2 = (jax.random.normal(kq, (b, t, h * d)) * 0.2).astype(dtype)
            k2 = (jax.random.normal(kk, (b, t, h_kv * d)) * 0.2).astype(dtype)
            v2 = (jax.random.normal(kv, (b, t, h_kv * d)) * 0.2).astype(dtype)

            @functools.lru_cache(maxsize=None)
            def compiled(bq, bk):
                return jax.jit(lambda q, k, v: flash_bthd(
                    q, k, v, h, h_kv, causal=True, block_q=bq, block_k=bk,
                ))

            def run(config):
                cfg = config or {}
                return compiled(cfg.get("block_q"),
                                cfg.get("block_k"))(q2, k2, v2)
        return run

    return TuneCase(name=name, kernel="flash_fwd", shape=shape,
                    dtype=canonical_dtype(dtype), build=build, smoke=smoke)


def _flash_bwd_case(name, b, t, h, h_kv, d, dtype, smoke=False):
    import jax.numpy as jnp

    shape = {"t": t, "d": d, "h": h, "h_kv": h_kv, "causal": True}

    def build():
        from rocket_tpu.ops.flash_native import flash_bthd, flash_fused

        key = jax.random.key(1)
        if h == h_kv:
            fused = (jax.random.normal(key, (b, t, 3 * h * d)) * 0.2) \
                .astype(dtype)

            @functools.lru_cache(maxsize=None)
            def compiled(bq, bk):
                def loss(f):
                    out = flash_fused(
                        f, h, causal=True,
                        bwd_block_q=bq, bwd_block_k=bk,
                    )
                    return (out.astype(jnp.float32) ** 2).sum()

                return jax.jit(jax.grad(loss))

            def run(config):
                cfg = config or {}
                return compiled(cfg.get("block_q"), cfg.get("block_k"))(fused)
        else:
            kq, kk, kv = jax.random.split(key, 3)
            q2 = (jax.random.normal(kq, (b, t, h * d)) * 0.2).astype(dtype)
            k2 = (jax.random.normal(kk, (b, t, h_kv * d)) * 0.2).astype(dtype)
            v2 = (jax.random.normal(kv, (b, t, h_kv * d)) * 0.2).astype(dtype)

            @functools.lru_cache(maxsize=None)
            def compiled(bq, bk):
                def loss(q, k, v):
                    out = flash_bthd(
                        q, k, v, h, h_kv, causal=True,
                        bwd_block_q=bq, bwd_block_k=bk,
                    )
                    return (out.astype(jnp.float32) ** 2).sum()

                return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

            def run(config):
                cfg = config or {}
                return compiled(cfg.get("block_q"),
                                cfg.get("block_k"))(q2, k2, v2)
        return run

    return TuneCase(name=name, kernel="flash_bwd", shape=shape,
                    dtype=canonical_dtype(dtype), build=build, smoke=smoke)


def _decode_case(name, b, hq, h_kv, d, t, dtype, smoke=False):
    import jax.numpy as jnp

    shape = {"t": t, "d": d, "hkv": h_kv}

    def build():
        from rocket_tpu.ops.decode_attention import decode_attention

        key = jax.random.key(2)
        kq, kn, kc = jax.random.split(key, 3)
        q = (jax.random.normal(kq, (b, hq, d)) * 0.2).astype(dtype)
        k_new = (jax.random.normal(kn, (b, h_kv, d)) * 0.2).astype(dtype)
        v_new = k_new * 0.5
        k_cache = (jax.random.normal(kc, (b, h_kv, t, d)) * 0.2).astype(dtype)
        v_cache = k_cache * 0.5
        pos = jnp.int32(t // 2 + 3)

        @functools.lru_cache(maxsize=None)
        def compiled(rows):
            return jax.jit(lambda *a: decode_attention(*a, rows=rows))

        def run(config):
            cfg = config or {}
            out, k_out, v_out = compiled(cfg.get("rows"))(
                q, k_new, v_new, k_cache, v_cache, pos
            )
            return out, k_out, v_out

        return run

    return TuneCase(name=name, kernel="decode_attention", shape=shape,
                    dtype=canonical_dtype(dtype), build=build, smoke=smoke)


def _paged_case(name, s, mb, bl, hkv, hq, d, dtype, smoke=False):
    """paged_decode at a serve-engine wave shape: operands mirror one
    C=1 decode wave (every slot active mid-context) against a pool sized
    exactly like ``ServeConfig.resolve`` would size it. ``impl`` is the
    structural axis: candidates run BOTH the fused pallas kernel and the
    XLA gather path, parity-checked against the default."""
    import jax.numpy as jnp

    shape = {"s": s, "mb": mb, "bl": bl, "hkv": hkv, "hq": hq, "d": d}

    def build():
        from rocket_tpu.ops.paged_attention import paged_attention

        key = jax.random.key(5)
        kq, kn, kp = jax.random.split(key, 3)
        nb = 1 + s * mb
        q = (jax.random.normal(kq, (s, 1, hq, d)) * 0.2).astype(dtype)
        k_new = (jax.random.normal(kn, (s, 1, hkv, d)) * 0.2).astype(dtype)
        v_new = k_new * 0.5
        k_pages = (jax.random.normal(kp, (1, nb, bl, hkv * d)) * 0.2) \
            .astype(dtype)
        v_pages = k_pages * 0.5
        table = jnp.asarray(
            1 + np.arange(s * mb, dtype=np.int32).reshape(s, mb)
        )
        # Mid-context positions exercise both the active-page stream and
        # the masked tail (different per slot so tiles partially fill).
        positions = jnp.asarray(
            [(mb * bl) // 2 + i * (bl // 2) for i in range(s)], jnp.int32
        )
        valid = jnp.ones((s,), jnp.int32)
        interpret = jax.devices()[0].platform == "cpu"

        @functools.lru_cache(maxsize=None)
        def compiled(impl, block_kv):
            return jax.jit(lambda *a: paged_attention(
                *a, impl=impl, block_kv=block_kv, interpret=interpret,
            ))

        def run(config):
            cfg = config or {}
            return compiled(cfg.get("impl"), cfg.get("block_kv"))(
                q, k_new, v_new, k_pages, v_pages, table, positions, valid
            )

        return run

    return TuneCase(name=name, kernel="paged_decode", shape=shape,
                    dtype=canonical_dtype(dtype), build=build, smoke=smoke)


def _gmm_case(name, m, k, n, e, dtype, routed=True):
    """moe_gmm at the dropless-dispatch shape: ``impl`` is the
    structural axis. ``impl="gmm"`` measures what the model path
    actually runs — the EXPLICIT row gather (the round-5 ~30 GB/s
    random-row loser, docs/performance.md) followed by megablox gmm;
    ``impl="fused"`` the gather-gmm kernel routing the same rows
    in-kernel. ``routed=False`` (the out-projection case, whose lhs is
    contiguous in the real dispatch) uses identity routing — the fused
    variant then measures pure kernel overhead and loses honestly."""
    import jax.numpy as jnp

    shape = {"m": m, "k": k, "n": n}

    def build():
        from jax.experimental.pallas.ops.tpu.megablox.ops import gmm

        from rocket_tpu.ops.gather_gmm import gather_gmm

        key = jax.random.key(3)
        kl, kr, kp = jax.random.split(key, 3)
        x = (jax.random.normal(kl, (m, k)) * 0.1).astype(dtype)
        rhs = (jax.random.normal(kr, (e, k, n)) * 0.1).astype(dtype)
        # Uniform groups (m/e each — a tile multiple for every candidate
        # at the bench shapes) over a fixed random routing permutation.
        sizes = jnp.full((e,), m // e, jnp.int32)
        ids = (
            jax.random.permutation(kp, jnp.arange(m, dtype=jnp.int32))
            if routed else jnp.arange(m, dtype=jnp.int32)
        )
        interpret = jax.devices()[0].platform == "cpu"

        @functools.lru_cache(maxsize=None)
        def compiled(impl, tiling):
            if impl == "fused":
                # The fused variant always pays its own gather machinery
                # — with identity ids (routed=False) that is exactly the
                # overhead it must beat zero of, so it loses honestly.
                return jax.jit(lambda a, b, s, i: gather_gmm(
                    a, b, i, s, tile_m=tiling[0], tile_n=tiling[2],
                    interpret=interpret,
                ))
            if routed:
                return jax.jit(lambda a, b, s, i: gmm(
                    jnp.take(a, i, axis=0), b, s, a.dtype, tiling
                ))
            # The real out-projection consumes already-contiguous rows —
            # no gather exists on that path, so none is timed (an
            # identity take would inflate default AND candidates alike
            # and compress real tile speedups below min_speedup).
            return jax.jit(lambda a, b, s, i: gmm(a, b, s, a.dtype,
                                                  tiling))

        def run(config):
            cfg = config or TUNE_SPACES["moe_gmm"].default(shape)
            tiling = (min(cfg["tile_m"], m), min(cfg["tile_k"], k),
                      min(cfg["tile_n"], n))
            return compiled(cfg.get("impl", "gmm"), tiling)(
                x, rhs, sizes, ids
            )

        return run

    return TuneCase(name=name, kernel="moe_gmm", shape=shape,
                    dtype=canonical_dtype(dtype), build=build)


def _fused_conv_case(name, b, hw, c, dtype, smoke=False):
    """fused_conv at a conv-stack activation shape: fwd+bwd of the
    BN(+relu) epilogue — impl 'reference' (the unfused chain) is the
    parity baseline and speedup denominator."""
    import jax.numpy as jnp

    shape = {"n": b * hw * hw, "c": c}

    def build():
        from rocket_tpu.ops.fused_conv import fused_bn_act, reference_bn_act

        key = jax.random.key(6)
        x = (jax.random.normal(key, (b, hw, hw, c)) + 0.5).astype(dtype)
        scale = jnp.ones((c,), jnp.float32) * 1.5
        bias = jnp.zeros((c,), jnp.float32)
        interpret = jax.devices()[0].platform == "cpu"

        @functools.lru_cache(maxsize=None)
        def compiled(impl, schedule, block_rows):
            def loss(x, scale, bias):
                if impl == "pallas":
                    y, stats = fused_bn_act(
                        x, scale, bias, eps=1e-5, act=True,
                        schedule=schedule, block_rows=block_rows,
                        interpret=interpret,
                    )
                else:
                    y, stats = reference_bn_act(x, scale, bias, 1e-5, True)
                return (y.astype(jnp.float32) ** 2).sum(), stats

            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True
            ))

        def run(config):
            cfg = config or {}
            (l, stats), grads = compiled(
                cfg.get("impl", "reference"), cfg.get("schedule"),
                cfg.get("block_rows"),
            )(x, scale, bias)
            return l, stats, grads

        return run

    return TuneCase(name=name, kernel="fused_conv", shape=shape,
                    dtype=canonical_dtype(dtype), build=build, smoke=smoke)


def _block_attn_case(name, b, t, d, h, dtype, smoke=False):
    """block_attn at a small-LM block shape: fwd+bwd of the attention
    half — impl 'reference' (the per-op chain) is the parity baseline."""
    import jax.numpy as jnp

    shape = {"b": b, "t": t, "d": d, "h": h}

    def build():
        from rocket_tpu.ops.fused_block import (
            block_attn_half,
            reference_block_attn,
        )

        key = jax.random.key(7)
        ks = jax.random.split(key, 6)
        x = (jax.random.normal(ks[0], (b, t, d)) * 0.5).astype(dtype)
        ln_s = 1.0 + 0.1 * jax.random.normal(ks[1], (d,))
        ln_b = 0.1 * jax.random.normal(ks[2], (d,))
        wqkv = jax.random.normal(ks[3], (d, 3 * d)) * (d ** -0.5)
        bqkv = jnp.zeros((3 * d,))
        wproj = jax.random.normal(ks[4], (d, d)) * (d ** -0.5)
        bproj = jnp.zeros((d,))
        interpret = jax.devices()[0].platform == "cpu"

        @functools.lru_cache(maxsize=None)
        def compiled(impl, epilogue, block_b):
            def loss(x, ln_s, ln_b, wqkv, bqkv, wproj, bproj):
                if impl == "fused":
                    y = block_attn_half(
                        x, ln_s, ln_b, wqkv, bqkv, wproj, bproj,
                        num_heads=h, epilogue=epilogue, block_b=block_b,
                        interpret=interpret,
                    )
                    if epilogue == "separate":
                        # Projection applied outside the kernel (XLA) so
                        # the output surface — and therefore parity —
                        # stays comparable to the baseline.
                        y = y @ wproj.astype(y.dtype) \
                            + bproj.astype(y.dtype)
                else:
                    # The reference chain has no epilogue split —
                    # legality pins the axis inert for impl=reference.
                    y = reference_block_attn(
                        x, ln_s, ln_b, wqkv, bqkv, wproj, bproj,
                        num_heads=h, epilogue="fused",
                    )
                return (y.astype(jnp.float32) ** 2).sum()

            return jax.jit(jax.value_and_grad(loss, argnums=(0, 3, 5)))

        def run(config):
            cfg = config or {}
            loss, grads = compiled(
                cfg.get("impl", "reference"), cfg.get("epilogue", "fused"),
                cfg.get("block_b", 1),
            )(x, ln_s, ln_b, wqkv, bqkv, wproj, bproj)
            return (loss,) + grads

        return run

    return TuneCase(name=name, kernel="block_attn", shape=shape,
                    dtype=canonical_dtype(dtype), build=build, smoke=smoke)


def _bn_case(name, b, hw, c, dtype, smoke=False):
    import jax.numpy as jnp

    shape = {"c": c}

    def build():
        from rocket_tpu.nn.layers import _bn_train

        key = jax.random.key(4)
        x = (jax.random.normal(key, (b, hw, hw, c)) + 0.5).astype(dtype)
        scale = jnp.ones((c,), jnp.float32)
        bias = jnp.zeros((c,), jnp.float32)

        @functools.lru_cache(maxsize=None)
        def compiled(moments):
            def loss(x, scale, bias):
                y, stats = _bn_train(x, scale, bias, 1e-5, moments)
                return (y.astype(jnp.float32) ** 2).sum(), stats

            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True
            ))

        def run(config):
            moments = (config or {}).get("moments")
            (l, stats), grads = compiled(moments)(x, scale, bias)
            return l, stats, grads

        return run

    return TuneCase(name=name, kernel="fused_bn", shape=shape,
                    dtype=canonical_dtype(dtype), build=build, smoke=smoke)


def _builtin_cases() -> list:
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    return [
        # The bench soft spots (ROADMAP item 2 evidence): charlm 0.28,
        # longctx 0.50, moe 0.39, resnet50 0.27 MFU; gpt2 as the
        # regression sentinel for the best-tuned config.
        _flash_fwd_case("flash_fwd/gpt2", b=8, t=1024, h=12, d=64,
                        h_kv=12, dtype=bf16),
        _flash_fwd_case("flash_fwd/charlm", b=64, t=256, h=4, d=64,
                        h_kv=4, dtype=bf16),
        _flash_fwd_case("flash_fwd/longctx", b=2, t=4096, h=12, d=64,
                        h_kv=4, dtype=bf16),
        _flash_bwd_case("flash_bwd/gpt2", b=8, t=1024, h=12, d=64,
                        h_kv=12, dtype=bf16),
        _flash_bwd_case("flash_bwd/charlm", b=64, t=256, h=4, d=64,
                        h_kv=4, dtype=bf16),
        _flash_bwd_case("flash_bwd/longctx", b=2, t=4096, h=12, d=64,
                        h_kv=4, dtype=bf16),
        _decode_case("decode/gpt2", b=8, hq=12, h_kv=12, d=64, t=512,
                     dtype=bf16),
        # The serve-engine decode-wave shapes (ISSUE 11): charlm mirrors
        # bench serve_summary / the serve_audit charlm target, gpt2_geom
        # the GQA+wide-vocab audit target — the shapes whose measured
        # ITL the fused kernel exists to fix.
        _paged_case("paged/charlm", s=8, mb=16, bl=16, hkv=4, hq=4, d=64,
                    dtype=bf16),
        _paged_case("paged/gpt2_geom", s=8, mb=16, bl=32, hkv=4, hq=12,
                    d=64, dtype=bf16),
        _gmm_case("gmm/moe_bench", m=16384, k=768, n=3072, e=4,
                  dtype=bf16),
        _gmm_case("gmm/moe_bench_out", m=16384, k=3072, n=768, e=4,
                  dtype=bf16, routed=False),
        _bn_case("bn/resnet18", b=256, hw=32, c=64, dtype=bf16),
        # The structural soft-spot candidates (ROADMAP item 4): the
        # conv-stack BN(+relu) epilogue at the resnet18/50 stem shapes,
        # and the whole-block attention half at the charlm block shape.
        _fused_conv_case("fused_conv/resnet18", b=256, hw=32, c=64,
                         dtype=bf16),
        _fused_conv_case("fused_conv/resnet50", b=128, hw=56, c=64,
                         dtype=bf16),
        _block_attn_case("block_attn/charlm", b=64, t=256, d=256, h=4,
                         dtype=bf16),
        # CPU smoke subset: tiny shapes that run interpreted in seconds.
        _flash_fwd_case("flash_fwd/smoke", b=2, t=256, h=2, d=64,
                        h_kv=2, dtype=bf16, smoke=True),
        _flash_bwd_case("flash_bwd/smoke", b=1, t=256, h=2, d=64,
                        h_kv=2, dtype=bf16, smoke=True),
        _decode_case("decode/smoke", b=2, hq=2, h_kv=2, d=64, t=128,
                     dtype=bf16, smoke=True),
        _paged_case("paged/smoke", s=2, mb=2, bl=16, hkv=2, hq=2, d=64,
                    dtype=jnp.float32, smoke=True),
        _bn_case("bn/smoke", b=8, hw=8, c=16, dtype=bf16, smoke=True),
        _fused_conv_case("fused_conv/smoke", b=8, hw=8, c=16,
                         dtype=jnp.float32, smoke=True),
        _block_attn_case("block_attn/smoke", b=4, t=64, d=128, h=2,
                         dtype=jnp.float32, smoke=True),
    ]


#: name -> case. Built lazily (the builders import jnp) but cheap.
TUNE_CASES: dict[str, TuneCase] = {}


def load_cases() -> dict[str, TuneCase]:
    if not TUNE_CASES:
        for case in _builtin_cases():
            TUNE_CASES[case.name] = case
    return TUNE_CASES


def run_cases(
    names=None,
    kernels=None,
    *,
    iters: int = 20,
    min_speedup: float = 1.02,
    smoke_only: bool = False,
    log: Callable[[str], None] = lambda s: None,
) -> list:
    """Sweep the selected builtin cases on the local device."""
    cases = load_cases()
    selected = []
    for name, case in cases.items():
        if names and name not in names:
            continue
        if kernels and case.kernel not in kernels:
            continue
        if smoke_only and not case.smoke:
            continue
        if not smoke_only and case.smoke:
            continue
        selected.append(case)
    reports = []
    for case in selected:
        try:
            reports.append(sweep_case(
                case, iters=iters, min_speedup=min_speedup, log=log
            ))
        except Exception as exc:  # noqa: BLE001 — one broken case must
            # not kill the rest of the sweep (e.g. gmm import off-TPU).
            log(f"{case.name}: case failed entirely — "
                f"{type(exc).__name__}: {exc}")
    return reports
