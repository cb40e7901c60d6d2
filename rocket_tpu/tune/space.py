"""Declarative tune spaces — the LEGAL config set per tunable kernel.

Each tunable kernel declares a :class:`TuneSpace`: the config axes the
offline tuner (``python -m rocket_tpu.tune``) may sweep, the default
config (today's hand-picked values — the runtime fallback when no table
entry matches), and a legality predicate that rejects configs the
hardware cannot run correctly or efficiently BEFORE anything is timed.

Axes come in two kinds. **Launch-config axes** (block/tile sizes) pick
parameters of ONE kernel. **Structural axes** (named in
:attr:`TuneSpace.structural`) pick between *different traced programs*
— fusion boundaries (``fused_conv.impl``, ``block_attn.epilogue``),
whole-kernel variants (``paged_decode.impl``, ``moe_gmm.impl``),
reduction schedules (``fused_conv.schedule``, ``fused_bn.moments``).
The search machinery treats both identically (enumerate -> compile ->
time with compile excluded -> fwd+bwd parity-reject -> table), which is
the point: a structurally different kernel that is faster but WRONG is
discarded by the same gate that rejects a bad block size (CUDA-L1
2507.14111 / AutoKernel 2603.21331 style generate-and-verify). Every
structural default is the pre-existing path, so absent tables — or
``ROCKET_TPU_TUNE=0`` — are behavior-identical to an untuned checkout.

Launch-config legality rules (shared by every kernel):

* the flash kernels' causal path masks only diagonal blocks, which is
  correct ONLY when ``block_q == block_k`` (`ops/flash_attention.py`
  raises loudly on violation — an illegal tuner candidate fails fast
  instead of returning wrong attention);
* every block must respect the (sublane, 128) tile: the last dim a
  multiple of 128 or the whole array dim, the sublane dim a multiple of
  the dtype minimum (8 f32 / 16 bf16 / 32 int8);
* the double-buffered VMEM estimate of one grid step's blocks must fit
  the device's conservative scratch budget
  (:class:`rocket_tpu.utils.perf.DeviceSpec.vmem_bytes` — the same
  budget RKT504 gates statically).

The registry (:data:`TUNE_SPACES`) is the single source of truth shared
by the runtime lookup (``table.get_config`` buckets shapes with
``TuneSpace.bucket``), the offline tuner (candidate enumeration) and the
CI table gate (``table.validate_tables`` re-verifies every checked-in
entry's legality against its space, so a stale table cannot ship a
config a space change made illegal).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Callable, Mapping, Optional, Tuple

from rocket_tpu.utils.perf import DeviceSpec

__all__ = ["TuneSpace", "TUNE_SPACES", "sublane_min", "canonical_dtype"]

#: Minimum sublane multiple by dtype itemsize — same table as the RKT504
#: pallas-block check (`analysis/rules/sched_rules.py`).
_SUBLANE = {4: 8, 2: 16, 1: 32}

_DTYPE_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def sublane_min(dtype: str) -> int:
    return _SUBLANE.get(_DTYPE_ITEMSIZE.get(dtype, 4), 8)


def canonical_dtype(dtype) -> str:
    """'bfloat16' / 'float32' style name for a jnp dtype, dtype object or
    string — the table's dtype key."""
    name = getattr(dtype, "name", None)
    if name is None:
        import numpy as np

        try:
            name = np.dtype(dtype).name
        except TypeError:
            name = str(dtype)
    return name


@dataclass(frozen=True)
class TuneSpace:
    """The legal launch-config set for one tunable kernel.

    ``axes`` maps config-key -> candidate values (the full cross product
    is the raw search space; ``legal`` prunes it). ``default`` computes
    today's hand-picked config for a shape — the runtime fallback, and
    the baseline every candidate is timed and parity-checked against.
    ``legal`` returns a list of human-readable violations (empty =
    legal). ``shape_keys`` documents which shape-dict keys the bucket is
    keyed on (validation rejects entries missing them).
    """

    kernel: str
    axes: Mapping[str, Tuple]
    shape_keys: Tuple[str, ...]
    default: Callable[[Mapping], dict]
    legal: Callable[[dict, Mapping, Optional[DeviceSpec], str], list] = \
        field(default=lambda config, shape, spec, dtype: [])
    doc: str = ""
    #: Axis names whose candidate values are DIFFERENT TRACED KERNELS
    #: (implementation variants / fusion choices / schedules), not
    #: launch parameters of one kernel. Drives the ``--list`` catalog
    #: and the stale-structural-winner table gate: a checked-in entry
    #: pinning a variant that no longer exists must fail LOUDLY, never
    #: silently fall back.
    structural: Tuple[str, ...] = ()
    #: Per-dtype (atol, rtol) parity-tolerance OVERRIDES for this
    #: kernel's sweeps, merged over the tuner's defaults. Scoped here —
    #: not widened globally — so a kernel whose variants legitimately
    #: reassociate f32 reductions (fused_conv's tile-sequential moments
    #: vs XLA's tree) can declare it without loosening the gate for
    #: every launch-config sweep.
    parity_tol: Mapping[str, Tuple[float, float]] = \
        field(default_factory=dict)

    def bucket(self, shape: Mapping) -> str:
        """Deterministic shape-bucket string for the table key. Exact
        shapes, not ranges: the tuner measures the exact bench shapes and
        anything else falls back to the default config — the conservative
        choice that keeps untuned shapes behavior-identical."""
        parts = []
        for key in self.shape_keys:
            value = shape[key]
            if isinstance(value, bool):
                value = "t" if value else "f"
            parts.append(f"{key}{value}")
        return "_".join(parts)

    def candidates(self, shape: Mapping, spec: Optional[DeviceSpec],
                   dtype: str) -> list:
        """Every LEGAL config in the axes cross product (default included
        when legal), deterministic order."""
        keys = sorted(self.axes)
        out = []
        for values in itertools.product(*(self.axes[k] for k in keys)):
            config = dict(zip(keys, values))
            if not self.legal(config, shape, spec, dtype):
                out.append(config)
        return out

    def violations(self, config: Mapping, shape: Mapping,
                   spec: Optional[DeviceSpec], dtype: str) -> list:
        """Axis-membership + kernel legality violations for ``config``."""
        problems = []
        for key, value in config.items():
            if key not in self.axes:
                problems.append(f"unknown config axis {key!r}")
            elif value not in self.axes[key]:
                problems.append(
                    f"{key}={value!r} not in candidates {self.axes[key]}"
                )
        for key in self.axes:
            if key not in config:
                # A partial config would KeyError in the kernel's
                # resolution path — every axis must be pinned.
                problems.append(f"config missing axis {key!r}")
        for key in self.shape_keys:
            if key not in shape:
                problems.append(f"shape missing key {key!r}")
        if problems:
            return problems
        return list(self.legal(dict(config), shape, spec, dtype))


# -- per-kernel legality ------------------------------------------------------


def _block_legal(block: int, t: int, dtype: str, what: str) -> list:
    problems = []
    if t % block:
        problems.append(f"{what}={block} does not divide T={t}")
    if block % sublane_min(dtype):
        problems.append(
            f"{what}={block} % {sublane_min(dtype)} sublane tile ({dtype})"
        )
    return problems


def _flash_vmem_bytes(config, shape, dtype: str) -> int:
    """Double-buffered VMEM estimate for one grid step of the native-
    layout flash kernels (`ops/flash_native.py`): q/out blocks are
    (block_q, h*d) wide, k/v blocks (block_k, h_kv*d), plus the f32
    accumulator/stat scratch. Mirrors the 2x-per-block estimate RKT504
    applies to the traced jaxpr (`sched_audit._pallas_fact`)."""
    itemsize = _DTYPE_ITEMSIZE.get(dtype, 4)
    bq, bk = config["block_q"], config["block_k"]
    qw = shape["h"] * shape["d"]
    kw = shape["h_kv"] * shape["d"]
    blocks = 2 * (bq * qw + 2 * bk * kw + bq * qw) * itemsize  # q,k,v,out x2
    scratch = (qw * bq + 2 * shape["h"] * bq) * 4              # acc,m,l f32
    return blocks + scratch


def _flash_legal(config, shape, spec, dtype) -> list:
    problems = []
    t = shape["t"]
    problems += _block_legal(config["block_q"], t, dtype, "block_q")
    problems += _block_legal(config["block_k"], t, dtype, "block_k")
    if shape.get("causal", True) and config["block_q"] != config["block_k"]:
        # Diagonal-block masking is only correct on aligned square blocks
        # — the kernel entry raises on this; reject before timing.
        problems.append(
            f"causal requires block_q == block_k "
            f"(got {config['block_q']} != {config['block_k']})"
        )
    if spec is not None:
        need = _flash_vmem_bytes(config, shape, dtype)
        if need > spec.vmem_bytes:
            problems.append(
                f"VMEM estimate {need >> 20} MiB over the {spec.kind} "
                f"budget {spec.vmem_bytes >> 20} MiB"
            )
    return problems


def _flash_default(shape) -> dict:
    from rocket_tpu.ops.flash_attention import pick_block

    block = pick_block(shape["t"], min(512, shape["t"])) or 512
    return {"block_q": block, "block_k": block}


def _decode_legal(config, shape, spec, dtype) -> list:
    rows = config["rows"]
    problems = []
    if rows % 8:
        problems.append(f"rows={rows} % 8 (Mosaic sublane minimum)")
    if shape["t"] % rows:
        problems.append(f"rows={rows} does not divide T_max={shape['t']}")
    if spec is not None:
        # The kernel holds the whole (Hkv, T, D) K and V cache blocks per
        # grid cell; rows only sizes the aliased write-back tile.
        itemsize = _DTYPE_ITEMSIZE.get(dtype, 4)
        cache = 2 * 2 * shape["hkv"] * shape["t"] * shape["d"] * itemsize
        if cache > spec.vmem_bytes:
            problems.append(
                f"cache blocks {cache >> 20} MiB over the {spec.kind} "
                f"budget {spec.vmem_bytes >> 20} MiB"
            )
    return problems


def _paged_legal(config, shape, spec, dtype) -> list:
    """paged_decode: for the fused kernel, ``block_kv`` — the rows of one
    streamed tile — must be a sublane multiple that divides the pool page
    or is a multiple of it (part of a page, or whole pages, a copy), the
    page itself sublane-tileable for the dtype, the pool's rows a
    multiple of 128 lanes (Mosaic slices an HBM array at its tiling), and
    the double-buffered tiles must fit VMEM. For ``impl="xla"`` block_kv
    is INERT (the gather path never reads it) — it is pinned to the
    default so the cross product enumerates ONE xla candidate instead of
    timing byte-identical programs once per block_kv value."""
    bl, d = shape["bl"], shape["d"]
    block_kv = config["block_kv"]
    problems = []
    if d % 8:
        problems.append(f"head_dim={d} % 8 (lane-minor tiling)")
    if config["impl"] == "xla":
        default_kv = _paged_default(shape)["block_kv"]
        if block_kv != default_kv:
            problems.append(
                f"block_kv={block_kv} is inert for impl=xla — only the "
                f"default {default_kv} is enumerated"
            )
        return problems
    if bl % sublane_min(dtype):
        # The pool page itself cannot tile for this dtype: the kernel
        # never engages (paged_attention falls back to the gather
        # path), so a "pallas" entry here would record a config that
        # cannot run — reject every pallas candidate.
        problems.append(
            f"block_len={bl} % {sublane_min(dtype)} sublane tile "
            f"({dtype}) — the fused kernel cannot tile this pool page"
        )
    if (shape["hkv"] * d) % 128:
        problems.append(
            f"pool rows of {shape['hkv'] * d} lanes, no multiple of 128 — "
            "the fused kernel cannot copy them out of HBM"
        )
    if block_kv % sublane_min(dtype):
        problems.append(
            f"block_kv={block_kv} % {sublane_min(dtype)} sublane tile "
            f"({dtype})"
        )
    if bl % block_kv and block_kv % bl:
        problems.append(f"block_kv={block_kv} neither divides "
                        f"block_len={bl} nor is a multiple of it")
    if spec is not None:
        # Double-buffered K+V tiles (every kv head rides one tile) + the
        # q/out/accumulator residents (every query head).
        itemsize = _DTYPE_ITEMSIZE.get(dtype, 4)
        hq, hkv = shape["hq"], shape["hkv"]
        need = 2 * 2 * block_kv * hkv * d * itemsize \
            + 2 * hq * d * itemsize + hq * (d + 256) * 4
        if need > spec.vmem_bytes:
            problems.append(
                f"VMEM estimate {need >> 20} MiB over the {spec.kind} "
                f"budget {spec.vmem_bytes >> 20} MiB"
            )
    return problems


def _paged_default(shape) -> dict:
    """An untuned checkout's behavior: the fused kernel (TPU decode
    waves; CPU dispatch falls back to the XLA path regardless) with the
    tile height the op derives from the page and the row's width."""
    from rocket_tpu.ops.paged_attention import _default_block_kv

    return {"impl": "pallas", "block_kv": _default_block_kv(
        shape["bl"], row_lanes=2 * shape["hkv"] * shape["d"])}


#: Hand-picked defaults, single-sourced: the TuneSpace ``default``
#: lambdas AND the inert-axis legality pins both read these, so a
#: default change cannot silently reject its own baseline candidate.
_GMM_DEFAULT = {"impl": "gmm", "tile_m": 512, "tile_k": 512,
                "tile_n": 512}
_FUSED_CONV_DEFAULT = {"impl": "reference", "schedule": "twopass",
                       "block_rows": 512}
_BLOCK_ATTN_DEFAULT = {"impl": "reference", "epilogue": "fused",
                       "block_b": 1}


def _gmm_legal(config, shape, spec, dtype) -> list:
    problems = []
    itemsize = _DTYPE_ITEMSIZE.get(dtype, 4)
    tm = min(config["tile_m"], shape["m"])
    tk = min(config["tile_k"], shape["k"])
    tn = min(config["tile_n"], shape["n"])
    for name, tile in (("tile_k", tk), ("tile_n", tn)):
        if tile % 128:
            problems.append(f"{name}={tile} % 128 lane tile")
    if tm % sublane_min(dtype):
        problems.append(f"tile_m={tm} % {sublane_min(dtype)} sublane tile")
    if config.get("impl", "gmm") == "fused":
        # The gather-gmm variant (ops/gather_gmm.py) holds the WHOLE
        # contraction dim per lhs tile (the gathered rows land once, the
        # n-tiles reuse them) — tile_k is inert; only the default is
        # enumerated so the cross product never times byte-identical
        # programs.
        problems += _inert(
            config, {"tile_k": _GMM_DEFAULT["tile_k"]},
            "impl=fused (whole-K lhs scratch)",
        )
        if shape["n"] % tn:
            problems.append(
                f"tile_n={tn} does not divide N={shape['n']} "
                "(the fused kernel masks nothing)"
            )
        if spec is not None:
            # Gathered-lhs scratch (full K) + double-buffered rhs/out.
            need = (tm * shape["k"] + 2 * (shape["k"] * tn + tm * tn)) \
                * itemsize
            if need > spec.vmem_bytes:
                problems.append(
                    f"VMEM estimate {need >> 20} MiB over the "
                    f"{spec.kind} budget {spec.vmem_bytes >> 20} MiB"
                )
        return problems
    if spec is not None:
        # lhs/rhs/out tiles double-buffered + the f32 accumulator scratch
        # the megablox kernel allocates.
        need = 2 * (tm * tk + tk * tn + tm * tn) * itemsize + tm * tn * 4
        if need > spec.vmem_bytes:
            problems.append(
                f"VMEM estimate {need >> 20} MiB over the {spec.kind} "
                f"budget {spec.vmem_bytes >> 20} MiB"
            )
    return problems


def _inert(config, pins: Mapping, why: str) -> list:
    """Reject non-default values of axes that cannot affect the selected
    variant — one candidate per byte-identical program."""
    return [
        f"{axis}={config[axis]!r} is inert for {why} — only the default "
        f"{default!r} is enumerated"
        for axis, default in pins.items()
        if config.get(axis) != default
    ]


def _fused_conv_legal(config, shape, spec, dtype) -> list:
    """fused_conv: the 2-phase BN(+relu) epilogue kernel
    (ops/fused_conv.py) over the flattened (N, C) conv output."""
    if config["impl"] == "reference":
        return _inert(
            config,
            {k: _FUSED_CONV_DEFAULT[k] for k in ("schedule", "block_rows")},
            "impl=reference (the unfused XLA chain)",
        )
    problems = []
    itemsize = _DTYPE_ITEMSIZE.get(dtype, 4)
    br = config["block_rows"]
    n, c = shape["n"], shape["c"]
    if br % sublane_min(dtype):
        problems.append(
            f"block_rows={br} % {sublane_min(dtype)} sublane tile ({dtype})"
        )
    if n % br:
        problems.append(
            f"block_rows={br} does not divide N={n} (the kernel masks "
            "no ragged tail)"
        )
    if spec is not None:
        # x in + y out tiles double-buffered, + the f32 stat scratch.
        need = 2 * 2 * br * c * itemsize + 6 * c * 4
        if need > spec.vmem_bytes:
            problems.append(
                f"VMEM estimate {need >> 20} MiB over the {spec.kind} "
                f"budget {spec.vmem_bytes >> 20} MiB"
            )
    return problems


def _block_attn_legal(config, shape, spec, dtype) -> list:
    """block_attn: the whole-block ln1+QKV+attention(+projection) fusion
    (ops/fused_block.py) — the whole (T, D) sequence rides VMEM."""
    if config["impl"] == "reference":
        return _inert(
            config,
            {k: _BLOCK_ATTN_DEFAULT[k] for k in ("epilogue", "block_b")},
            "impl=reference (the per-op layer chain)",
        )
    problems = []
    itemsize = _DTYPE_ITEMSIZE.get(dtype, 4)
    b, t, d, h = shape["b"], shape["t"], shape["d"], shape["h"]
    bb = config["block_b"]
    if b % bb:
        problems.append(f"block_b={bb} does not divide B={b}")
    if h <= 0 or d % h or (d // h) % 8:
        problems.append(
            f"head layout D={d} H={h} is not lane-minor friendly "
            "(head_dim % 8)"
        )
    if t % sublane_min(dtype):
        problems.append(
            f"T={t} % {sublane_min(dtype)} sublane tile ({dtype})"
        )
    if spec is not None:
        # x/out tiles double-buffered + resident weights + the f32
        # qkv/score intermediates of one row.
        need = 2 * 2 * bb * t * d * itemsize \
            + (3 * d * d + d * d + 4 * d) * itemsize \
            + 4 * (3 * t * d + t * t)
        if need > spec.vmem_bytes:
            problems.append(
                f"VMEM estimate {need >> 20} MiB over the {spec.kind} "
                f"budget {spec.vmem_bytes >> 20} MiB"
            )
    return problems


#: kernel name -> TuneSpace. The names are the table file names
#: (``rocket_tpu/tune/configs/<kernel>.json``) and the runtime lookup
#: keys (`table.get_config(kernel, ...)`).
TUNE_SPACES: dict[str, TuneSpace] = {
    space.kernel: space
    for space in (
        TuneSpace(
            kernel="flash_fwd",
            axes={"block_q": (128, 256, 512, 1024),
                  "block_k": (128, 256, 512, 1024)},
            shape_keys=("t", "d", "h", "h_kv", "causal"),
            default=_flash_default,
            legal=_flash_legal,
            doc="flash attention forward (ops/flash_native.py _fwd and "
                "ops/flash_attention.py _fwd): query/kv block sizes; "
                "causal pins the diagonal to square blocks",
        ),
        TuneSpace(
            kernel="flash_bwd",
            axes={"block_q": (128, 256, 512, 1024),
                  "block_k": (128, 256, 512, 1024)},
            shape_keys=("t", "d", "h", "h_kv", "causal"),
            default=_flash_default,
            legal=_flash_legal,
            doc="flash attention fused backward (dk/dv sweep + dq "
                "partials): block sizes independent of the forward's",
        ),
        TuneSpace(
            kernel="decode_attention",
            axes={"rows": (8, 16, 32)},
            shape_keys=("t", "d", "hkv"),
            default=lambda shape: {"rows": 8},
            legal=_decode_legal,
            doc="fused decode attention (ops/decode_attention.py): the "
                "aliased cache write-back tile height",
        ),
        TuneSpace(
            kernel="paged_decode",
            axes={"impl": ("pallas", "xla"),
                  "block_kv": (8, 16, 32, 64, 128, 256, 512)},
            shape_keys=("s", "mb", "bl", "hkv", "hq", "d"),
            default=_paged_default,
            legal=_paged_legal,
            structural=("impl",),
            doc="paged-pool decode attention (ops/paged_attention.py): "
                "impl is a structural axis (fused VMEM-streaming pallas "
                "kernel vs the XLA gather path — the tuner measures "
                "both and may pin XLA on shapes where the gather wins), "
                "block_kv the rows of one streamed KV tile (part of a "
                "page, or whole pages, a compute step)",
        ),
        TuneSpace(
            kernel="moe_gmm",
            axes={"impl": ("gmm", "fused"),
                  "tile_m": (128, 256, 512, 1024),
                  "tile_k": (128, 256, 512, 1024),
                  "tile_n": (128, 256, 512, 1024)},
            shape_keys=("m", "k", "n"),
            default=lambda shape: dict(_GMM_DEFAULT),
            legal=_gmm_legal,
            structural=("impl",),
            doc="dropless-MoE grouped matmuls (nn/moe.py): impl is a "
                "structural axis — 'gmm' (explicit row gather + "
                "megablox) vs 'fused' (ops/gather_gmm.py: the token "
                "gather rides the kernel's own DMA pipeline, no sorted "
                "copy materializes — aimed at the round-5 dropless "
                "loss); (m, k, n) tile triple clamped to the operand "
                "dims at call",
        ),
        TuneSpace(
            kernel="fused_bn",
            axes={"moments": ("stacked", "separate")},
            shape_keys=("c",),
            default=lambda shape: {"moments": "stacked"},
            structural=("moments",),
            doc="train-mode batchnorm statistics (nn/layers.py "
                "_bn_train_impl): one stacked (C, 2) moment reduction "
                "(default — one activation read, one collective under "
                "data sharding) vs two separate mean/E[x^2] reductions",
        ),
        TuneSpace(
            kernel="fused_conv",
            axes={"impl": ("reference", "pallas"),
                  "schedule": ("twopass", "stats_xla"),
                  "block_rows": (256, 512, 1024)},
            shape_keys=("n", "c"),
            default=lambda shape: dict(_FUSED_CONV_DEFAULT),
            legal=_fused_conv_legal,
            structural=("impl", "schedule"),
            # The schedules legitimately reassociate the f32 moment
            # reduction (tile-sequential vs XLA's tree: ~e-6 on the
            # statistic, a few e-5 on bench-N gradients); a WRONG kernel
            # still lands orders of magnitude outside. Scoped here so
            # the launch-config sweeps keep the tight default.
            parity_tol={"float32": (5e-5, 5e-5)},
            doc="conv-stack BN(+relu) epilogue (ops/fused_conv.py via "
                "nn/layers.bn_act_train): impl 'reference' (the "
                "_bn_train + relu XLA chain — the bitwise default) vs "
                "'pallas' (one fused stats+normalize+relu program); "
                "schedule 'twopass' (in-kernel 2-phase moments) vs "
                "'stats_xla' (XLA reduction + fused normalize pass); "
                "block_rows the flattened-activation tile height",
        ),
        TuneSpace(
            kernel="block_attn",
            axes={"impl": ("reference", "fused"),
                  "epilogue": ("fused", "separate"),
                  "block_b": (1, 2, 4, 8)},
            shape_keys=("b", "t", "d", "h"),
            default=lambda shape: dict(_BLOCK_ATTN_DEFAULT),
            legal=_block_attn_legal,
            structural=("impl", "epilogue"),
            # Like fused_conv: the fused program reorders f32 LN/softmax
            # reductions, and the backward (the reference vjp over the
            # saved inputs) inherits the forward's reassociation through
            # the cotangent. Scoped; launch sweeps keep the default.
            parity_tol={"float32": (5e-5, 5e-5)},
            doc="whole-block attention half (ops/fused_block.py via "
                "models/transformer.Block): impl 'reference' (the "
                "per-op ln1+QKV+attention+proj chain — the bitwise "
                "default) vs 'fused' (ONE pallas program — the "
                "launch-bound small-model candidate); epilogue 'fused' "
                "(projection inside the program) vs 'separate' (stop at "
                "the attention output — the train-dropout shape); "
                "block_b batch rows per grid step",
        ),
    )
}
