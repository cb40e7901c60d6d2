"""Mixture-of-Experts FFN — expert parallelism over a mesh axis.

GShard/Switch-style top-k routing with a fixed per-expert capacity
(reference carries no MoE — this is north-star scale-out surface):

* router logits -> top-k gates, renormalized over the chosen experts;
* routing is GROUPED per batch row (GShard groups): each row's tokens take
  a slot in their expert up to ``capacity = cf * k * T / E`` (overflow
  tokens drop to the residual path — standard Switch behavior);
* dispatch/combine are einsums against a (B, T, E, C) one-hot — O(B*T^2)
  memory, jit-compatible static shapes;
* expert params are STACKED with a leading E dim. Declare
  ``moe_rules(axis="expert")`` (parallel/sharding.py) to shard them over an
  'expert' mesh axis — GSPMD then lowers the dispatch/combine einsums to
  all-to-alls over ICI, which IS expert parallelism; no collective is
  written by hand.

The router's load-balancing auxiliary loss (mean gate fraction x mean
dispatch fraction x E, GShard eq. 4) is returned to the caller; the model
surfaces it in the output batch for the objective to add.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from rocket_tpu.nn.layers import Dense
from rocket_tpu.nn.module import Layer

__all__ = ["MoE", "RoutedExperts", "RoutedExpertsConfig",
           "route_sigmoid_grouped", "route_softmax"]


def _gmm_config(m: int, k: int, n: int, dtype) -> dict:
    """The ``moe_gmm`` tuned config for this device kind / (m, k, n)
    bucket / dtype: the structural ``impl`` axis ('gmm' — explicit
    gather + megablox, the default — vs 'fused' — in-kernel-routed
    ``ops/gather_gmm.py``) plus the tile triple, falling back to the
    hand-picked 512s (docs/performance.md: 512-wide within ~5% of dense
    per row, the 128 default ~2x slower)."""
    from rocket_tpu.tune import get_config

    config = dict(get_config(
        "moe_gmm", shape={"m": m, "k": k, "n": n}, dtype=dtype
    ) or {})
    config.setdefault("impl", "gmm")
    config.setdefault("tile_m", 512)
    config.setdefault("tile_k", 512)
    config.setdefault("tile_n", 512)
    return config


def _gmm_tiling(m: int, k: int, n: int, dtype) -> tuple:
    """Clamped megablox tile triple (see :func:`_gmm_config`)."""
    config = _gmm_config(m, k, n, dtype)
    tile_m = min(config["tile_m"], m)
    if m % tile_m:
        # megablox wants whole row tiles: the tallest that divides ``m`` in
        # whole sublane tiles (640 rows — 64 slots x 10 choices — take 320).
        tile_m = max((d for d in range(8, tile_m, 8) if m % d == 0), default=tile_m)
    return (tile_m, min(config["tile_k"], k), min(config["tile_n"], n))


def _grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` rows grouped by ``group_sizes`` times per-group ``rhs[g]``.

    TPU: the pallas megablox ``gmm`` kernel — with 512-wide tiles it runs
    within ~5% of a dense batched einsum PER ROW (measured at bench-MoE
    shapes; the default 128 tiling is ~2x slower, and
    ``jax.lax.ragged_dot``'s XLA lowering ~1.4x slower — probe record in
    docs/performance.md). Elsewhere (CPU tests) ``ragged_dot`` — identical
    semantics, no Mosaic.

    Accumulation is fp32 on both paths (RKT401: a grouped matmul chains
    partial sums across tile/group boundaries, so a sub-fp32 accumulator
    rounds between partials). The gmm kernel does this by construction —
    an fp32 VMEM ``acc_scratch`` cast to the output dtype once at store —
    so it keeps the operand-dtype output. The XLA ``ragged_dot`` lowering
    has no such internal scratch, and its AD rule mishandles
    ``preferred_element_type`` != operand dtype (fp32 cotangents meet
    bf16 ones in ``add_jaxvals`` — verified on this jax), so fp32
    accumulation goes in through WIDENED OPERANDS and the result is
    downcast after; the operand casts keep the VJP dtypes consistent.
    """
    m, k = lhs.shape
    _, _, n = rhs.shape
    if _megablox_fits(m, k, n):
        from jax.experimental.pallas.ops.tpu.megablox.ops import gmm

        return gmm(lhs, rhs, group_sizes, lhs.dtype,
                   _gmm_tiling(m, k, n, lhs.dtype))
    return _ragged_dot(lhs, rhs, group_sizes)


def _on_tpu() -> bool:
    """Whether this process's default backend is a TPU."""
    return jax.devices()[0].platform == "tpu"


def _megablox_fits(m: int, k: int, n: int) -> bool:
    """Whether the grouped matmul is the megablox kernel: on a TPU, at
    shapes its tiles divide."""
    return _on_tpu() and k % 128 == 0 and n % 128 == 0 and m % 8 == 0


def _ragged_dot(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(
        lhs.astype(jnp.float32), rhs.astype(jnp.float32), group_sizes,
        preferred_element_type=jnp.float32,
    ).astype(lhs.dtype)


@functools.cache
def _named_gmm(name: str):
    """The megablox ``gmm`` under a jit of its own called ``name``: the
    Mosaic custom call takes the innermost jit's name, so the profiler's
    events read ``<name>.<n> custom-call`` and a per-layer metric can sum
    them (megablox itself offers no ``name=``)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    def call(lhs, rhs, group_sizes, tiling):
        return gmm.__wrapped__(lhs, rhs, group_sizes, lhs.dtype, tiling)

    call.__name__ = call.__qualname__ = name
    return jax.jit(call, static_argnames=("tiling",))


def _held_grouped_matmul(name, lhs, rhs, group_sizes):
    """``lhs`` rows ``[0, sum(group_sizes))`` grouped by ``group_sizes``
    times ``rhs[g]``; rows past the last group are NOT computed (on the
    TPU they are whatever the buffer held: the caller masks them). Only
    the row tiles that hold a group's rows are visited, and only the
    weights of a group that has rows are read."""
    m, k = lhs.shape
    n = rhs.shape[2]
    if _megablox_fits(m, k, n):
        return _named_gmm(name)(
            lhs, rhs, group_sizes, tiling=_gmm_tiling(m, k, n, lhs.dtype)
        )
    return _ragged_dot(lhs, rhs, group_sizes)


class MoE(Layer):
    """Top-k routed expert FFN (drop-in for the dense MLP in a block).

    Input (B, T, D) -> output (B, T, D) plus a scalar aux loss.
    """

    def __init__(
        self,
        dim: int,
        hidden: int,
        num_experts: int,
        top_k: int = 2,
        capacity_factor: float = 1.25,
        dispatch: str = "einsum",
    ):
        if not 1 <= top_k <= num_experts:
            raise ValueError(
                f"MoE: top_k {top_k} must be in [1, num_experts={num_experts}]"
            )
        if dispatch not in ("einsum", "scatter", "dropless"):
            raise ValueError(f"MoE: unknown dispatch mode {dispatch!r}")
        self.dim = dim
        self.hidden = hidden
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        #: "einsum": one-hot dispatch/combine tensors (B, T, E, C) — with
        #: C = cf*k*T/E that is cf*k*B*T^2 elements INDEPENDENT of E, the
        #: memory ceiling at long T. GSPMD lowers these einsums to clean
        #: all-to-alls under expert sharding, so it stays the default.
        #: "scatter": scatter-add dispatch / gather combine — O(k*B*T*D),
        #: linear in T; prefer it for long sequences (T >= ~2048) when the
        #: experts are NOT sharded over a mesh axis (XLA's scatter does not
        #: lower to all-to-alls as cleanly). Both modes compute identical
        #: outputs (tested).
        #: "dropless": sort-based dispatch + ``jax.lax.ragged_dot`` grouped
        #: matmuls — does ONLY the routed work (no capacity padding, no
        #: E×C one-hots, no token drops; round-4 verdict ask #3). Single-
        #: device experts only: ragged_dot has no all-to-all lowering under
        #: expert sharding, so keep "einsum" for an 'expert' mesh axis.
        self.dispatch = dispatch
        self.router = Dense(dim, num_experts, use_bias=False)

    def init_params(self, key):
        k_r, k_in, k_out = jax.random.split(key, 3)
        e, d, h = self.num_experts, self.dim, self.hidden
        scale_in = d ** -0.5
        scale_out = h ** -0.5
        return {
            "router": self.router.init(k_r)["params"],
            "experts": {
                "w_in": jax.random.normal(k_in, (e, d, h)) * scale_in,
                "b_in": jnp.zeros((e, h)),
                "w_out": jax.random.normal(k_out, (e, h, d)) * scale_out,
                "b_out": jnp.zeros((e, d)),
            },
        }

    def apply(self, variables, x, *, mode="train", rng=None):
        p = variables["params"]
        # Under the TP-overlap context the residual stream arrives
        # SEQUENCE-SHARDED over the TP axis; routing groups span the full
        # sequence, so the layer gathers its input once at the boundary
        # and re-shards the combined output (parallel/collectives.py —
        # the backward relayouts cross at the gradient wire dtype). The
        # expert einsums inside stay GSPMD's to lower (all-to-alls under
        # an 'expert' mesh axis, exactly as before).
        from rocket_tpu.parallel import collectives as coll

        tp_spec = coll.current_tp()
        if tp_spec is not None and x.ndim == 3 and (
            x.shape[1] % tp_spec.tp_size == 0
        ):
            x = coll.seq_all_gather(tp_spec, x)
        else:
            tp_spec = None
        y, aux = self._apply_inner(p, x, mode=mode, rng=rng)
        if tp_spec is not None:
            y = coll.seq_shard(tp_spec, y)
        return y, aux

    def _apply_inner(self, p, x, *, mode="train", rng=None):
        b, t, d = x.shape
        e, k = self.num_experts, self.top_k

        # -- routing (f32 end-to-end: a bf16 router matmul flips near-tied
        # experts; the Switch/GShard lineage mandates f32 here). The
        # deliberate widening of x marks this as an fp32 island for the
        # precision auditor (RKT405 exempts widened-activation matmuls);
        # the assert pins the convention against future edits. ------------
        logits = x.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
        assert logits.dtype == jnp.float32, (
            "MoE router logits must stay fp32 end-to-end"
        )
        gates = jax.nn.softmax(logits, axis=-1)  # (B, T, E)
        top_gates, top_idx = jax.lax.top_k(gates, k)  # (B, T, K)
        top_gates = top_gates / jnp.maximum(
            jnp.sum(top_gates, axis=-1, keepdims=True), 1e-9
        )

        if self.dispatch == "dropless":
            y = self._apply_dropless(p, x, top_gates, top_idx)
            aux, _ = self._aux_loss(gates, top_idx, e)
            # No capacity, no drops — every routed (token, choice) pair is
            # computed. frac_dropped is identically 0 by construction.
            return y, {
                "aux_loss": aux,
                "frac_dropped": jnp.zeros((), jnp.float32),
            }

        # GShard-style GROUPED routing: each batch row is a routing group
        # with its own capacity, so the dispatch one-hots are
        # (B, T, E, C=cf*k*T/E) — O(B*T^2) elements rather than the
        # O((B*T)^2) an ungrouped formulation costs at scale.
        capacity = max(1, int(self.capacity_factor * t * k / e))

        # Slot assignment per group: a (token, choice) pair's position in
        # its expert = earlier pairs in the group that chose that expert.
        # Choices are ranked k-major so primary routes win slots first.
        flat_idx = jnp.swapaxes(top_idx, 1, 2).reshape(b, k * t)  # k-major
        choice_onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)  # (B, K*T, E)
        position = jnp.cumsum(choice_onehot, axis=1) - choice_onehot
        slot = jnp.sum(position * choice_onehot, axis=-1)  # (B, K*T)
        keep = slot < capacity

        if self.dispatch == "scatter":
            # Linear-in-T dispatch: scatter tokens into (B, E, C, D) expert
            # slots, run the experts, gather back. k-major flat order:
            # position j = choice*T + token, matching flat_idx/slot above.
            slot_c = jnp.minimum(slot, capacity - 1)
            b_ix = jnp.arange(b)[:, None]
            xk = jnp.tile(x, (1, k, 1))  # (B, K*T, D), k-major
            upd = jnp.where(keep[..., None], xk, jnp.zeros_like(xk))
            expert_in = jnp.swapaxes(
                jnp.zeros((b, e, capacity, d), x.dtype)
                .at[b_ix, flat_idx, slot_c]
                .add(upd),
                0, 1,
            )  # (E, B, C, D)
        else:
            # Dispatch/combine tensors (B, T, E, C).
            slot_onehot = jax.nn.one_hot(slot, capacity, dtype=x.dtype) * keep[
                ..., None
            ].astype(x.dtype)  # (B, K*T, C)
            dispatch_kc = (
                choice_onehot.astype(x.dtype)[..., :, None]
                * slot_onehot[..., None, :]
            ).reshape(b, k, t, e, capacity)
            dispatch = jnp.sum(dispatch_kc, axis=1)  # (B, T, E, C) 0/1
            combine = jnp.sum(
                dispatch_kc
                * jnp.swapaxes(top_gates, 1, 2)[..., None, None].astype(x.dtype),
                axis=1,
            )  # (B, T, E, C) gate-weighted
            expert_in = jnp.einsum("btec,btd->ebcd", dispatch, x)

        # -- expert computation (E leading; shard E over 'expert' — GSPMD
        # lowers the einsum-mode dispatch/combine to all-to-alls). The
        # expert matmuls accumulate fp32 (RKT401) and downcast after; the
        # dispatch/combine einsums stay in the compute dtype — their
        # one-hot contractions touch at most one (dispatch) / top_k
        # (combine) nonzero per output, so nothing accumulates. ----------
        ex = p["experts"]
        h = jnp.einsum(
            "ebcd,edh->ebch", expert_in, ex["w_in"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)
        h = jax.nn.gelu(h + ex["b_in"].astype(x.dtype)[:, None, None, :])
        out = jnp.einsum(
            "ebch,ehd->ebcd", h, ex["w_out"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)
        out = out + ex["b_out"].astype(x.dtype)[:, None, None, :]

        if self.dispatch == "scatter":
            picked = jnp.swapaxes(out, 0, 1)[b_ix, flat_idx, slot_c]  # (B,K*T,D)
            picked = jnp.where(keep[..., None], picked, jnp.zeros_like(picked))
            gates_k = (
                jnp.swapaxes(top_gates, 1, 2).reshape(b, k * t, 1).astype(x.dtype)
            )
            y = jnp.sum((picked * gates_k).reshape(b, k, t, d), axis=1)
        else:
            y = jnp.einsum("btec,ebcd->btd", combine, out)

        aux, _ = self._aux_loss(gates, top_idx, e)

        # Capacity utilization: the fraction of routed (token, choice)
        # pairs that found an expert slot. 1 - frac_kept is the dropped
        # fraction (those tokens ride the residual path only); sustained
        # drops mean the balance loss isn't holding or capacity_factor is
        # too tight. Surfaced as batch["moe_frac_dropped"].
        frac_dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))

        return y, {"aux_loss": aux, "frac_dropped": frac_dropped}

    @staticmethod
    def _aux_loss(gates, top_idx, e):
        """GShard eq. 4 load-balancing loss (dispatch-mode independent)."""
        primary = jax.nn.one_hot(top_idx[..., 0], e, dtype=jnp.float32)
        fraction_routed = jnp.mean(primary, axis=(0, 1))  # tokens per expert
        mean_gate = jnp.mean(gates, axis=(0, 1))
        return e * jnp.sum(fraction_routed * mean_gate), fraction_routed

    def _apply_dropless(self, p, x, top_gates, top_idx):
        """Sort-based dropless dispatch: grouped matmuls over exactly the
        routed (token, choice) pairs via ``jax.lax.ragged_dot``.

        The einsum/scatter modes execute ``capacity_factor``x the routed
        FLOPs (expert matmuls run on C padded slots) plus O(B*T*E*C)
        dispatch/combine contractions — measured ~20 ms/step of genuinely
        wasted work at the bench MoE config (docs/performance.md). Here:

        * flatten to N = B*T tokens, NK = N*k (token, choice) pairs;
        * stable-argsort pairs by expert id — per-expert rows contiguous;
        * gather the pair rows of x (NK, D), run both expert matmuls as
          ragged group-matmuls (group sizes = per-expert pair counts);
        * scatter-add gate-weighted outputs back per token.

        No capacity concept: counts are data-dependent VALUES but every
        shape is static (NK rows total), so it jits cleanly. Routing-
        identical to the other modes with unlimited capacity; with finite
        capacity those modes additionally DROP overflow pairs.
        """
        b, t, d = x.shape
        e, k = self.num_experts, self.top_k
        n = b * t
        x_flat = x.reshape(n, d)

        pair_expert = top_idx.reshape(n * k)          # token-major pairs
        pair_token = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
        order = jnp.argsort(pair_expert, stable=True)
        sorted_expert = pair_expert[order]
        sorted_token = pair_token[order]
        counts = jnp.bincount(pair_expert, length=e).astype(jnp.int32)
        gate_sorted = top_gates.reshape(n * k)[order].astype(x.dtype)

        # Structural impl axis (tune kernel ``moe_gmm``, ISSUE 14): the
        # round-5 dropless loss was the GLUE — the materialized
        # x[sorted_token] gather ran at random-row bandwidth
        # (docs/performance.md). impl="fused" routes the in-projection
        # through ops/gather_gmm.py, which gathers the rows inside the
        # kernel's own DMA pipeline; impl="gmm" (the default — and the
        # only behavior with absent tables) is the pre-existing path.
        out = self._dropless_matmuls(
            p, x_flat, sorted_token, sorted_expert, counts, x.dtype
        )

        y = (
            jnp.zeros((n, d), x.dtype)
            .at[sorted_token]
            .add(out * gate_sorted[:, None])
        )
        return y.reshape(b, t, d)

    def _dropless_matmuls(self, p, x_flat, sorted_token, sorted_expert,
                          counts, dtype):
        """Both expert matmuls over the sorted (token, choice) rows —
        gather-explicit ('gmm') or gather-in-kernel ('fused') per the
        ``moe_gmm`` table; ``ROCKET_TPU_MOE_GMM`` force-overrides (the
        fused kernel runs interpreted on CPU under force)."""
        import os

        nk = sorted_token.shape[0]
        d, hidden = p["experts"]["w_in"].shape[1:]
        config = _gmm_config(nk, d, hidden, dtype)
        forced = os.environ.get("ROCKET_TPU_MOE_GMM")
        impl = forced or config["impl"]
        ex = p["experts"]
        if impl == "fused":
            from rocket_tpu.ops.gather_gmm import (
                gather_gmm,
                gather_gmm_supported,
                padded_group_layout,
            )

            on_cpu = jax.devices()[0].platform == "cpu"
            tm = min(config["tile_m"], nk)
            tn = min(config["tile_n"], hidden)
            if gather_gmm_supported(d, hidden, tn) and (
                bool(forced) or not on_cpu
            ):
                row_ids, gsz, padded_pos, m_pad = padded_group_layout(
                    counts, sorted_token, tm, nk,
                    sorted_expert=sorted_expert,
                )
                # Per padded-row expert id (bias gathers), scattered
                # from the ids the sort already produced. Pad rows read
                # expert 0's bias — inert: their outputs are never
                # gathered back through padded_pos.
                pexpert = (
                    jnp.zeros((m_pad,), jnp.int32)
                    .at[padded_pos].set(sorted_expert.astype(jnp.int32))
                )
                h = gather_gmm(
                    x_flat, ex["w_in"].astype(dtype), row_ids, gsz,
                    tile_m=tm, tile_n=tn,
                    interpret=True if on_cpu else None,
                )
                h = jax.nn.gelu(h + ex["b_in"].astype(dtype)[pexpert])
                # The hidden rows are already contiguous in padded-group
                # order — the out-projection needs no gather; the padded
                # groups stay tile-aligned for megablox.
                out = _grouped_matmul(h, ex["w_out"].astype(dtype), gsz)
                out = out + ex["b_out"].astype(dtype)[pexpert]
                return out[padded_pos]                       # (NK, D)

        xs = x_flat[sorted_token]                     # (NK, D)
        h = _grouped_matmul(xs, ex["w_in"].astype(dtype), counts)  # (NK, H)
        h = jax.nn.gelu(h + ex["b_in"].astype(dtype)[sorted_expert])
        out = _grouped_matmul(h, ex["w_out"].astype(dtype), counts)
        return out + ex["b_out"].astype(dtype)[sorted_expert]      # (NK, D)

    def __repr__(self):
        return (
            f"MoE(d={self.dim}, h={self.hidden}, E={self.num_experts}, "
            f"k={self.top_k})"
        )


# -- sigmoid, group-limited, bias-corrected routing over a held share --------


@dataclass(frozen=True)
class RoutedExpertsConfig:
    """A routed FFN as ONE chip of an expert-parallel deployment holds it.
    Two routers (``scoring``): ``"sigmoid"`` — DeepSeek-V3's (arXiv
    2412.19437, §2.1.2 and its auxiliary-loss-free balancing): sigmoid
    scores, a selection bias, group-limited choice
    (:func:`route_sigmoid_grouped`); ``"softmax"`` — Qwen3-Next's and
    Qwen3-MoE's: a softmax over all experts, the ``top_k`` largest,
    renormalised over the chosen (:func:`route_softmax`; no bias, no
    groups)."""

    num_experts: int            # the router's outputs (all chips' experts)
    top_k: int
    hidden: int                 # one routed expert's width
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    #: Width of the shared expert (``n_shared_experts * hidden``); 0 = none.
    shared_hidden: int = 0
    #: The shared expert behind a gate of its own: ``sigmoid(w_sg . x) *
    #: E_shared(x)`` (parameter ``shared/w_sg`` (D, 1)).
    shared_gate: bool = False
    #: "sigmoid" | "softmax": see the class docstring.
    scoring: str = "sigmoid"
    #: ``(offset, count)``: the experts THIS chip holds, ``offset ..
    #: offset + count``. The router scores all ``num_experts``; only the
    #: held experts' part of the sum is computed here. None = all.
    experts_held: Optional[tuple] = None

    @property
    def held(self) -> tuple:
        return self.experts_held or (0, self.num_experts)


def route_sigmoid_grouped(scores_logits, bias, cfg: RoutedExpertsConfig):
    """``(weights (N, k) f32, experts (N, k) int32)`` from router logits
    ``(N, E)`` in float32: ``s = sigmoid(logits)``; ``s' = s + bias``
    decides the SELECTION only — each of ``n_group`` groups is scored by
    the sum of its two best ``s'``, the best ``topk_group`` groups stay,
    the ``top_k`` best ``s'`` within them are chosen; the WEIGHTS are the
    unbiased ``s`` of the chosen, normalised over all ``top_k`` (held here
    or not) and times ``routed_scaling_factor``."""
    n, e = scores_logits.shape
    s = jax.nn.sigmoid(scores_logits.astype(jnp.float32))
    biased = s + bias.astype(jnp.float32)[None, :]
    if cfg.n_group > 1:
        per = e // cfg.n_group
        grouped = biased.reshape(n, cfg.n_group, per)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, cfg.topk_group)     # (N, kept)
        kept = jnp.zeros((n, cfg.n_group), bool).at[
            jnp.arange(n)[:, None], keep
        ].set(True)
        biased = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(n, e)
    _, experts = jax.lax.top_k(biased, cfg.top_k)
    weights = jnp.take_along_axis(s, experts, axis=1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scaling_factor, experts.astype(jnp.int32)


def route_softmax(scores_logits, cfg: RoutedExpertsConfig):
    """``(weights (N, k) f32, experts (N, k) int32)`` from router logits
    ``(N, E)`` in float32: ``p = softmax(logits)`` over ALL ``E`` experts;
    the ``top_k`` largest are chosen; the weights are their ``p``,
    normalised (``norm_topk_prob``) over all ``top_k`` — held here or not
    — and times ``routed_scaling_factor``."""
    p = jax.nn.softmax(scores_logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(p, cfg.top_k)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=1, keepdims=True)
    return weights * cfg.routed_scaling_factor, experts.astype(jnp.int32)


class RoutedExperts(Layer):
    """One chip's share of a routed expert layer: gated, bias-free experts
    ``E(x) = W_down(silu(W_gate x) * W_up x)``, the router the
    configuration's ``scoring`` names (:func:`route_sigmoid_grouped` or
    :func:`route_softmax`), a shared expert every chip computes alike
    (behind ``sigmoid(w_sg . x)`` with ``shared_gate``), and
    ``experts_held``::

        y = sum_{i chosen and held} w_i * E_i(x) + E_shared(x)

    What the absent experts would add is left out; nothing stands in for
    the exchange. Dropless: the (token, choice) pairs routed to a held
    expert are sorted to the front by expert and run through two grouped
    matmuls (``moe_gmm_gate_up``, ``moe_gmm_down``) whose work follows the
    pairs that are there — a pair routed to an absent expert costs no
    matmul row and no token is ever dropped. ``apply`` returns ``(y,
    counts)``: ``counts`` (held,) int32, the pairs each held expert got.
    ``token_mask`` (the shape of ``x`` without its last axis) marks the
    rows that are tokens: the pairs of a padding row or an idle slot are
    treated as absent too, and its output is only the shared expert's.

    Parameters: ``router`` ``{w (D, E), bias (E,)}`` (the bias is
    ``e_score_correction_bias``; the softmax router has none), ``experts``
    ``{w_gate_up (held, D, 2H), w_down (held, H, D)}`` (gate and up side
    by side: one matmul), ``shared`` ``{w_gate, w_up, w_down[, w_sg]}``."""

    def __init__(self, dim: int, config: RoutedExpertsConfig):
        c = config
        offset, count = c.held
        if not (0 <= offset and offset + count <= c.num_experts and count > 0):
            raise ValueError(
                f"RoutedExperts: experts_held {c.held} outside "
                f"[0, {c.num_experts})"
            )
        if c.num_experts % c.n_group or not 1 <= c.topk_group <= c.n_group:
            raise ValueError(
                f"RoutedExperts: {c.num_experts} experts in {c.n_group} "
                f"groups, {c.topk_group} kept"
            )
        if c.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"RoutedExperts: unknown scoring {c.scoring!r}")
        if c.scoring == "softmax" and c.n_group != 1:
            raise ValueError("RoutedExperts: the softmax router has no groups")
        if c.shared_gate and not c.shared_hidden:
            raise ValueError("RoutedExperts: shared_gate without a shared expert")
        self.dim = dim
        self.config = c

    def init_params(self, key):
        c, d = self.config, self.dim
        held = c.held[1]
        ks = jax.random.split(key, 6)
        normal = jax.random.normal
        params = {
            "router": {"w": normal(ks[0], (d, c.num_experts)) * d ** -0.5},
            "experts": {
                "w_gate_up": normal(ks[1], (held, d, 2 * c.hidden)) * d ** -0.5,
                "w_down": normal(ks[2], (held, c.hidden, d)) * c.hidden ** -0.5,
            },
        }
        if c.shared_hidden:
            hs = c.shared_hidden
            params["shared"] = {
                "w_gate": normal(ks[3], (d, hs)) * d ** -0.5,
                "w_up": normal(ks[4], (d, hs)) * d ** -0.5,
                "w_down": normal(ks[5], (hs, d)) * hs ** -0.5,
            }
            if c.shared_gate:
                params["shared"]["w_sg"] = jnp.zeros((d, 1))
        if c.scoring == "sigmoid":
            params["router"]["bias"] = jnp.zeros((c.num_experts,))
        return params

    def apply(self, variables, x, *, mode="eval", rng=None, token_mask=None):
        if mode == "train":
            raise NotImplementedError(
                "RoutedExperts has no training path (no balance term for "
                "its router): eval and serving only"
            )
        p, c = variables["params"], self.config
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        n, k = x2.shape[0], c.top_k
        offset, held = c.held
        with jax.named_scope("moe/route"):
            # Float32 end to end: a bfloat16 score within rounding of the
            # k-th/(k+1)-th boundary picks another expert.
            logits = jnp.dot(
                x2.astype(jnp.float32), p["router"]["w"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            if c.scoring == "softmax":
                weights, experts = route_softmax(logits, c)
            else:
                weights, experts = route_sigmoid_grouped(
                    logits, p["router"]["bias"], c
                )
            # Held pairs to the front, by expert; absent pairs behind them.
            local = experts.reshape(n * k) - offset
            here = (local >= 0) & (local < held)
            if token_mask is not None:
                here &= jnp.repeat(token_mask.reshape(n), k)
            group = jnp.where(here, local, held)
            order = jnp.argsort(group, stable=True)
            counts = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
            token = (order // k).astype(jnp.int32)
            gate = weights.reshape(n * k)[order]
            live = jnp.arange(n * k, dtype=jnp.int32) < jnp.sum(counts)
        with jax.named_scope("moe/experts"):
            ex = p["experts"]
            h = _held_grouped_matmul(
                "moe_gmm_gate_up", x2[token], ex["w_gate_up"].astype(x.dtype),
                counts,
            )
            h = jax.nn.silu(h[:, :c.hidden]) * h[:, c.hidden:]
            out = _held_grouped_matmul(
                "moe_gmm_down", h, ex["w_down"].astype(x.dtype), counts
            )
            out = jnp.where(
                live[:, None], out.astype(jnp.float32) * gate[:, None], 0.0
            )
            y = jnp.zeros((n, shape[-1]), jnp.float32).at[token].add(out)
        if c.shared_hidden:
            with jax.named_scope("moe/shared"):
                sh = p["shared"]
                dt = x.dtype
                hid = jax.nn.silu(x2 @ sh["w_gate"].astype(dt)) * (
                    x2 @ sh["w_up"].astype(dt)
                )
                out = (hid @ sh["w_down"].astype(dt)).astype(jnp.float32)
                if c.shared_gate:
                    out = out * jax.nn.sigmoid(
                        (x2 @ sh["w_sg"].astype(dt)).astype(jnp.float32))
                y = y + out
        return y.astype(x.dtype).reshape(shape), counts

    def __repr__(self):
        c = self.config
        return (
            f"RoutedExperts(d={self.dim}, h={c.hidden}, E={c.num_experts}, "
            f"k={c.top_k}, held={c.held})"
        )
