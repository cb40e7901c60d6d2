"""Decoder-only transformer LM (char-LM and GPT-2 families).

North-star configs (BASELINE.json configs[2,4]): TinyShakespeare
char-Transformer and GPT-2 124M with pjit param sharding + bfloat16. The
reference has no model code — models are user-space — but the framework ships
these as the flagship north-star models.

TPU design: pre-LN blocks, fused QKV, GELU MLP at 4x width, float32 layernorm/
softmax inside a bf16 compute path, GPT-2 residual init scaling. Tensor
parallelism comes from OUTSIDE the model: ``parallel/sharding.py`` maps the
param tree produced here onto a ('data', 'model') mesh (attention/MLP kernels
sharded on the model axis), XLA inserting the collectives.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from rocket_tpu.nn.attention import MultiHeadAttention
from rocket_tpu.nn.layers import Dense, Dropout, Embedding, LayerNorm, RMSNorm
from rocket_tpu.nn.module import Layer, Model, Variables

__all__ = ["AttentionKind", "TransformerConfig", "TransformerLM", "Block",
           "next_token_loss", "generate"]


@dataclass(frozen=True)
class AttentionKind:
    """What one kind of layer changes of the configuration's attention (a
    field left None keeps the configuration's): its query heads, its rotary
    (``rope`` False: none; base, share of each head's lanes, YaRN) and its
    ``window`` (0: it sees the whole context and caches pages; > 0: it sees
    the last ``window`` positions and keeps a ring of them a slot). A
    ``state`` kind is no attention at all: the configuration's state mixer
    (``ssm``, ``gdn`` or ``lightning``), which carries a state a slot. A
    ``sparse`` kind caches pages and attends the blocks it picks through
    the configuration's ``sparse_attention``."""

    num_heads: Optional[int] = None
    rope_base: Optional[float] = None
    rope_fraction: Optional[float] = None
    rope_yarn: Optional[Any] = None
    window: int = 0
    rope: Optional[bool] = None
    state: bool = False
    sparse: bool = False


#: The kind of a state layer of a model described by the period-and-offset
#: rule (no ``layer_types``).
_STATE_KIND = AttentionKind(state=True)

#: Memoized jax.checkpoint policies (see TransformerConfig.remat_policy).
_REMAT_POLICIES: dict = {}


@dataclass
class TransformerConfig:
    vocab_size: int
    max_seq_len: int
    dim: int
    num_layers: int
    num_heads: int
    #: Grouped-query attention: K/V heads (None = num_heads = standard MHA;
    #: 1 = MQA). Shrinks the KV cache and K/V projection by
    #: num_heads/num_kv_heads. Training attention uses the flash kernel
    #: (K/V broadcast to full heads) when shapes allow, else a grouped
    #: einsum; cached decode always runs grouped on the small cache.
    num_kv_heads: Optional[int] = None
    mlp_ratio: int = 4
    dropout: float = 0.0
    #: Causal (decoder) attention by default; False builds encoder blocks
    #: (ViT reuses Block this way — ``models/vit.py``).
    causal: bool = True
    tied_embeddings: bool = True
    #: "auto" | "xla" | "flash" | "ring" — see ``nn.attention.resolve_impl``;
    #: "ring" shards the sequence over the mesh's ``seq_axis`` (long-context
    #: sequence parallelism, ``parallel/ring_attention.py``).
    attention_impl: str = "auto"
    #: Mesh axis for impl="ring".
    seq_axis: str = "seq"
    #: Fold the L blocks into one ``lax.scan`` over stacked params: the block
    #: is traced/compiled ONCE instead of L times (GPT-2 compile drops by
    #: minutes) and the param tree gets a single ``blocks_stacked`` subtree
    #: with a leading L dim (sharding rules left-pad specs accordingly).
    scan_layers: bool = False
    #: Rematerialize each scanned block in the backward pass (the standard
    #: scan+remat recipe — per-layer granularity beats a whole-forward
    #: checkpoint). Only meaningful with scan_layers.
    scan_remat: bool = True
    #: Selective-remat policy for the scanned blocks (round-3 verdict ask
    #: #5: all-or-nothing scan_remat recomputes every block and costs ~18%
    #: throughput, and pipeline parallelism REQUIRES scan_layers).
    #: None = full per-block remat (max memory savings); "dots" = save
    #: matmul outputs, recompute elementwise/norm chains
    #: (jax.checkpoint_policies.dots_with_no_batch_dims_saveable);
    #: "block_io" = save only each block's attention and MLP outputs
    #: (checkpoint_name tags), recompute projections and the flash forward.
    #: Measured taxes on GPT-2 124M: see docs/performance.md.
    scan_remat_policy: Optional[str] = None
    #: Unroll factor for the layer scan (lax.scan unroll=): keeps the
    #: stacked (L, ...) param layout (sharding/pipeline compatible) while
    #: letting XLA schedule several blocks as straight-line code. Measured
    #: effects in docs/performance.md.
    scan_unroll: int = 1
    #: Pipeline parallelism: run the (scan_layers-stacked) blocks as GPipe
    #: stages over this mesh axis (``parallel/pipeline.py``); shard the
    #: stacked params with ``parallel.sharding.pipeline_rules``. Requires
    #: scan_layers and num_layers divisible by the axis size.
    pipeline_axis: Optional[str] = None
    pipeline_microbatches: Optional[int] = None
    #: Pipeline schedule: "gpipe" (default — all-forward-then-all-backward
    #: by autodiff of the forward pipeline; per-stage live activations grow
    #: O(M) in the microbatch count) or "1f1b" (one-forward-one-backward:
    #: the train step runs loss+backward INSIDE the pipelined program via
    #: ``parallel.pipeline.pipeline_train_1f1b``; per-stage live
    #: activations are O(P) — the standard at real pipeline depth).
    #: 1F1B requirements: a Loss objective that consumes ``batch["nll"]``
    #: (``next_token_loss`` does), dense blocks (no MoE aux channel), and
    #: eval/generate still run the GPipe forward. Selecting it changes the
    #: training-step construction (``Module`` asks the model for
    #: ``pipelined_value_and_grad``), not the model's parameters.
    pipeline_schedule: str = "gpipe"
    #: Mixture-of-Experts FFN: replace each block's dense MLP with
    #: ``num_experts`` routed experts (``nn/moe.py``); 0 = dense. Shard the
    #: stacked expert params over an 'expert' mesh axis with
    #: ``parallel.sharding.moe_rules`` for expert parallelism.
    num_experts: int = 0
    expert_top_k: int = 2
    expert_capacity_factor: float = 1.25
    #: "einsum" (default; one-hot dispatch, clean all-to-alls under expert
    #: sharding but O(B*T^2) memory) or "scatter" (linear in T — prefer for
    #: long sequences without an 'expert' mesh axis). See ``nn/moe.py``.
    expert_dispatch: str = "einsum"
    #: Aux load-balancing loss weight, surfaced as batch["moe_aux_loss"]
    #: and added by ``next_token_loss``.
    moe_aux_weight: float = 0.01
    #: Activation dtype for the trunk (e.g. "bfloat16"). The LM's input is
    #: int tokens, so ``Module(compute_dtype=...)``'s float-batch cast never
    #: fires — without this the f32 embedding gather silently promotes the
    #: ENTIRE model to f32 compute (≈2x MXU time). Params stay f32 masters;
    #: layernorm/softmax math stays f32 internally.
    activation_dtype: Optional[str] = None
    #: Positional encoding: "learned" (GPT-2 wpe table), "rope" (rotary,
    #: applied to q/k inside attention; no wpe params) or "none" (no
    #: positional signal outside the mixers: Jamba). RoPE is the
    #: Llama-family default and composes with num_kv_heads (GQA).
    pos_embedding: str = "learned"
    rope_base: float = 10000.0
    #: Normalizer: "layernorm" (GPT-2) or "rmsnorm" (Llama family).
    norm: str = "layernorm"
    #: Block FFN: "gelu" (GPT-2, fc_in 4x + gelu + fc_out) or "swiglu"
    #: (Llama family: fused gate+up projection, silu(gate) * up, down).
    mlp: str = "gelu"
    #: Fused head+cross-entropy chunk size (0 = off). In train mode the
    #: model skips materializing (B, T, V) logits and instead computes the
    #: next-token NLL directly (``batch["nll"]``), scanning the head
    #: projection + softmax-CE over T-chunks under ``jax.checkpoint``: the
    #: backward recomputes each chunk's logits, so the saved residual is x
    #: (B, T, D) instead of the logits. At GPT-2 shapes the full-logits path
    #: moves ~2.5 GB/step of HBM (bf16 logits + their f32 upcast) and is the
    #: largest single allocation in the step. ``next_token_loss`` consumes
    #: either form. Eval mode always materializes logits (metrics need them).
    loss_chunk: int = 0
    #: Latent attention (MLA, ``nn.attention.LatentAttention``) in place of
    #: multi-head K/V attention: a ``LatentAttentionConfig``. The layer
    #: rotates its own decoupled keys, so ``pos_embedding`` must be "rope";
    #: its serving cache is ONE latent array (:attr:`kv_pool_lanes`).
    latent_attention: Optional[Any] = None
    #: Routed expert FFN of the sigmoid / group-limited kind with a shared
    #: expert and an ``experts_held`` share (``nn.moe.RoutedExperts``): a
    #: ``RoutedExpertsConfig``. The first ``first_dense_layers`` blocks
    #: keep the dense FFN (of ``mlp`` kind and ``mlp_hidden`` width).
    #: Serving and eval only: the router's balance has no training path.
    routed_experts: Optional[Any] = None
    first_dense_layers: int = 0
    #: Dense FFN width where it is not ``mlp_ratio * dim``.
    mlp_hidden: Optional[int] = None
    #: Biases on the dense FFN's projections (False: the Llama/DeepSeek
    #: families carry none).
    mlp_bias: bool = True
    #: Epsilon of every normalizer (None = the normalizer's default).
    norm_eps: Optional[float] = None
    #: Biases on the attention projections (False: the Llama/Jamba kind).
    attn_bias: bool = True
    #: State-space mixers (``nn.ssm.MambaMixer``) in place of attention:
    #: an ``SSMConfig``. Layer ``i`` keeps attention where ``i %
    #: attn_layer_period == attn_layer_offset`` (Jamba's rule; period 0 =
    #: every layer is a mixer) and is a mixer elsewhere. A mixer caches no
    #: pages: what it carries is a fixed-size state a SLOT
    #: (:meth:`slot_state_shapes`). Such a stack cannot be scanned
    #: (``scan_layers``) or pipelined: its layers are not alike.
    ssm: Optional[Any] = None
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    #: Gated DeltaNet mixers (``nn.gdn.GatedDeltaNet``) as the state
    #: layers, in place of ``ssm``: a ``GatedDeltaNetConfig``. The same
    #: period-and-offset rule says which layers keep attention; what a slot
    #: carries is a MATRIX a head (:meth:`slot_state_shapes`).
    gdn: Optional[Any] = None
    #: Attention head width where it is not ``dim // num_heads``.
    head_dim: Optional[int] = None
    #: Gated attention (``nn.attention.MultiHeadAttention``): the query
    #: projection twice as wide, ``sigmoid(gate) * attention`` before the
    #: output projection.
    attn_gate: bool = False
    #: A per-head RMSNorm of q and of k (one weight over the head's lanes).
    qk_norm: bool = False
    #: Share of each head's lanes that rotate (the first ones).
    rope_fraction: float = 1.0
    #: RMSNorm weights stored about zero and applied as ``1 + w``.
    norm_zero_centered: bool = False
    #: One gate scalar a head, ``sigmoid(x w_g,h)``, on the attention's
    #: output (``nn.attention.MultiHeadAttention(head_gate=True)``).
    attn_head_gate: bool = False
    #: The attention kind of each layer, by name (``("full_attention",
    #: "sliding_attention", ...)``, as a published ``layer_types`` gives it;
    #: a list longer than ``num_layers`` is read from its start), and what
    #: each name changes of the attention the fields above describe: an
    #: :class:`AttentionKind`. Empty = every layer is the one kind.
    layer_types: tuple = ()
    attention_kinds: Optional[dict] = None
    #: Lightning-attention mixers (``nn.lightning.LightningAttention``) as
    #: the state layers, in place of ``ssm`` or ``gdn``: a
    #: ``LightningConfig``. Which layers they are is ``layer_types``' to say
    #: (kinds with ``state``).
    lightning: Optional[Any] = None
    #: Block-sparse attention (``ops.paged_attention.SparseAttentionConfig``)
    #: for the kinds with ``sparse``: a compressed-key cache a slot beside
    #: the pages (:meth:`slot_state_shapes`).
    sparse_attention: Optional[Any] = None
    #: muP scalings (MiniCPM's): the embedding times ``embed_scale``, each
    #: residual branch times ``residual_scale`` and the last hidden state
    #: over ``logit_divisor`` before the head. 1 = off.
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    #: Label smoothing for ``next_token_loss``: the target distribution is
    #: (1-eps) one-hot + eps uniform. Lives on the CONFIG (not the
    #: objective) so the fused (loss_chunk) and full-logits paths apply the
    #: same smoothing — the model threads it to whichever path runs.
    label_smoothing: float = 0.0

    def remat_policy(self):
        """Resolve ``scan_remat_policy`` to a jax.checkpoint policy (or
        None for full remat). Memoized per name: policy factories return a
        FRESH closure per call, and the policy object keys the compiled-
        pipeline cache (``parallel.pipeline._CACHE``) — an unmemoized
        closure would defeat that cache every invocation."""
        name = self.scan_remat_policy
        if name is None:
            return None
        pol = _REMAT_POLICIES.get(name)
        if pol is None:
            if name == "dots":
                pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            elif name == "block_io":
                pol = jax.checkpoint_policies.save_only_these_names(
                    "attn_out", "mlp_out"
                )
            else:
                raise ValueError(
                    f"TransformerConfig: unknown scan_remat_policy "
                    f"{name!r} (None | 'dots' | 'block_io')"
                )
            _REMAT_POLICIES[name] = pol
        return pol

    def validate(self) -> None:
        """Config-level knob validation — called by TransformerLM and Block
        so a bad value fails fast regardless of which submodule is built."""
        if self.scan_remat_policy is not None:
            self.remat_policy()  # fail fast on unknown values
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"TransformerConfig: unknown norm {self.norm!r}")
        if self.mlp not in ("gelu", "swiglu"):
            raise ValueError(f"TransformerConfig: unknown mlp {self.mlp!r}")
        if self.pos_embedding not in ("learned", "rope", "none"):
            raise ValueError(
                f"TransformerConfig: unknown pos_embedding {self.pos_embedding!r}"
            )
        if not 0.0 <= self.label_smoothing < 1.0:
            raise ValueError(
                f"TransformerConfig: label_smoothing must be in [0, 1), got "
                f"{self.label_smoothing}"
            )
        if self.num_experts > 0 and self.mlp != "gelu":
            raise ValueError(
                f"TransformerConfig: mlp={self.mlp!r} has no effect with "
                "num_experts > 0 (nn.moe.MoE's experts are two-matrix GELU; "
                "gated experts are routed_experts)"
            )
        if self.routed_experts is not None:
            if self.num_experts > 0:
                raise ValueError(
                    "TransformerConfig: num_experts (nn.moe.MoE) and "
                    "routed_experts (nn.moe.RoutedExperts) are two kinds "
                    "of expert layer; give one"
                )
            if not 0 <= self.first_dense_layers <= self.num_layers:
                raise ValueError(
                    f"TransformerConfig: first_dense_layers "
                    f"{self.first_dense_layers} outside [0, {self.num_layers}]"
                )
            if self.scan_layers and 0 < self.first_dense_layers < self.num_layers:
                raise ValueError(
                    "TransformerConfig: scan_layers needs every block alike; "
                    "dense-then-routed layers run as a Python loop"
                )
            if self.pipeline_axis:
                raise ValueError(
                    "TransformerConfig: routed_experts has no pipelined path"
                )
        elif self.first_dense_layers:
            raise ValueError(
                "TransformerConfig: first_dense_layers without routed_experts"
            )
        if sum(m is not None for m in (self.ssm, self.gdn, self.lightning)) > 1:
            raise ValueError(
                "TransformerConfig: ssm, gdn and lightning are kinds of state "
                "layer, and a model holds no two kinds of state layer; give one")
        if self.norm_zero_centered and self.norm != "rmsnorm":
            raise ValueError(
                "TransformerConfig: norm_zero_centered is RMSNorm's")
        if self.state_mixer is not None:
            if self.attn_layer_period < 0 or (
                self.attn_layer_period
                and not 0 <= self.attn_layer_offset < self.attn_layer_period
            ):
                raise ValueError(
                    f"TransformerConfig: attn_layer_offset "
                    f"{self.attn_layer_offset} outside [0, attn_layer_period "
                    f"{self.attn_layer_period})"
                )
            if self.scan_layers or self.pipeline_axis:
                raise ValueError(
                    "TransformerConfig: scan_layers and pipeline_axis need "
                    "every block alike; state-space and attention layers "
                    "run as a Python loop"
                )
            if self.latent_attention is not None:
                raise ValueError(
                    "TransformerConfig: ssm beside latent_attention has no "
                    "serving pool (one kind of page a model)"
                )
        elif self.attn_layer_period or self.attn_layer_offset:
            raise ValueError(
                "TransformerConfig: attn_layer_period / attn_layer_offset "
                "without ssm or gdn"
            )
        if self.layer_types:
            kinds = self.attention_kinds or {}
            unknown = set(self.layer_types[:self.num_layers]) - set(kinds)
            if len(self.layer_types) < self.num_layers or unknown:
                raise ValueError(
                    f"TransformerConfig: layer_types must name an "
                    f"attention_kinds entry for each of {self.num_layers} "
                    f"layers (unknown: {sorted(unknown)})")
            used = [kinds[t] for t in self.layer_types[:self.num_layers]]
            if any(k.state for k in used) and (
                    self.state_mixer is None or self.attn_layer_period):
                raise ValueError(
                    "TransformerConfig: a state kind needs a state mixer (ssm, "
                    "gdn or lightning), and layer_types in place of "
                    "attn_layer_period")
            if any(k.sparse for k in used) and (
                    self.sparse_attention is None or self.latent_attention is not None
                    or self.scan_layers or self.pipeline_axis):
                raise ValueError(
                    "TransformerConfig: a sparse kind needs sparse_attention, "
                    "multi-head attention and a Python loop of layers")
            windows = {self.attention_kind(i).window for i in range(self.num_layers)}
            if len(windows - {0}) > 1:
                raise ValueError(
                    f"TransformerConfig: one window a model, not {sorted(windows - {0})}")
            if self.window_layers and (
                    self.state_mixer is not None or self.latent_attention is not None
                    or self.scan_layers or self.pipeline_axis):
                raise ValueError(
                    "TransformerConfig: window layers keep a ring a slot beside "
                    "the paged K/V of multi-head attention: no ssm, gdn, "
                    "latent_attention, scan_layers or pipeline_axis")
        elif self.attention_kinds:
            raise ValueError("TransformerConfig: attention_kinds without layer_types")
        if self.latent_attention is not None and self.pos_embedding != "rope":
            raise ValueError(
                "TransformerConfig: latent_attention rotates its own "
                "decoupled keys; set pos_embedding='rope'"
            )
        if self.pipeline_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"TransformerConfig: unknown pipeline_schedule "
                f"{self.pipeline_schedule!r} ('gpipe' | '1f1b')"
            )
        if self.pipeline_schedule == "1f1b" and self.num_experts > 0:
            raise ValueError(
                "TransformerConfig: pipeline_schedule='1f1b' does not carry "
                "the MoE aux-loss channel; use 'gpipe' for MoE pipelines."
            )
        if self.pipeline_schedule == "1f1b" and not self.pipeline_axis:
            raise ValueError(
                "TransformerConfig: pipeline_schedule='1f1b' requires "
                "pipeline_axis — without it the model would silently train "
                "unpipelined on the standard O(M)-memory path."
            )

    def norm_cls(self):
        """The configured normalizer class — single source of truth for
        Block (ln1/ln2) and TransformerLM (ln_f). Callers run
        :meth:`validate` first; unknown values fall through to it."""
        self.validate()
        return RMSNorm if self.norm == "rmsnorm" else LayerNorm

    def make_norm(self, features: int):
        """A normalizer of the configured class and epsilon."""
        cls = self.norm_cls()
        how = {} if self.norm_eps is None else {"eps": self.norm_eps}
        if self.norm_zero_centered:
            how["zero_centered"] = True
        return cls(features, **how)

    @property
    def state_mixer(self):
        """The state layers' mixer configuration, of whichever kind is
        given (``ssm``, ``gdn`` or ``lightning``), or None: it declares
        what a slot carries (``state_shapes``) and builds the layer
        (``make_mixer``)."""
        if self.ssm is not None:
            return self.ssm
        return self.gdn if self.gdn is not None else self.lightning

    @property
    def kv_pool_lanes(self) -> tuple:
        """What one layer caches per token in the serving pool: the lanes
        of each pool array. Two arrays of ``Hkv * head_dim`` (K and V), or
        ONE latent array under ``latent_attention`` — the one description
        ``serve/kv_pool.py`` sizes the pool from."""
        if self.latent_attention is not None:
            return (self.latent_attention.pool_lanes,)
        lanes = (self.num_kv_heads or self.num_heads) * (
            self.head_dim or self.dim // self.num_heads
        )
        return (lanes, lanes)

    def is_state_layer(self, layer_idx: int) -> bool:
        """Whether layer ``layer_idx`` is a state mixer (else it is
        attention and caches pages or a ring): its kind's ``state``."""
        return self.state_mixer is not None and self.attention_kind(layer_idx).state

    @property
    def state_layers(self) -> int:
        """How many layers carry a per-slot state."""
        return sum(self.is_state_layer(i) for i in range(self.num_layers))

    def attention_kind(self, layer_idx: int) -> AttentionKind:
        """Layer ``layer_idx``'s :class:`AttentionKind`: ``layer_types``
        read once, here. A model without it has one plain kind and, with a
        state mixer, the state kind wherever the period-and-offset rule
        (Jamba's: attention where ``i % attn_layer_period ==
        attn_layer_offset``; period 0: nowhere) puts no attention."""
        if self.layer_types:
            return self.attention_kinds[self.layer_types[layer_idx]]
        period = self.attn_layer_period
        if self.state_mixer is not None and not (
                period and layer_idx % period == self.attn_layer_offset):
            return _STATE_KIND
        return AttentionKind()

    @property
    def sparse_layers(self) -> int:
        """How many layers attend the blocks they pick (and keep
        compressed keys a slot)."""
        return sum(self.attention_kind(i).sparse for i in range(self.num_layers))

    @property
    def window_layers(self) -> int:
        """How many layers attend a window (and keep a ring a slot)."""
        return sum(self.attention_kind(i).window > 0
                   for i in range(self.num_layers))

    @property
    def window(self) -> int:
        """The rows a window layer's ring holds a slot (0: no window)."""
        return max((self.attention_kind(i).window
                    for i in range(self.num_layers)), default=0)

    @property
    def cache_layers(self) -> int:
        """How many layers cache pages: the layer rows of the serving
        pool. An attention layer's ``layer=`` coordinate in the pool is its
        index among these. A window layer keeps a ring instead."""
        return self.num_layers - self.state_layers - self.window_layers

    @property
    def slot_state_shapes(self) -> tuple:
        """What ONE slot carries beside its pages, for every array of the
        per-slot state: ``((state layers, per-slot shape, dtype), ...)`` —
        empty for a model whose every layer caches pages. The one
        description ``serve/kv_pool.py`` sizes the state arrays from. The
        window layers' rings are two such arrays, K and V, each ``(window,
        Hkv * head_dim)`` a slot in the activation dtype."""
        if self.window_layers:
            ring = (self.window, self.kv_pool_lanes[0])
            dtype = self.activation_dtype or "float32"
            return ((self.window_layers, ring, dtype),) * 2
        if self.sparse_layers:
            # Behind the mixers' state, the sparse layers' compressed keys:
            # a row of Hkv * head_dim lanes every kernel_stride positions.
            dtype = self.activation_dtype or "float32"
            units = self.sparse_attention.units(self.max_seq_len)
            mixers = () if not self.state_layers else tuple(
                (self.state_layers, shape, kind) for shape, kind in
                self.state_mixer.state_shapes(dtype))
            return mixers + (
                (self.sparse_layers, (units, self.kv_pool_lanes[0]), dtype),)
        if not self.state_layers:
            return ()
        return tuple(
            (self.state_layers, shape, dtype) for shape, dtype in
            self.state_mixer.state_shapes(self.activation_dtype or "float32")
        )

    @staticmethod
    def char_lm(vocab_size: int = 128, max_seq_len: int = 256) -> "TransformerConfig":
        # num_heads=4 (head_dim 64, the GPT-2 ratio), not 8: head_dim 32
        # fills only a quarter of the MXU's 128 lanes in both attention
        # matmuls, and the flash kernels were 38% of the step's device
        # time. Same-session sweep at d=256: H=8 32.7% MFU, H=4 38.3%,
        # H=2 41.3%; training loss identical to 0.01 nats over 59 steps
        # (docs/performance.md char-LM section).
        return TransformerConfig(
            vocab_size=vocab_size, max_seq_len=max_seq_len,
            dim=256, num_layers=6, num_heads=4, dropout=0.1,
            activation_dtype="bfloat16",
        )

    @staticmethod
    def gpt2_124m(vocab_size: int = 50257, max_seq_len: int = 1024) -> "TransformerConfig":
        return TransformerConfig(
            vocab_size=vocab_size, max_seq_len=max_seq_len,
            dim=768, num_layers=12, num_heads=12, dropout=0.1,
            activation_dtype="bfloat16", loss_chunk=128,
        )

    @staticmethod
    def llama_style(
        vocab_size: int = 50257,
        max_seq_len: int = 1024,
        dim: int = 768,
        num_layers: int = 12,
        num_heads: int = 12,
        num_kv_heads: int = 4,
    ) -> "TransformerConfig":
        """Llama-family recipe at any size: RoPE positions, RMSNorm,
        SwiGLU FFN, grouped-query attention, untied head."""
        return TransformerConfig(
            vocab_size=vocab_size, max_seq_len=max_seq_len,
            dim=dim, num_layers=num_layers, num_heads=num_heads,
            num_kv_heads=num_kv_heads, pos_embedding="rope", norm="rmsnorm",
            mlp="swiglu", tied_embeddings=False, dropout=0.0,
            activation_dtype="bfloat16", loss_chunk=128,
        )

    @staticmethod
    def gpt2_350m(vocab_size: int = 50257, max_seq_len: int = 1024) -> "TransformerConfig":
        """GPT-2 medium (~354M params). The wider (d=1024) matmuls fill the
        MXU better than 124M: measured ~51% single-chip MFU where the same
        contention window gave 124M ~45%."""
        return TransformerConfig(
            vocab_size=vocab_size, max_seq_len=max_seq_len,
            dim=1024, num_layers=24, num_heads=16, dropout=0.1,
            activation_dtype="bfloat16", loss_chunk=128,
        )


class Block(Layer):
    """Pre-LN transformer block: x += mixer(ln1(x)); x += mlp(ln2(x)) —
    the mixer attention (``attn``, parameters ``attn``) or, in a state
    layer (``TransformerConfig.is_state_layer``), a state-space mixer
    (``mixer``, parameters ``mixer``)."""

    def __init__(self, config: TransformerConfig, layer_idx: int):
        c = config
        c.validate()
        self.ln1 = c.make_norm(c.dim)
        self.latent = c.latent_attention is not None
        self.attn = self.mixer = None
        kind = c.attention_kind(layer_idx)
        #: The rows this layer's attention sees (0: the whole context).
        self.window = kind.window
        #: This layer attends the blocks it picks (``sparse_attention``).
        self.sparse = kind.sparse
        #: The muP factor on each residual branch (1: none).
        self.branch_scale = c.residual_scale
        if c.is_state_layer(layer_idx):
            # A lightning mixer's decay depends on which layer it is.
            where = {} if c.lightning is None else {"layer": layer_idx}
            self.mixer = c.state_mixer.make_mixer(
                c.dim, norm_eps=1e-6 if c.norm_eps is None else c.norm_eps,
                **where,
            )
        elif self.latent:
            from rocket_tpu.nn.attention import LatentAttention

            self.attn = LatentAttention(
                c.dim, c.num_heads, c.latent_attention,
                rope_base=c.rope_base,
                norm_eps=1e-6 if c.norm_eps is None else c.norm_eps,
            )
        else:
            pick = lambda own, default: default if own is None else own
            self.attn = MultiHeadAttention(
                c.dim, pick(kind.num_heads, c.num_heads),
                num_kv_heads=c.num_kv_heads,
                causal=c.causal, dropout=c.dropout, use_bias=c.attn_bias,
                impl=c.attention_impl,
                seq_axis=c.seq_axis,
                rope=pick(kind.rope, c.pos_embedding == "rope"),
                rope_base=pick(kind.rope_base, c.rope_base),
                head_dim=c.head_dim, gate=c.attn_gate,
                qk_norm=c.qk_norm,
                rope_fraction=pick(kind.rope_fraction, c.rope_fraction),
                norm_eps=1e-6 if c.norm_eps is None else c.norm_eps,
                norm_zero_centered=c.norm_zero_centered,
                head_gate=c.attn_head_gate, rope_yarn=kind.rope_yarn,
                window=kind.window,
            )
        self.ln2 = c.make_norm(c.dim)
        self.routed = None
        if c.routed_experts is not None and layer_idx >= c.first_dense_layers:
            from rocket_tpu.nn.moe import RoutedExperts

            self.routed = RoutedExperts(c.dim, c.routed_experts)
            self.moe = self.fc_in = self.fc_out = self.fc_gate = None
        elif c.num_experts > 0:
            from rocket_tpu.nn.moe import MoE

            self.moe = MoE(
                c.dim, c.mlp_ratio * c.dim, c.num_experts,
                top_k=c.expert_top_k,
                capacity_factor=c.expert_capacity_factor,
                dispatch=c.expert_dispatch,
            )
            self.fc_in = self.fc_out = self.fc_gate = None
        else:
            self.moe = None
            hidden = c.mlp_hidden or c.mlp_ratio * c.dim
            dense = functools.partial(Dense, use_bias=c.mlp_bias)
            if c.mlp == "swiglu":
                # TWO separate projections, not one fused (gate|up) matmul.
                # Same matmul FLOPs, but the fused variant materializes the
                # 2x-wide intermediate and then splits it — a midpoint split
                # breaks column parallelism under TP, and a lane-interleaved
                # split costs a strided relayout that measured ~2x slower
                # for the whole MLP fwd+bwd on chip (6-8 ms vs 3.8 ms/layer
                # at GPT-2 shapes). Separate kernels also shard
                # column-parallel independently.
                self.fc_gate = dense(c.dim, hidden)
                self.fc_in = dense(c.dim, hidden)  # the "up" projection
            else:
                self.fc_gate = None
                self.fc_in = dense(c.dim, hidden)
            self.fc_out = dense(hidden, c.dim)
        self.mlp_type = c.mlp
        self.sparse_cfg = c.sparse_attention
        self.dropout = Dropout(c.dropout) if c.dropout else None
        # GPT-2: residual projections scaled by 1/sqrt(2*num_layers).
        self._resid_scale = (2 * c.num_layers) ** -0.5
        self.layer_idx = layer_idx
        # Whole-block fusion eligibility (tune kernel "block_attn" —
        # ISSUE 14): the fused ln1+QKV+attention(+proj) program covers
        # exactly the LayerNorm / learned-positions / MHA / causal /
        # biased configuration (the char-LM shape). Anything else stays
        # on the reference chain statically.
        self._block_attn_ok = (
            not self.latent
            and self.mixer is None
            and not self.attn.extended
            and c.norm == "layernorm"
            and c.pos_embedding == "learned"
            and c.causal
            and (c.num_kv_heads is None or c.num_kv_heads == c.num_heads)
            and c.attention_impl != "ring"
            and self.ln1.use_bias
            and self.attn.qkv.use_bias
            and self.attn.proj.use_bias
        )

    def init_params(self, key):
        keys = jax.random.split(key, 4)
        params = {
            "ln1": self.ln1.init(keys[0])["params"],
            "ln2": self.ln2.init(keys[2])["params"],
        }
        # Residual-output scaling (the mixer's output projection and the
        # FFN output kernel).
        if self.mixer is not None:
            params["mixer"] = self.mixer.init(keys[1])["params"]
            out = params["mixer"]["out_proj"]
        else:
            params["attn"] = self.attn.init(keys[1])["params"]
            out = params["attn"]["proj"]
        out["w"] = out["w"] * self._resid_scale
        if self.routed is not None:
            params["moe"] = self.routed.init_params(keys[3])
        elif self.moe is not None:
            params["moe"] = self.moe.init_params(keys[3])
            params["moe"]["experts"]["w_out"] = (
                params["moe"]["experts"]["w_out"] * self._resid_scale
            )
        else:
            if self.fc_gate is not None:
                k_in, k_out, k_gate = jax.random.split(keys[3], 3)
            else:
                # Two-way split preserved for gelu models: a 3-way split
                # would silently change seed-pinned init streams.
                k_in, k_out = jax.random.split(keys[3])
            params["mlp"] = {
                "fc_in": self.fc_in.init(k_in)["params"],
                "fc_out": self.fc_out.init(k_out)["params"],
            }
            if self.fc_gate is not None:
                params["mlp"]["fc_gate"] = self.fc_gate.init(k_gate)["params"]
            params["mlp"]["fc_out"]["w"] = params["mlp"]["fc_out"]["w"] * self._resid_scale
        return params

    def apply(self, variables, x, *, mode="train", rng=None, layer_idx=None):
        p = variables["params"]
        # layer_idx may be a traced scalar (scan-over-layers path) — fold_in
        # accepts traced ints, so the same Block code serves both layouts.
        idx = self.layer_idx if layer_idx is None else layer_idx
        rngs = (
            jax.random.split(jax.random.fold_in(rng, idx), 3)
            if rng is not None
            else (None, None, None)
        )

        h = self._attn_half(p, x, mode, rngs[0])
        # Tag for scan_remat_policy="block_io" (save these two, recompute
        # the rest in backward); inert without that policy.
        h = checkpoint_name(h, "attn_out")
        if self.dropout is not None:
            h, _ = self.dropout.apply({"params": {}, "state": {}}, h, mode=mode, rng=rngs[1])
        x = x + self._scaled(h)

        h, _ = self.ln2.apply({"params": p["ln2"], "state": {}}, x)
        aux = None
        if self.routed is not None:
            h, _ = self.routed.apply(
                {"params": p["moe"], "state": {}}, h, mode=mode
            )
        elif self.moe is not None:
            h, moe_out = self.moe.apply({"params": p["moe"], "state": {}}, h)
            aux = moe_out
        else:
            h = self._mlp(p["mlp"], h)
        h = checkpoint_name(h, "mlp_out")
        if self.dropout is not None:
            h, _ = self.dropout.apply({"params": {}, "state": {}}, h, mode=mode, rng=rngs[2])
        if aux is not None:
            # Namespaced INTO the state dict (not replacing it): the Layer
            # contract keeps real state flowing; TransformerLM pops this
            # transient before anything could persist it.
            out_state = dict(variables["state"])
            out_state["aux_loss"] = aux["aux_loss"]
            out_state["frac_dropped"] = aux["frac_dropped"]
            return x + self._scaled(h), out_state
        return x + self._scaled(h), variables["state"]

    def _scaled(self, h):
        """A residual branch times the muP factor (none at 1)."""
        return h if self.branch_scale == 1.0 else h * self.branch_scale

    def _block_attn_config(self, x):
        """The ``block_attn`` structural config when the fused
        whole-block program can serve this call, else None.

        The fused variant engages only when the table (or the
        ``ROCKET_TPU_BLOCK_ATTN`` force-override, which also runs it
        interpreted on CPU) pins ``impl="fused"`` — the default is the
        reference chain, bitwise the pre-seam path. The TP-overlap
        context and multi-device meshes are excluded: the fusion is the
        single-chip launch-bound small-model candidate; scale-out keeps
        the flash shard_map seam."""
        import os

        if not self._block_attn_ok or x.ndim != 3:
            return None
        from rocket_tpu.parallel import collectives as coll

        if coll.current_tp() is not None:
            return None
        from rocket_tpu.ops.fused_block import block_attn_supported
        from rocket_tpu.tune import get_config

        b, t, d = x.shape
        config = get_config(
            "block_attn",
            shape={"b": b, "t": t, "d": d, "h": self.attn.num_heads},
            dtype=x.dtype,
        ) or {}
        forced = os.environ.get("ROCKET_TPU_BLOCK_ATTN")
        impl = forced or config.get("impl", "reference")
        if impl != "fused":
            return None
        on_cpu = jax.devices()[0].platform == "cpu"
        if not forced and (on_cpu or jax.device_count() > 1):
            return None
        block_b = config.get("block_b", 1)
        if not block_attn_supported(b, t, d, self.attn.num_heads, block_b):
            return None
        return {
            "epilogue": config.get("epilogue", "fused"),
            "block_b": block_b,
            "interpret": True if on_cpu else None,
        }

    def _attn_half(self, p, x, mode, rng):
        """ln1 + attention, through either the reference per-op chain
        (the bitwise default) or the fused whole-block pallas program
        (``ops/fused_block.py``) when the ``block_attn`` table pins it.
        Train-mode attention dropout forces ``epilogue="separate"`` —
        the reference applies dropout BETWEEN the attention core and the
        output projection, so the fused program stops there and the
        identical dropout+projection tail runs outside."""
        cfg = self._block_attn_config(x)
        if cfg is not None:
            from rocket_tpu.ops.fused_block import block_attn_half

            attn = self.attn
            pa = p["attn"]
            epilogue = cfg["epilogue"]
            if attn.dropout and mode == "train":
                epilogue = "separate"
            out = block_attn_half(
                x, p["ln1"]["scale"], p["ln1"]["bias"],
                pa["qkv"]["w"], pa["qkv"]["b"],
                pa["proj"]["w"], pa["proj"]["b"],
                num_heads=attn.num_heads, eps=self.ln1.eps,
                causal=attn.causal, epilogue=epilogue,
                block_b=cfg["block_b"], interpret=cfg["interpret"],
            )
            if epilogue == "separate":
                b, t, _ = x.shape
                out = out.reshape(b, t, attn.num_heads, attn.head_dim)
                out = attn._attn_dropout(out, mode, rng)
                out = out.reshape(b, t, attn.features)
                out, _ = attn.proj.apply(
                    {"params": pa["proj"], "state": {}}, out
                )
            return out
        h, _ = self.ln1.apply({"params": p["ln1"], "state": {}}, x)
        if self.sparse:
            raise NotImplementedError(
                "Block: block-sparse attention runs against the paged pool "
                "(TransformerLM.paged_step) only")
        if self.mixer is not None:
            return self.mixer.apply(
                {"params": p["mixer"], "state": {}}, h, mode=mode
            )[0]
        h, _ = self.attn.apply(
            {"params": p["attn"], "state": {}}, h, mode=mode, rng=rng
        )
        return h

    def apply_cached(self, params, x, cache: dict, pos):
        """Decode step: (B, 1, D) through the block with KV-cached attention
        (eval semantics — no dropout). Returns (y, new_cache)."""
        h, _ = self.ln1.apply({"params": params["ln1"], "state": {}}, x)
        h, cache = self.attn.apply_cached(params["attn"], h, cache, pos)
        x = x + h
        h, _ = self.ln2.apply({"params": params["ln2"], "state": {}}, x)
        return x + self._ffn_eval(params, h)[0], cache

    def _ffn_eval(self, params, h, token_mask=None):
        """The block's FFN of whichever kind, eval semantics: ``(out,
        counts)`` — ``counts`` the pairs each held expert received (a
        routed block; ``token_mask`` marks the rows that are tokens) or
        None."""
        if self.routed is not None:
            return self.routed.apply(
                {"params": params["moe"], "state": {}}, h,
                token_mask=token_mask,
            )
        if self.moe is not None:
            return self.moe.apply({"params": params["moe"], "state": {}}, h)[0], None
        return self._mlp(params["mlp"], h), None

    def apply_paged(self, params, x, pages, block_table, positions, valid,
                    layer=0):
        """Decode/prefill chunk through the block against an EXTERNAL
        paged pool (``rocket_tpu.serve``): ``x`` (S, C, D) at per-slot
        global positions (eval semantics — no dropout); ``pages`` the
        pool's arrays as ``TransformerConfig.kv_pool_lanes`` declares them
        (``(k_pages, v_pages)``, or one latent array); ``layer`` is this
        block's coordinate in the whole pool. Returns ``(y, pages',
        counts)`` — ``counts`` the pairs each held expert received (a
        routed block) or None."""
        h, _ = self.ln1.apply({"params": params["ln1"], "state": {}}, x)
        h, *pages = self.attn.apply_paged(
            params["attn"], h, *pages, block_table, positions, valid,
            layer=layer,
        )
        y, counts = self._ffn_half(params, x + h, valid)
        return y, tuple(pages), counts

    def apply_window(self, params, x, rings, positions, valid, slots=None,
                     layer=0):
        """:meth:`apply_paged` for a layer with a window: ``rings`` the
        WHOLE ``(k_ring, v_ring)`` arrays (``MultiHeadAttention.
        apply_window``), read and written at ``(layer, slots)`` — ``layer``
        this block's index among the window layers. Returns ``(y, rings',
        counts)``."""
        h, _ = self.ln1.apply({"params": params["ln1"], "state": {}}, x)
        h, *rings = self.attn.apply_window(
            params["attn"], h, *rings, positions, valid, slots=slots,
            layer=layer,
        )
        y, counts = self._ffn_half(params, x + h, valid)
        return y, tuple(rings), counts

    def apply_state(self, params, x, state, positions, valid, slots=None,
                    layer=0):
        """:meth:`apply_paged` for a state layer: ``state`` the WHOLE
        per-slot state arrays (``nn.ssm.MambaMixer.apply_state``), read and
        written at ``(layer, slots)`` — ``layer`` this block's index among
        the state layers, ``slots`` (S,) the slot of each row of ``x``
        (None: row ``s`` is slot ``s``, the decode wave). Returns ``(y,
        state', counts)``."""
        h, _ = self.ln1.apply({"params": params["ln1"], "state": {}}, x)
        h, state = self.mixer.apply_state(
            params["mixer"], h, state, positions, valid, layer=layer,
            slots=slots,
        )
        y, counts = self._ffn_half(params, x + self._scaled(h), valid)
        return y, state, counts

    def apply_sparse(self, params, x, pages, state, block_table, positions,
                     valid, slots=None, layer=0):
        """:meth:`apply_paged` for a block-sparse layer: its K/V rows go to
        the pages at its ``layer`` coordinate, and its compressed keys to
        the LAST of the per-slot ``state`` arrays at ``(layer, slots)``
        (``ops.paged_attention.sparse_attention``). No rotary unless its
        kind asks. Returns ``(y, pages', state', counts)``."""
        from rocket_tpu.ops.paged_attention import sparse_attention

        h, _ = self.ln1.apply({"params": params["ln1"], "state": {}}, x)
        q, k, v, gate = self.attn._project(params["attn"], h, positions)
        out, k_pages, v_pages, kc = sparse_attention(
            q, k, v, *pages, state[-1], block_table, positions, valid,
            slots=slots, layer=layer, cfg=self.sparse_cfg,
        )
        h = self.attn._gated_out(params["attn"], out, gate)
        y, counts = self._ffn_half(params, x + self._scaled(h), valid)
        return y, (k_pages, v_pages), tuple(state[:-1]) + (kc,), counts

    def _ffn_half(self, params, x, valid):
        """ln2 + the FFN of a paged or stateful chunk ``x`` (S, C, D)."""
        h, _ = self.ln2.apply({"params": params["ln2"], "state": {}}, x)
        # Padding rows and idle slots are no tokens: they route nowhere.
        real = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :] < valid[:, None]
        h, counts = self._ffn_eval(params, h, token_mask=real)
        return x + self._scaled(h), counts

    def _mlp_tp_spec(self, h):
        """Overlap spec when the MLP can take the collective-matmul
        path: (B, T, D) input with T and the hidden width dividing the
        TP axis."""
        if h.ndim != 3:
            return None
        from rocket_tpu.parallel import collectives as coll

        spec = coll.current_tp()
        if spec is None:
            return None
        n = spec.tp_size
        if h.shape[1] % n or self.fc_in.out_features % n:
            return None
        return spec

    def _mlp(self, p, h):
        spec = self._mlp_tp_spec(h)
        if spec is not None:
            # Overlapped TP path: ONE gather feeds both column-parallel
            # projections (swiglu's gate+up share it), the activation
            # runs on the local hidden shard, and fc_out reduce-scatters
            # onto the sequence shards (parallel/collectives.py).
            from rocket_tpu.parallel import collectives as coll

            dt = h.dtype
            ws = [p["fc_in"]["w"].astype(dt)]
            if self.mlp_type == "swiglu":
                ws.append(p["fc_gate"]["w"].astype(dt))
            outs = coll.all_gather_matmul(spec, h, tuple(ws))
            up = outs[0] + p["fc_in"]["b"].astype(dt)
            if self.mlp_type == "swiglu":
                gate = outs[1] + p["fc_gate"]["b"].astype(dt)
                hid = jax.nn.silu(gate) * up
            else:
                hid = jax.nn.gelu(up)
            return coll.matmul_reduce_scatter(
                spec, hid, p["fc_out"]["w"].astype(dt),
                bias=p["fc_out"]["b"].astype(dt),
            )
        up, _ = self.fc_in.apply({"params": p["fc_in"], "state": {}}, h)
        if self.mlp_type == "swiglu":
            gate, _ = self.fc_gate.apply({"params": p["fc_gate"], "state": {}}, h)
            h = jax.nn.silu(gate) * up
        else:
            h = jax.nn.gelu(up)
        h, _ = self.fc_out.apply({"params": p["fc_out"], "state": {}}, h)
        return h


class TransformerLM(Model):
    """Batch contract: reads ``batch["tokens"]`` (B, T) int32, writes
    ``batch["logits"]`` (B, T, V) — EXCEPT in train mode with
    ``config.loss_chunk > 0`` (the gpt2_124m default), where the fused
    head+CE path writes the ready scalar ``batch["nll"]`` instead and no
    logits exist (that is the point: the (B, T, V) materialization is the
    step's largest allocation). Attach logits consumers (e.g. metrics) to
    eval loopers, which always get logits."""

    def __init__(
        self,
        config: TransformerConfig,
        tokens_key: str = "tokens",
        logits_key: str = "logits",
    ):
        self.config = config
        self.wte = Embedding(config.vocab_size, config.dim)
        config.validate()
        # RoPE encodes positions inside attention — no learned wpe table.
        self.wpe = (
            Embedding(config.max_seq_len, config.dim)
            if config.pos_embedding == "learned"
            else None
        )
        self.blocks = [Block(config, i) for i in range(config.num_layers)]
        self.ln_f = config.make_norm(config.dim)
        self.head = (
            None
            if config.tied_embeddings
            else Dense(config.dim, config.vocab_size, use_bias=False)
        )
        self.drop = Dropout(config.dropout) if config.dropout else None
        self.tokens_key = tokens_key
        self.logits_key = logits_key
        self._pipe_mesh = None  # pinned at first pipelined trace
        self._pipe_block_apply: dict = {}  # mode -> stable pipeline body
        #: objective -> built 1F1B value_and_grad. The tail_fn closure keys
        #: the compiled-pipeline cache (_CACHE_1F1B), so rebuilding it per
        #: call would recompile the whole pipelined program each time a
        #: train step is (re)built.
        self._pipe_vag: dict = {}

    def init(self, key: jax.Array) -> Variables:
        keys = jax.random.split(key, len(self.blocks) + 3)
        per_block = [
            block.init_params(keys[2 + i]) for i, block in enumerate(self.blocks)
        ]
        params = {
            "wte": self.wte.init(keys[0])["params"],
            "ln_f": self.ln_f.init(keys[-1])["params"],
        }
        if self.wpe is not None:
            params["wpe"] = self.wpe.init(keys[1])["params"]
        if self.config.scan_layers:
            # One stacked subtree with a leading L dim — the scan's xs.
            params["blocks_stacked"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *per_block
            )
        else:
            params["blocks"] = {str(i): p for i, p in enumerate(per_block)}
        if self.head is not None:
            params["head"] = self.head.init(jax.random.fold_in(key, 99))["params"]
        return {"params": params, "state": {}}

    def num_params(self, variables: Variables) -> int:
        return sum(int(l.size) for l in jax.tree.leaves(variables["params"]))

    # -- incremental decoding ---------------------------------------------

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """Per-layer KV caches for :meth:`decode_step` (list of L dicts, or
        one stacked (L, ...) dict under scan_layers)."""
        per_layer = self.blocks[0].attn.init_cache(batch, max_len, dtype)
        L = self.config.num_layers
        if self.config.scan_layers:
            return jax.tree.map(
                lambda l: jnp.zeros((L,) + l.shape, l.dtype), per_layer
            )
        # Arrays are immutable — the same zero cache can seed every layer.
        return [per_layer] * L

    def decode_step(self, params, tokens, caches, pos):
        """``tokens`` (B, S) int32 written at positions [pos, pos+S) —
        S = the whole prompt for the batched prefill, S = 1 per decode step
        after -> (logits (B, V) of the LAST position, updated caches).
        Attention reads only the KV caches — O(T_max) per step."""
        p = params
        s = tokens.shape[1]
        x = jnp.take(p["wte"]["table"], tokens, axis=0)
        if self.wpe is not None:
            x = x + jax.lax.dynamic_slice_in_dim(p["wpe"]["table"], pos, s, axis=0)
        if self.config.activation_dtype is not None:
            x = x.astype(self.config.activation_dtype)

        if self.config.scan_layers:
            block = self.blocks[0]

            def body(h, xs):
                params_i, cache_i = xs
                h, cache_i = block.apply_cached(params_i, h, cache_i, pos)
                return h, cache_i

            x, caches = jax.lax.scan(body, x, (p["blocks_stacked"], caches))
        else:
            new_caches = []
            for i, block in enumerate(self.blocks):
                x, cache_i = block.apply_cached(
                    p["blocks"][str(i)], x, caches[i], pos
                )
                new_caches.append(cache_i)
            caches = new_caches

        x = x[:, -1:]  # only the last position's logits are consumed
        x, _ = self.ln_f.apply({"params": p["ln_f"], "state": {}}, x)
        if self.head is not None:
            logits, _ = self.head.apply({"params": p["head"], "state": {}}, x)
        else:
            logits = jnp.einsum("btd,vd->btv", x, p["wte"]["table"].astype(x.dtype))
        return logits[:, 0], caches

    def decode_step_paged(self, params, tokens, k_pages, v_pages,
                          block_table, positions, valid):
        """:meth:`paged_step` for a model whose pool is a K and a V array:
        ``(logits, k_pages', v_pages')``."""
        logits, (k_pages, v_pages), _ = self.paged_step(
            params, tokens, (k_pages, v_pages), block_table, positions, valid
        )
        return logits, k_pages, v_pages

    def paged_step(self, params, tokens, pages, block_table, positions,
                   valid, slots=None):
        """Decode/prefill chunk against an EXTERNAL paged pool — the
        cache is indexed by slot, not owned by the call
        (``rocket_tpu.serve``; pool layout in ``ops/paged_attention.py``).

        ``tokens`` (S, C) int32 — slot ``s``'s chunk occupies global
        positions ``[positions[s], positions[s]+C)`` with the first
        ``valid[s]`` rows real; ``pages`` is the whole pool, a tuple of
        ``(L, NB, BL, lanes)`` arrays as ``config.kv_pool_lanes`` declares
        (K and V, or one latent array; ``L`` = ``config.cache_layers``)
        and, behind them, the per-slot state arrays that
        ``config.slot_state_shapes`` declares (``(state layers, max_slots,
        ...)``; none for a model whose every layer caches pages);
        ``block_table`` (S, MB) maps slot positions onto pool blocks;
        ``slots`` (S,) int32 names the slot of each row, which a state
        layer needs where row ``s`` is not slot ``s`` (the prefill chunk;
        None = the decode wave over every slot). A slot's state starts
        from zeros wherever its chunk starts at position 0. Returns
        ``(logits (S, V) of the
        chunk's LAST position, pages', expert_pairs)`` — C=1 is the decode
        wave, C=chunk the prefill step, one code path for both. Every
        layer reads and writes the whole pool at its own layer coordinate:
        no layer is sliced out or put back, so a donated pool is updated
        in place. ``expert_pairs`` is None for a model with no routed
        layer, else int32 ``(routed layers, experts held)``: the (token,
        choice) pairs each held expert received in this call.
        """
        p = params
        s, c = tokens.shape
        pages = tuple(pages)
        x = jnp.take(p["wte"]["table"], tokens, axis=0)
        if self.config.embed_scale != 1.0:
            x = x * self.config.embed_scale
        if self.wpe is not None:
            pos_ids = jnp.clip(
                positions[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :],
                0, self.config.max_seq_len - 1,
            )
            x = x + jnp.take(p["wpe"]["table"], pos_ids, axis=0)
        if self.config.activation_dtype is not None:
            x = x.astype(self.config.activation_dtype)

        if self.config.scan_layers:
            block = self.blocks[0]

            def body(carry, xs):
                params_i, i = xs
                x, pages, counts = block.apply_paged(
                    params_i, *carry, block_table, positions, valid, layer=i
                )
                return (x, pages), counts

            layers = jnp.arange(self.config.num_layers, dtype=jnp.int32)
            (x, pages), pairs = jax.lax.scan(
                body, (x, pages), (p["blocks_stacked"], layers)
            )
        else:
            pairs = []
            n_pool = len(self.config.kv_pool_lanes)
            pages, state = pages[:n_pool], pages[n_pool:]
            cached = stateful = 0   # each kind's own layer coordinate
            for i, block in enumerate(self.blocks):
                if block.mixer is not None:
                    x, state, counts = block.apply_state(
                        p["blocks"][str(i)], x, state, positions, valid,
                        slots, layer=stateful,
                    )
                    stateful += 1
                elif block.window:
                    # The rings are the per-slot arrays of a window model.
                    x, state, counts = block.apply_window(
                        p["blocks"][str(i)], x, state, positions, valid,
                        slots, layer=stateful,
                    )
                    stateful += 1
                elif block.sparse:
                    x, pages, state, counts = block.apply_sparse(
                        p["blocks"][str(i)], x, pages, state, block_table,
                        positions, valid, slots, layer=cached,
                    )
                    cached += 1
                else:
                    x, pages, counts = block.apply_paged(
                        p["blocks"][str(i)], x, pages, block_table,
                        positions, valid, layer=cached,
                    )
                    cached += 1
                if counts is not None:
                    pairs.append(counts)
            pairs = jnp.stack(pairs) if pairs else None
            pages = pages + tuple(state)

        x = x[:, -1:]  # only the last position's logits are consumed
        x, _ = self.ln_f.apply({"params": p["ln_f"], "state": {}}, x)
        if self.config.logit_divisor != 1.0:
            x = x / self.config.logit_divisor
        if self.head is not None:
            logits, _ = self.head.apply({"params": p["head"], "state": {}}, x)
        else:
            logits = jnp.einsum(
                "btd,vd->btv", x, p["wte"]["table"].astype(x.dtype)
            )
        return logits[:, 0], pages, pairs

    def _resolve_pipe_mesh(self):
        """Pin the pipeline mesh at first trace (same rule as ring/flash
        seams) and validate the axis exists."""
        c = self.config
        if not c.scan_layers:
            raise RuntimeError(
                "TransformerConfig.pipeline_axis requires scan_layers=True "
                "(stacked block params are the pipeline stages)."
            )
        if self._pipe_mesh is None:
            from rocket_tpu.runtime.context import Runtime

            runtime = Runtime.current()
            if runtime is None or c.pipeline_axis not in runtime.mesh.shape:
                raise RuntimeError(
                    f"pipeline_axis={c.pipeline_axis!r} needs a live Runtime "
                    "whose mesh has that axis (e.g. Runtime(mesh_shape="
                    "{'data': 2, 'pipe': 4}))."
                )
            self._pipe_mesh = runtime.mesh
        return self._pipe_mesh

    def _get_pipe_block_apply(self, mode):
        """One STABLE block_apply per mode — it keys the compiled-pipeline
        cache, so a fresh closure per call would recompile every step."""
        c = self.config
        moe = c.num_experts > 0
        block_apply = self._pipe_block_apply.get(mode)
        if block_apply is None:
            block = self.blocks[0]

            def block_apply(params_i, idx, mb, h, r):
                if r is not None:
                    # Distinct dropout masks per microbatch — one shared
                    # key would correlate every microbatch's mask. The
                    # per-data-shard fold happens in the pipeline itself
                    # (BEFORE any lax.cond — the differentiable fill/drain
                    # skip needs the key data-varying at cond entry, see
                    # parallel/pipeline.py module docstring).
                    r = jax.random.fold_in(r, mb)
                y, bstate = block.apply(
                    {"params": params_i, "state": {}}, h,
                    mode=mode, rng=r, layer_idx=idx,
                )
                if moe:
                    # Aux rides the pipeline's with_aux channel. NB: each
                    # microbatch is its own GShard routing group, so the
                    # pipelined aux is the microbatch-mean — the unpipelined
                    # full-batch product differs slightly (they coincide at
                    # num_microbatches=1).
                    return y, bstate["aux_loss"]
                return y

            self._pipe_block_apply[mode] = block_apply
        return block_apply

    def _apply_pipelined(self, p, x, *, mode, rng):
        """Trunk via GPipe stages over config.pipeline_axis
        (``parallel/pipeline.py``). Requires the scan_layers stacked layout;
        the mesh is pinned at first trace (same rule as ring attention).
        Training under pipeline_schedule="1f1b" bypasses this (the whole
        fwd+bwd runs in :meth:`pipelined_value_and_grad`); eval and
        generation still come through here."""
        c = self.config
        self._resolve_pipe_mesh()
        from rocket_tpu.parallel.pipeline import pipeline_blocks

        moe = c.num_experts > 0
        block_apply = self._get_pipe_block_apply(mode)

        return pipeline_blocks(
            block_apply,
            p["blocks_stacked"],
            x,
            mesh=self._pipe_mesh,
            pipe_axis=c.pipeline_axis,
            data_axis="data",
            num_microbatches=c.pipeline_microbatches,
            remat=c.scan_remat,
            remat_policy=c.remat_policy(),
            rng=rng,
            with_aux=moe,
        )

    def pipelined_value_and_grad(self, objective):
        """1F1B training-step builder (``Module`` calls this when present;
        None means "use the standard jax.value_and_grad path").

        Returns ``fn(params, model_state, batch, rng) ->
        ((loss, (out, model_state)), grads)`` matching the value_and_grad
        contract, with loss AND backward computed inside ONE pipelined
        shard_map program (``parallel.pipeline.pipeline_train_1f1b``) —
        per-stage live activations O(P) instead of GPipe's O(M). The
        embedding runs outside the pipeline (its cotangent comes back from
        stage 0); the ln_f + head + CE tail runs per-microbatch on the
        last stage. The objective must consume ``batch["nll"]``
        (``next_token_loss`` does) — it is applied per microbatch to a
        batch dict that carries no logits.
        """
        c = self.config
        if not c.pipeline_axis or c.pipeline_schedule != "1f1b":
            return None
        cached = self._pipe_vag.get(objective)
        if cached is not None:
            return cached
        from rocket_tpu.parallel.pipeline import pipeline_train_1f1b

        tied = self.head is None

        def tail_fn(tp, h, tokens_mb):
            h2, _ = self.ln_f.apply({"params": tp["ln_f"], "state": {}}, h)
            if tied:
                table = tp["wte"]["table"]

                def proj(xc):
                    return jnp.einsum("bcd,vd->bcv", xc, table.astype(xc.dtype))
            else:
                hp = tp["head"]

                def proj(xc):
                    return self.head.apply({"params": hp, "state": {}}, xc)[0]

            t = tokens_mb.shape[1]
            out_mb = {self.tokens_key: tokens_mb}
            if c.loss_chunk > 0 and t > 1 and t % c.loss_chunk == 0:
                out_mb["nll"] = _chunked_next_token_nll(
                    h2, tokens_mb, c.loss_chunk, proj,
                    label_smoothing=c.label_smoothing,
                )
            else:
                out_mb[self.logits_key] = proj(h2)
                if c.label_smoothing:
                    out_mb["label_smoothing"] = c.label_smoothing
            return jnp.asarray(objective(out_mb), jnp.float32)

        def vag(params, model_state, batch, rng):
            mesh = self._resolve_pipe_mesh()
            tokens = batch[self.tokens_key]
            t = tokens.shape[1]
            emb_keys = ["wte"] + (["wpe"] if self.wpe is not None else [])

            def embed(emb_p):
                x = jnp.take(emb_p["wte"]["table"], tokens, axis=0)
                if self.wpe is not None:
                    x = x + emb_p["wpe"]["table"][:t]
                if c.activation_dtype is not None:
                    x = x.astype(c.activation_dtype)
                if self.drop is not None:
                    x, _ = self.drop.apply(
                        {"params": {}, "state": {}}, x, mode="train",
                        rng=None if rng is None
                        else jax.random.fold_in(rng, 0x0E0BED),
                    )
                return x

            x, embed_vjp = jax.vjp(embed, {k: params[k] for k in emb_keys})

            tail_p = {"ln_f": params["ln_f"]}
            tail_p["wte" if tied else "head"] = params["wte" if tied else "head"]

            loss, g_stacked, g_tail, dx = pipeline_train_1f1b(
                self._get_pipe_block_apply("train"),
                params["blocks_stacked"],
                x,
                tail_p,
                tail_fn,
                tokens,
                mesh=mesh,
                pipe_axis=c.pipeline_axis,
                data_axis="data",
                num_microbatches=c.pipeline_microbatches,
                rng=rng,
            )
            (d_emb,) = embed_vjp(dx.astype(x.dtype))

            grads = {
                "blocks_stacked": g_stacked,
                "ln_f": g_tail["ln_f"],
            }
            if tied:
                # The table gets gradient from BOTH ends: the embedding
                # gather and the output projection.
                grads["wte"] = jax.tree.map(
                    jnp.add, d_emb["wte"], g_tail["wte"]
                )
            else:
                grads["wte"] = d_emb["wte"]
                grads["head"] = g_tail["head"]
            if self.wpe is not None:
                grads["wpe"] = d_emb["wpe"]

            out = dict(batch)
            out["nll"] = loss  # for the Loss capsule's running value
            return (loss, (out, model_state)), grads

        self._pipe_vag[objective] = vag
        return vag

    def _tp_spec(self, t: int):
        """Active TP-overlap spec for this forward (None = plain GSPMD
        program). Pipelined models are excluded — the stage shard_map
        owns the mesh there."""
        if self.config.pipeline_axis:
            return None
        from rocket_tpu.parallel import collectives as coll

        spec = coll.current_tp()
        if spec is None or t % spec.tp_size:
            return None
        return spec

    def apply(self, variables, batch, *, mode="train", rng=None):
        p = variables["params"]
        tokens = batch[self.tokens_key]
        b, t = tokens.shape
        if t > self.config.max_seq_len:
            raise ValueError(
                f"sequence length {t} > max_seq_len {self.config.max_seq_len}"
            )

        tp_spec = self._tp_spec(t)
        if tp_spec is not None:
            # Overlapped TP path: the residual stream runs SEQUENCE-
            # SHARDED over the TP axis from the embedding to the head —
            # norms/residual adds touch 1/n of the tokens and every
            # block-boundary collective is an explicit gather/scatter
            # (parallel/collectives.py) instead of a GSPMD all-reduce.
            from rocket_tpu.parallel import collectives as coll

            if (
                tp_spec.vocab_sharded_embed
                and self.config.vocab_size % tp_spec.tp_size == 0
                and self.wpe is None
            ):
                # Vocab-parallel lookup reduce-scattered straight onto
                # the sequence shards. Each row has exactly ONE nonzero
                # contribution, so crossing at the activation dtype is
                # bitwise-equal to cast-after-psum — but it narrows a
                # PARAM (the fp32 master table) on the wire, which
                # prec_audit RKT403 flags unless the step certifies it.
                x = coll.embed_lookup_sharded(
                    tp_spec, p["wte"]["table"], tokens,
                    compute_dtype=self.config.activation_dtype,
                )
            else:
                x = jnp.take(p["wte"]["table"], tokens, axis=0)
                if self.wpe is not None:
                    x = x + p["wpe"]["table"][:t]
                x = coll.seq_shard(tp_spec, x)
        else:
            x = jnp.take(p["wte"]["table"], tokens, axis=0)
            if self.wpe is not None:
                x = x + p["wpe"]["table"][:t]
        if self.config.embed_scale != 1.0:
            x = x * self.config.embed_scale
        if self.config.activation_dtype is not None:
            x = x.astype(self.config.activation_dtype)
        if self.drop is not None:
            x, _ = self.drop.apply(
                {"params": {}, "state": {}}, x, mode=mode,
                # Salt from a domain disjoint with the per-block
                # fold_in(rng, layer_idx) keys — a small constant would
                # collide with that block's key and correlate dropout masks.
                rng=None if rng is None else jax.random.fold_in(rng, 0x0E0BED),
            )

        moe = self.config.num_experts > 0
        aux_total = jnp.zeros((), jnp.float32) if moe else None
        # Mean dropped-routing fraction across layers (capacity-utilization
        # metric); the pipelined aux channel carries only the loss scalar,
        # so it stays None there.
        dropped_total = jnp.zeros((), jnp.float32) if moe else None
        if self.config.pipeline_axis:
            dropped_total = None
            if moe:
                x, aux_total = self._apply_pipelined(p, x, mode=mode, rng=rng)
            else:
                x = self._apply_pipelined(p, x, mode=mode, rng=rng)
        elif self.config.scan_layers:
            block = self.blocks[0]  # one traced body serves every layer

            def body(carry, xs):
                params_i, i = xs
                h, aux, dropped = carry
                y, bstate = block.apply(
                    {"params": params_i, "state": {}}, h,
                    mode=mode, rng=rng, layer_idx=i,
                )
                if moe:
                    aux = aux + bstate["aux_loss"]
                    dropped = dropped + bstate["frac_dropped"]
                return (y, aux, dropped), None

            if self.config.scan_remat:
                body = jax.checkpoint(body, policy=self.config.remat_policy())
            (x, aux_total, dropped_total), _ = jax.lax.scan(
                body,
                (x, aux_total, dropped_total),
                (p["blocks_stacked"], jnp.arange(self.config.num_layers)),
                unroll=self.config.scan_unroll,
            )
        else:
            for i, block in enumerate(self.blocks):
                x, bstate = block.apply(
                    {"params": p["blocks"][str(i)], "state": {}}, x, mode=mode, rng=rng
                )
                if moe:
                    aux_total = aux_total + bstate["aux_loss"]
                    dropped_total = dropped_total + bstate["frac_dropped"]

        x, _ = self.ln_f.apply({"params": p["ln_f"], "state": {}}, x)
        if self.config.logit_divisor != 1.0:
            x = x / self.config.logit_divisor
        out = dict(batch)
        if self.config.label_smoothing and mode == "train":
            # Train-only: eval loss stays plain CE, comparable to
            # log(perplexity) and to unsmoothed baselines.
            out["label_smoothing"] = self.config.label_smoothing
        fused = (
            self.config.loss_chunk > 0
            and mode == "train"
            and t > 1
            and t % self.config.loss_chunk == 0
        )
        if tp_spec is not None:
            from rocket_tpu.parallel import collectives as coll

            if (
                not fused
                and self.config.vocab_size % tp_spec.tp_size == 0
            ):
                # Head projection as a collective matmul: gather the
                # sequence shards into the vocab-sharded logits (tied
                # and untied heads are the same column-parallel shape).
                w_head = (
                    p["head"]["w"]
                    if self.head is not None
                    else p["wte"]["table"].T
                )
                (logits,) = coll.all_gather_matmul(
                    tp_spec, x, (w_head.astype(x.dtype),)
                )
                out[self.logits_key] = logits
                if moe:
                    out["moe_aux_loss"] = aux_total * self.config.moe_aux_weight
                    if dropped_total is not None:
                        out["moe_frac_dropped"] = (
                            dropped_total / self.config.num_layers
                        )
                return out, variables["state"]
            # Fused-loss scan (or an indivisible vocab): reassemble the
            # full sequence first; the gradient crosses back compressed
            # (seq_all_gather's backward is a wire-dtype relayout).
            x = coll.seq_all_gather(tp_spec, x)
        if fused:
            if self.head is not None:
                hp = p["head"]

                def proj(xc):
                    return self.head.apply({"params": hp, "state": {}}, xc)[0]
            else:
                table = p["wte"]["table"]

                def proj(xc):
                    return jnp.einsum("bcd,vd->bcv", xc, table.astype(xc.dtype))

            out["nll"] = _chunked_next_token_nll(
                x, tokens, self.config.loss_chunk, proj,
                label_smoothing=self.config.label_smoothing,
            )
        elif self.head is not None:
            logits, _ = self.head.apply({"params": p["head"], "state": {}}, x)
            out[self.logits_key] = logits
        else:
            # Tied head: project back through the embedding table. Logits
            # stay in the compute dtype — at GPT-2 shapes an f32 (B, T, V)
            # materialization costs ~6ms/step in HBM traffic; the objective
            # upcasts to f32 for the softmax math (next_token_loss).
            logits = jnp.einsum("btd,vd->btv", x, p["wte"]["table"].astype(x.dtype))
            out[self.logits_key] = logits
        if moe:
            # Pre-weighted router load-balancing loss; next_token_loss adds
            # it when present.
            out["moe_aux_loss"] = aux_total * self.config.moe_aux_weight
            if dropped_total is not None:
                # Layer-mean fraction of routed (token, choice) pairs that
                # overflowed expert capacity — track it (Meter/Tracker) to
                # see whether the balance loss is holding.
                out["moe_frac_dropped"] = (
                    dropped_total / self.config.num_layers
                )
        return out, variables["state"]


def _chunked_next_token_nll(x, tokens, chunk, proj, label_smoothing=0.0):
    """Mean next-token NLL without materializing (B, T, V) logits.

    Scans ``proj`` (the head projection) + softmax-CE over T-chunks under
    ``jax.checkpoint``: the backward recomputes each chunk's logits, so the
    residual carried from forward to backward is x (B, T, D) instead of the
    logits. The softmax math runs in f32 per chunk; grads to the head
    weights accumulate across the scan. Matches ``next_token_loss`` exactly:
    mean CE of positions [0, T-1) vs tokens[:, 1:].
    """
    b, t, d = x.shape
    nc = t // chunk
    # Position i predicts tokens[i+1]; the last position has no target and
    # is masked out (the wrapped filler value never contributes).
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    mask = (jnp.arange(t) < t - 1).astype(jnp.float32)
    xs = jnp.swapaxes(x.reshape(b, nc, chunk, d), 0, 1)          # (nc,b,c,d)
    ys = jnp.swapaxes(targets.reshape(b, nc, chunk), 0, 1)       # (nc,b,c)
    ms = mask.reshape(nc, chunk)                                 # (nc,c)

    def chunk_nll(x_c, y_c, m_c):
        logits = proj(x_c).astype(jnp.float32)                   # (b,c,V)
        lse = jax.nn.logsumexp(logits, axis=-1)                  # (b,c)
        lab = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        if label_smoothing:
            # Smoothed CE: lse - (1-eps)*label_logit - eps*mean(logits).
            eps = label_smoothing
            lab = (1.0 - eps) * lab + eps * jnp.mean(logits, axis=-1)
        return jnp.sum((lse - lab) * m_c)

    def body(acc, args):
        return acc + jax.checkpoint(chunk_nll)(*args), None

    # Keep the scan ROLLED: unrolling looks like a win in summed-op-time
    # traces (the while wrapper disappears) but wall-clock A/B on chip
    # measures it ~2% slower — summed op durations don't count the
    # scheduling gaps the unrolled straight-line program introduces.
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ys, ms))
    return total / (b * (t - 1))


def next_token_loss(
    logits_key: str = "logits", tokens_key: str = "tokens"
):
    """Objective: mean cross-entropy of logits[:, :-1] vs tokens[:, 1:],
    plus the model's (pre-weighted) MoE load-balancing aux loss if the batch
    carries one. When the model ran with ``loss_chunk`` (fused head+CE) the
    batch carries the ready ``nll`` scalar instead of logits."""
    import optax

    def objective(batch):
        if "nll" in batch:
            loss = batch["nll"]  # fused path applied any label smoothing
        else:
            logits = batch[logits_key][:, :-1].astype(jnp.float32)
            targets = batch[tokens_key][:, 1:]
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, targets
            )
            eps = batch.get("label_smoothing")
            if eps is not None:
                # Smoothed target = (1-eps) one-hot + eps uniform:
                # CE_smooth = (1-eps)*CE + eps*(lse - mean(logits)).
                lse = jax.nn.logsumexp(logits, axis=-1)
                loss = (1.0 - eps) * loss + eps * (
                    lse - jnp.mean(logits, axis=-1)
                )
            loss = loss.mean()
        aux = batch["moe_aux_loss"] if "moe_aux_loss" in batch else None
        return loss if aux is None else loss + aux

    return objective


def generate(
    model: TransformerLM,
    variables: Variables,
    prompt_tokens,
    max_new_tokens,
    *,
    key=None,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_token_id: Optional[int] = None,
    use_cache: bool = True,
):
    """Autoregressive sampling from a trained LM, as ONE compiled loop.

    ``use_cache=True`` (default) prefills the prompt in one batched pass,
    then decodes incrementally through per-layer KV caches — O(T_max)
    attention per token (:meth:`TransformerLM.decode_step`).
    ``use_cache=False`` recomputes the full causal prefix each step —
    O(T^2) per token, but exercises the exact training forward (useful for
    cross-checking). Ring attention (sequence-sharded K/V has no dense
    cache to fill) falls back to the recompute path automatically. MoE
    decodes through the cache: the prompt prefill routes with the whole
    prompt as one GShard group (training semantics), then each generated
    token routes alone — per-expert capacity is >= 1, so single-token
    decode never drops to the residual path, where a training forward over
    the same prefix might (capacity pressure from the other tokens). With
    ample ``expert_capacity_factor`` the two paths agree exactly.

    ``temperature=0`` is greedy argmax (no key needed); otherwise pass a
    PRNG ``key``. ``top_k`` restricts sampling to the k most likely tokens;
    ``top_p`` to the smallest set whose (temperature-scaled) probability
    mass reaches p (nucleus sampling) — both filters compose.
    ``eos_token_id``: once a sequence samples EOS, every later position is
    forced to EOS (the loop stays a fixed-trip compiled scan; finished
    sequences just stop changing).

    ``max_new_tokens`` and ``eos_token_id`` may each also be a per-sequence
    array of length B (``rocket_tpu.serve`` parity — both paths share the
    sampling core in ``models/sampling.py``): the loop runs to the LONGEST
    limit and sequences that hit their own limit freeze early, filling
    with their EOS (or 0 where eos is absent/-1). Per-sequence values are
    runtime arrays, not compile-time constants — varying them never
    recompiles the loop.

    Per-step sample keys are derived with ``fold_in(key, position)``, so
    both paths produce identical samples for the same key. Returns
    (B, prompt_len + max(max_new_tokens)) int32.
    """
    import numpy as np

    if use_cache and (
        model.config.attention_impl == "ring"
        or model.config.state_mixer is not None
        or any(getattr(b.attn, "extended", False) for b in model.blocks)
    ):
        # See docstring — no dense KV cache to fill; a state layer's cache
        # is its state, which only the serving path carries.
        use_cache = False
    prompt = jnp.asarray(prompt_tokens, jnp.int32)
    if prompt.ndim == 1:
        prompt = prompt[None, :]
    b, start = prompt.shape
    if np.ndim(max_new_tokens) == 0:  # python OR numpy integer scalar
        per_seq_new = np.full((b,), int(max_new_tokens), np.int32)
    else:
        per_seq_new = np.asarray(max_new_tokens, np.int32)
        if per_seq_new.shape != (b,):
            raise ValueError(
                f"generate: per-sequence max_new_tokens must have shape "
                f"({b},), got {per_seq_new.shape}"
            )
        if (per_seq_new < 0).any():
            raise ValueError("generate: max_new_tokens must be >= 0")
    total = start + int(per_seq_new.max())
    if total > model.config.max_seq_len:
        raise ValueError(
            f"generate: prompt {start} + new {int(per_seq_new.max())} tokens "
            f"exceed max_seq_len {model.config.max_seq_len}"
        )
    if temperature > 0 and key is None:
        raise ValueError("generate: sampling (temperature > 0) needs a PRNG key")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        # top_p <= 0 would mask EVERY token to -inf and categorical() would
        # silently emit token 0 forever.
        raise ValueError(f"generate: top_p must be in (0, 1], got {top_p}")
    if eos_token_id is None:
        eos_vec = np.full((b,), -1, np.int32)
    elif np.ndim(eos_token_id) == 0:  # python OR numpy integer scalar
        eos_vec = np.full((b,), int(eos_token_id), np.int32)
    else:
        eos_vec = np.asarray(eos_token_id, np.int32)
        if eos_vec.shape != (b,):
            raise ValueError(
                f"generate: per-sequence eos_token_id must have shape "
                f"({b},), got {eos_vec.shape}"
            )

    buf = jnp.zeros((b, total), jnp.int32).at[:, :start].set(prompt)
    key = jax.random.key(0) if key is None else key
    run = _generate_fn(
        model, start, total, float(temperature),
        None if top_k is None else int(top_k),
        None if top_p is None else float(top_p),
        use_cache,
    )
    # Absolute end position per sequence — a runtime arg (with eos_vec), so
    # per-request values never key the compile cache.
    limits = jnp.asarray(start + per_seq_new, jnp.int32)
    return run(variables["params"], buf, key, jnp.asarray(eos_vec), limits)


def _decode_params(params, activation_dtype):
    """Cast float params ONCE to the compute dtype before the decode loop.

    Inside the loop every layer would otherwise cast its f32 master weights
    per token step (``Dense.apply``'s ``w.astype(x.dtype)``) — decode is
    HBM-bound on parameter streaming, so reading 4-byte masters to produce
    2-byte operands every step doubles the bytes on the binding resource.
    Hoisting the cast out of the loop halved measured ms/token on GPT-2
    124M (see docs/performance.md Decode). Matches training numerics: the
    compiled train step computes with the same bf16-cast weights."""
    if activation_dtype is None:
        return params
    dt = jnp.dtype(activation_dtype)
    return jax.tree.map(
        lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        params,
    )


@functools.lru_cache(maxsize=32)
def _generate_fn(model, start, total, temperature, top_k, top_p, use_cache):
    """Jitted generation loop, cached by (model, window, sampling knobs) —
    a fresh closure per generate() call would retrace and recompile the
    whole model every invocation. Per-sequence EOS ids and length limits
    enter as runtime arrays (``eos_vec``: -1 = no EOS for that row;
    ``limits``: absolute end positions), so they never key this cache."""
    from rocket_tpu.models.sampling import freeze_after_eos, sample_tokens

    if use_cache:

        @jax.jit
        def run(params, buf, key, eos_vec, limits):
            params = _decode_params(params, model.config.activation_dtype)
            dtype = jnp.dtype(model.config.activation_dtype or jnp.float32)
            caches = model.init_cache(buf.shape[0], total, dtype)
            # Batched prefill: one MXU-friendly pass fills every layer's
            # cache for the whole prompt and yields position start-1 logits.
            logits, caches = model.decode_step(
                params, buf[:, :start], caches, 0
            )

            done0 = start >= limits

            def body(i, carry):
                buf, caches, logits, done = carry
                nxt = sample_tokens(logits, key, i, temperature, top_k, top_p)
                nxt, done = freeze_after_eos(nxt, done, eos_vec)
                done = done | (i + 1 >= limits)
                buf = buf.at[:, i].set(nxt.astype(jnp.int32))
                tok = jax.lax.dynamic_slice_in_dim(buf, i, 1, axis=1)
                logits, caches = model.decode_step(params, tok, caches, i)
                return buf, caches, logits, done

            buf, _, _, _ = jax.lax.fori_loop(
                start, total, body, (buf, caches, logits, done0)
            )
            return buf

        return run

    @jax.jit
    def run(params, buf, key, eos_vec, limits):
        params = _decode_params(params, model.config.activation_dtype)

        def body(i, carry):
            buf, done = carry
            out, _ = model.apply(
                {"params": params, "state": {}}, {model.tokens_key: buf},
                mode="eval",
            )
            logits = jax.lax.dynamic_index_in_dim(
                out[model.logits_key], i - 1, axis=1, keepdims=False
            )
            nxt = sample_tokens(logits, key, i, temperature, top_k, top_p)
            nxt, done = freeze_after_eos(nxt, done, eos_vec)
            done = done | (i + 1 >= limits)
            return buf.at[:, i].set(nxt.astype(jnp.int32)), done

        done0 = start >= limits
        buf, _ = jax.lax.fori_loop(start, total, body, (buf, done0))
        return buf

    return run
