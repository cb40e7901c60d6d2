"""Module capsule — wraps a model; compiles the fused TPU train/eval step.

Reference semantics (``rocket/core/module.py``):

* children are the post-forward pipeline — Loss / Optimizer / Scheduler
  (``module.py:16-18``) — and the forward *replaces the batch*:
  ``attrs.batch = module.forward(attrs.batch)`` (``module.py:73``);
* prepared exactly once per raw model with identity-dedup (``module.py:29-43``),
  so one model shared by train and eval capsules has one set of variables;
* train/eval switched off the ambient grad mode (``module.py:62-68``) — here
  off the explicit ``attrs.mode`` set by the Looper;
* gradient accumulation wraps the forward (``module.py:71``).

TPU substrate (SURVEY.md §7 design stance): per-iteration array work —
forward, loss, backward, optimizer update, gradient accumulation and the
data-parallel gradient mean — cannot stay as N eager capsule bodies; it is
compiled here into ONE jitted, donated-argument ``train_step(state, batch) ->
(state, metrics)``. The Loss/Optimizer/Scheduler capsules contribute their
pieces at setup time (objective, optax factory, lr schedule) and keep their
host-side roles (logging, checkpoint state) at launch time. The cross-replica
gradient mean needs no explicit collective: the loss is a mean over the
*global* (mesh-sharded) batch, and XLA GSPMD lowers the backward reduction to
ICI collectives.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from rocket_tpu import optim as optim_lib
from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.dispatcher import Dispatcher

__all__ = ["Module", "PreparedModule"]


def _tree_zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def _to_plain(tree):
    """Normalize Attributes bags to plain dicts so the step fn sees one
    container type regardless of how the bag auto-wrapped nested dicts."""
    from rocket_tpu.core.attributes import Attributes

    if isinstance(tree, (dict, Attributes)):
        return {k: _to_plain(v) for k, v in tree.items()}
    if isinstance(tree, tuple):
        return tuple(_to_plain(v) for v in tree)
    if isinstance(tree, list):
        return [_to_plain(v) for v in tree]
    return tree


def _split_batch(batch):
    """Split a batch pytree into (jit-traceable, static) halves.

    Rocket collate lets strings/tuples pass through uncollated
    (``utils.py:19-27``); those leaves cannot enter jit, so they ride around
    the compiled step and are merged back into the output batch.
    """
    batch = _to_plain(batch)
    is_arr = lambda leaf: isinstance(leaf, (jax.Array, np.ndarray))
    dynamic = jax.tree.map(lambda l: l if is_arr(l) else None, batch)
    static = jax.tree.map(lambda l: None if is_arr(l) else l, batch)
    return dynamic, static


def _strip_marker(batch):
    """Drop the device-gather/slice marker's all-None residue from a merged
    output batch (the step materialized the real rows; downstream capsules
    must see only data keys)."""
    if isinstance(batch, dict):
        batch.pop("_device_gather", None)
        batch.pop("_device_slice", None)
    return batch


def _merge_batch(dynamic, static):
    """Overlay the static (non-array) leaves back onto the step output.

    The output structure may differ from the input (the forward adds keys —
    e.g. ``logits``), so this is a recursive union, not a tree.map: dynamic
    values win, static fills the holes.
    """
    if static is None:
        return dynamic
    if dynamic is None:
        return static
    if isinstance(dynamic, dict) and isinstance(static, dict):
        out = {}
        for key in {**static, **dynamic}:
            out[key] = _merge_batch(dynamic.get(key), static.get(key))
        return out
    if isinstance(dynamic, (list, tuple)) and isinstance(static, (list, tuple)):
        merged = [
            _merge_batch(d, s)
            for d, s in zip(dynamic, static)
        ]
        merged += list(dynamic[len(static):]) + list(static[len(dynamic):])
        return type(dynamic)(merged) if isinstance(dynamic, tuple) else merged
    return dynamic


class PreparedModule:
    """The shared prepared record for one raw model (reference
    ``Accelerator._models`` entry): its live variables plus step bookkeeping.
    Mutable on purpose — train and eval capsules wrapping the same model see
    the same state."""

    def __init__(self, model, state: dict) -> None:
        self.model = model
        self.state = state  # {"params", "model_state", "opt_state", "step", "base_key", ...}
        # Which layout the state carries: None (not yet placed), "default"
        # (replicated), or "rule" (an explicit param_sharding was applied).
        self.placed_by: Optional[str] = None
        # Host mirror of state["step"], maintained WITHOUT device reads: 0 at
        # init, overwritten by the Checkpointer from the (host-side)
        # checkpoint index on resume. A device_get here would be a host
        # sync on every step of the loop that reads it.
        self.host_step: int = 0


class Module(Dispatcher):
    """Capsule wrapping a :class:`rocket_tpu.nn.Model`.

    Parameters
    ----------
    model:
        Object with ``init(key) -> variables`` and
        ``apply(variables, batch, *, mode, rng) -> (batch, new_state)``.
    capsules:
        Post-forward pipeline — ``Loss`` / ``Optimizer`` / ``Scheduler``
        (train) or empty (eval).
    compute_dtype:
        When set (e.g. ``jnp.bfloat16``), float batch inputs are cast to this
        dtype before the forward; params stay float32 master copies (layers
        cast at use).
    remat:
        Apply ``jax.checkpoint`` to the forward to trade FLOPs for HBM.
    param_sharding:
        Optional fn ``(path_tuple, leaf) -> PartitionSpec`` for sharded params
        (tensor parallelism / fsdp); default fully replicated.
    return_outputs:
        ``"eval"`` (default): the transformed batch is materialized only in
        eval mode — train returns just metrics, keeping activations out of
        HBM round-trips. ``"always"`` / ``"never"`` override.
    """

    def __init__(
        self,
        model,
        capsules=(),
        compute_dtype=None,
        remat: bool = False,
        param_sharding: Optional[Callable] = None,
        return_outputs: str = "eval",
        ema_decay: Optional[float] = None,
        use_ema: bool = False,
        batch_transform: Optional[Callable] = None,
        statefull: bool = False,
        priority: int = 1000,
        runtime=None,
    ) -> None:
        """``ema_decay``: maintain an exponential moving average of the
        params in the compiled step (``state["ema_params"]``, updated on the
        sync boundary, checkpointed with the model). ``use_ema``: this
        (eval) module forwards with the EMA params instead of the raw ones —
        requires a train module with ``ema_decay`` sharing the same model.
        ``batch_transform``: pure ``fn(batch_dict, key) -> batch_dict``
        compiled into the TRAIN step before the forward (on-device data
        augmentation — see ``rocket_tpu.data.augment``); eval is untouched.
        """
        if ema_decay is not None and not 0.0 < ema_decay < 1.0:
            raise ValueError(f"Module: ema_decay must be in (0, 1), got {ema_decay}")
        super().__init__(capsules, statefull=statefull, priority=priority, runtime=runtime)
        self._model = model
        self._compute_dtype = compute_dtype
        self._remat = remat
        self._param_sharding = param_sharding
        self._return_outputs = return_outputs
        self._ema_decay = ema_decay
        self._use_ema = use_ema
        self._batch_transform = batch_transform
        self._prepared: Optional[PreparedModule] = None
        self._train_step = None
        self._eval_step = None
        self._host_step: Optional[int] = None
        self._health_label: Optional[str] = None
        # Per-mode "first call done" flags: the first invocation of a jitted
        # step blocks the host on trace+lower+compile, so telemetry wraps
        # exactly that call in an explicit "compile" span.
        self._stepped = {"train": False, "eval": False}

    # -- introspection helpers ---------------------------------------------

    @property
    def prepared(self) -> Optional[PreparedModule]:
        return self._prepared

    @property
    def state(self) -> Optional[dict]:
        return None if self._prepared is None else self._prepared.state

    def _find_contrib(self):
        """Collect compiled-step contributions from children."""
        from rocket_tpu.core.loss import Loss
        from rocket_tpu.core.optimizer import Optimizer
        from rocket_tpu.core.scheduler import Scheduler

        losses = self.find(Loss)
        optimizers = self.find(Optimizer)
        schedulers = self.find(Scheduler)
        if len(losses) > 1 or len(optimizers) > 1 or len(schedulers) > 1:
            raise RuntimeError(
                "Module: at most one Loss, Optimizer and Scheduler per Module."
            )
        objective = losses[0].objective if losses else None
        opt = optimizers[0].opt if optimizers else None
        schedule = schedulers[0].schedule if schedulers else None
        base_lr = optimizers[0].learning_rate if optimizers else None
        clip_norm = optimizers[0].clip_norm if optimizers else None
        self._opt_capsule = optimizers[0] if optimizers else None
        return objective, opt, schedule, base_lr, clip_norm

    def _grad_sync_plan(self):
        """Route the train step's gradient reduction through the
        bucketed async reduce-scatter (``parallel.grad_sync``)?

        Returns the kwargs for ``value_and_grad_sharded`` or None for
        the plain GSPMD reduction. Engages only where the explicit
        formulation is known-equivalent: a pure data-parallel mesh (the
        manual region owns every partitioned axis), no gradient
        accumulation (the accumulator holds REDUCED grads), and no
        batch-dependent model state (BatchNorm's cross-replica stats
        are GSPMD reductions inside the forward — a manual data region
        would silently localize them).
        """
        from rocket_tpu.parallel.collectives import overlap_enabled

        opt_capsule = getattr(self, "_opt_capsule", None)
        if opt_capsule is None or opt_capsule.grad_sync == "off":
            return None
        if not overlap_enabled():
            return None
        runtime = self._runtime
        mesh = runtime.mesh
        data_axes = tuple(runtime.DATA_AXES)
        import numpy as _np

        n = int(_np.prod([
            mesh.shape[a] for a in data_axes if a in mesh.shape
        ] or [1]))
        non_data = [
            a for a in mesh.axis_names
            if a not in data_axes and int(mesh.shape[a]) > 1
        ]
        if n <= 1 or non_data:
            return None
        if runtime.gradient_accumulation_steps > 1:
            return None
        if jax.tree_util.tree_leaves(self._prepared.state["model_state"]):
            return None
        marker = getattr(self._param_sharding, "fsdp_axis", None)
        if opt_capsule.grad_sync == "auto" and marker is None:
            return None
        return dict(
            mesh=mesh,
            data_axes=data_axes,
            spec_fn=self._param_sharding,
            bucket_bytes=opt_capsule.grad_bucket_bytes,
            wire_dtype=opt_capsule.grad_wire_dtype,
        )

    # -- events ------------------------------------------------------------

    def setup(self, attrs: Attributes | None = None) -> None:
        super().setup(attrs)  # children first register their own state
        runtime = self._runtime

        prepared = runtime.models.lookup(self._model)
        if prepared is None:
            # Init under jit: eager init dispatches thousands of tiny host
            # ops (GPT-2 124M measured ~23 s on a 1-core host vs ~2 s
            # compiled). Same keys -> same params; models whose init isn't
            # traceable (host-side randomness, data-dependent shapes) fall
            # back to eager.
            key = runtime.next_key()
            try:
                # block_until_ready: jax dispatch is async — an execution
                # failure (OOM etc.) would otherwise escape this guard and
                # surface later with a confusing traceback.
                with runtime.telemetry.span(
                    f"compile/init[{type(self._model).__name__}]",
                    cat="compile",
                ):
                    variables = jax.block_until_ready(
                        jax.jit(self._model.init)(key)
                    )
            except (TypeError, jax.errors.UnexpectedTracerError,
                    jax.errors.ConcretizationTypeError,
                    jax.errors.TracerArrayConversionError,
                    jax.errors.TracerIntegerConversionError,
                    jax.errors.TracerBoolConversionError) as exc:
                # Only TRACE-time failures mean "this init isn't jittable —
                # run it eagerly". Execution failures (OOM, numerics) would
                # fail eagerly too: falling back would run the broken init
                # twice and bury the first, more precise error (round-4
                # advisor) — let those propagate.
                self.log_warning(
                    f"compiled init failed ({type(exc).__name__}: {exc}) — "
                    "falling back to eager init"
                )
                variables = self._model.init(key)
            state = {
                "params": variables["params"],
                "model_state": variables.get("state", {}),
                "step": jnp.zeros((), jnp.int32),
                "base_key": jax.random.key_data(runtime.next_key()),
            }
            prepared = PreparedModule(self._model, state)
            runtime.models.add(self._model, prepared)
        self._prepared = prepared

        objective, opt, schedule, base_lr, clip_norm = self._find_contrib()
        if opt is not None:
            if objective is None:
                raise RuntimeError("Module: an Optimizer child requires a Loss child.")
            lr = schedule if schedule is not None else (base_lr if base_lr is not None else 1e-3)
            tx = optim_lib.resolve(opt, lr)
            report_grad_norm = clip_norm is not None
            if clip_norm is not None:
                tx = optax.chain(optax.clip_by_global_norm(clip_norm), tx)
            if "opt_state" not in prepared.state:
                prepared.state["opt_state"] = tx.init(prepared.state["params"])
                if runtime.gradient_accumulation_steps > 1:
                    prepared.state["grad_accum"] = _tree_zeros_like(
                        prepared.state["params"]
                    )
                    # Running loss over the accumulation window, kept in-step
                    # so the Loss capsule never issues eager device ops.
                    prepared.state["loss_acc"] = jnp.zeros((), jnp.float32)
            self._lr_fn = lr if callable(lr) else (lambda step: jnp.asarray(lr))
            if self._ema_decay is not None and "ema_params" not in prepared.state:
                # EMA shadow starts as a REAL copy of the params (aliased
                # leaves would be donated twice by the step); lives in the
                # donated state so it updates in-step and checkpoints with
                # the model.
                prepared.state["ema_params"] = jax.tree.map(
                    jnp.copy, prepared.state["params"]
                )
            health_mon = getattr(runtime, "health", None)
            if health_mon is not None and health_mon.enabled:
                # Health sentinels (rocket_tpu.obs.health): the on-device
                # EMA moments + skip/anomaly counters live in the donated
                # train state and checkpoint with the model; the monitor
                # learns the params tree's top-level branch order so the
                # fetched health words decode with real branch names.
                from rocket_tpu.obs import health as health_lib

                if "health" not in prepared.state:
                    prepared.state["health"] = health_lib.init_state()
                # register_step may disambiguate the label (two Modules
                # wrapping the same model class) — observe under what it
                # returns.
                self._health_label = health_mon.register_step(
                    f"train_step[{type(self._model).__name__}]",
                    health_lib.branch_names(prepared.state["params"]),
                )
            self._build_train_step(objective, tx, report_grad_norm=report_grad_norm)
        elif objective is not None:
            raise RuntimeError("Module: a Loss child requires an Optimizer child.")
        elif self._ema_decay is not None:
            # ema_decay on a module with no update rule would silently never
            # create or advance the shadow (likely confusion with use_ema).
            raise RuntimeError(
                "Module: ema_decay requires an Optimizer child (use "
                "use_ema=True on the eval module to READ the shadow)."
            )
        elif self._batch_transform is not None:
            raise RuntimeError(
                "Module: batch_transform compiles into the TRAIN step and "
                "requires Loss + Optimizer children (eval is never "
                "transformed)."
            )

        # Lay the state out on the mesh: replicated by default, or per the
        # param_sharding rule (tensor parallel / fsdp). Placement happens
        # ONCE per prepared model — a second capsule wrapping the same model
        # (e.g. the eval Module) must not clobber the layout the first one
        # installed. An explicit rule upgrades a default placement; two
        # different explicit rules are an error.
        if self._param_sharding is not None:
            if prepared.placed_by == "rule":
                raise RuntimeError(
                    "Module: model already placed by another capsule's "
                    "param_sharding rule; only one rule per model."
                )
            prepared.state = self._place_state(prepared.state)
            prepared.placed_by = "rule"
        elif prepared.placed_by is None:
            prepared.state = self._place_state(prepared.state)
            prepared.placed_by = "default"
        self._build_eval_step()

    def _place_state(self, state: dict) -> dict:
        runtime = self._runtime
        if self._param_sharding is None:
            return jax.device_put(state, runtime.replicated)

        from rocket_tpu.utils.pytree import key_path_names as norm

        mesh_shape = runtime.mesh.shape

        def spec_for(names, leaf):
            """The rule's spec for one param, minus the mesh axes that do
            not divide the dim they name: device_put refuses uneven
            shards, so such a dim stays replicated (GPT-2's 50257-row
            embedding over an even 'model' axis) and the rest of the spec
            applies. Said once per param, never silent."""
            spec = self._param_sharding(names, leaf)
            if spec is None:
                return None
            fitted = list(spec)
            for i, (dim, axes) in enumerate(zip(getattr(leaf, "shape", ()), spec)):
                if axes is None:
                    continue
                group = axes if isinstance(axes, tuple) else (axes,)
                size = int(np.prod([mesh_shape[a] for a in group]))
                if dim % size:
                    self.log_warning(
                        f"param {'/'.join(names)}: dim {i} ({dim}) is not "
                        f"divisible by mesh axes {group} ({size}) — that "
                        "dim stays replicated"
                    )
                    fitted[i] = None
            return tuple(fitted)

        # One (shape, spec) per param: params, the grad accumulator and the
        # EMA shadow share these paths, and optimizer moments mirror them.
        param_layout = {}
        for ppath, pleaf in jax.tree_util.tree_flatten_with_path(state["params"])[0]:
            names = norm(ppath)
            param_layout[names] = (getattr(pleaf, "shape", ()), spec_for(names, pleaf))

        def place(path, leaf):
            spec = param_layout[norm(path)][1]
            sharding = runtime.replicated if spec is None else runtime.sharding(*spec)
            return jax.device_put(leaf, sharding)

        # Param-shaped optimizer moments (Adam mu/nu, momentum buffers...)
        # must follow the param layout, or a TP/FSDP run replicates ~2x the
        # model per device and defeats the sharded layout. An opt_state leaf
        # at path (..., 'mu', <param path...>) is matched to its param by the
        # longest path suffix with the same shape; unmatched leaves (step
        # counters, scalars) replicate.
        def place_mirrored(path, leaf):
            names = norm(path)
            shape = getattr(leaf, "shape", None)
            for k in range(len(names)):
                hit = param_layout.get(names[k:])
                if hit is not None and hit[0] == shape:
                    spec = hit[1]
                    sharding = (
                        runtime.replicated if spec is None else runtime.sharding(*spec)
                    )
                    return jax.device_put(leaf, sharding)
            return jax.device_put(leaf, runtime.replicated)

        out = {
            key: jax.device_put(value, runtime.replicated)
            for key, value in state.items()
            if key not in ("params", "grad_accum", "opt_state", "ema_params")
        }
        out["params"] = jax.tree_util.tree_map_with_path(place, state["params"])
        if "opt_state" in state:
            out["opt_state"] = jax.tree_util.tree_map_with_path(
                place_mirrored, state["opt_state"]
            )
        if "grad_accum" in state:
            # Accumulator mirrors the param layout.
            out["grad_accum"] = jax.tree_util.tree_map_with_path(
                place, state["grad_accum"]
            )
        if "ema_params" in state:
            out["ema_params"] = jax.tree_util.tree_map_with_path(
                place, state["ema_params"]
            )
        return out

    # -- compiled steps ----------------------------------------------------

    def _batch_materializer(self):
        """In-step materialization of device-gather marker batches.

        A device-resident ``Dataset`` yields ``{"_device_gather": {cache,
        perm, index}}`` markers (``data/device_cache.py``); gathering the
        rows INSIDE the compiled step makes the steady-state loop one
        device dispatch per step instead of two (small-model steps are
        dispatch-bound)."""
        from rocket_tpu.data.device_cache import materialize_marker

        runtime = self._runtime
        multi = jax.device_count() > 1

        def materialize(batch):
            data = materialize_marker(batch)  # no-op on non-marker batches
            if data is not batch and multi:
                data = jax.lax.with_sharding_constraint(
                    data, runtime.batch_sharding
                )
            return data

        return materialize

    def _forward(self):
        model = self._model
        compute_dtype = self._compute_dtype

        def forward(params, model_state, batch, *, mode, rng):
            if compute_dtype is not None:
                batch = jax.tree.map(
                    lambda l: l.astype(compute_dtype)
                    if isinstance(l, jax.Array) and jnp.issubdtype(l.dtype, jnp.floating)
                    else l,
                    batch,
                )
            variables = {"params": params, "state": model_state}
            return model.apply(variables, batch, mode=mode, rng=rng)

        # Overlapped TP collectives: when the param_sharding rule set
        # carries the tp_axis marker (gpt2_tp_rules does) and the mesh
        # has that axis, the forward traces under the tp_overlap context
        # — layers swap GSPMD's blocking all-reduces for the ring-
        # pipelined all-gather/reduce-scatter matmuls
        # (parallel/collectives.py). ROCKET_TPU_OVERLAP=0 restores the
        # plain program; the context manager no-ops when the axis is
        # absent or size 1.
        tp_axis = getattr(self._param_sharding, "tp_axis", None)
        if tp_axis is not None:
            from rocket_tpu.parallel.collectives import tp_overlap

            runtime = self._runtime
            mesh = runtime.mesh
            vocab_sharded = bool(
                getattr(self._param_sharding, "tp_vocab_sharded", False)
            )
            data_axes = tuple(runtime.DATA_AXES)
            tp_inner = forward

            def forward(params, model_state, batch, *, mode, rng):  # noqa: F811
                with tp_overlap(
                    mesh, axis=tp_axis, data_axes=data_axes,
                    vocab_sharded_embed=vocab_sharded,
                ):
                    return tp_inner(
                        params, model_state, batch, mode=mode, rng=rng
                    )

        remat = self._remat
        cfg = getattr(self._model, "config", None)
        if (
            remat
            and getattr(cfg, "scan_layers", False)
            and getattr(cfg, "scan_remat", False)
        ):
            # The scanned blocks already checkpoint themselves (the
            # scan+remat recipe); an outer checkpoint would recompute the
            # whole scan AND each block again inside it.
            self.log_info("remat=True ignored: scan_layers already remats per block")
            remat = False
        if remat:
            base = forward

            def forward(params, model_state, batch, *, mode, rng):  # noqa: F811
                # `mode` is a python string — close over it so jax.checkpoint
                # only sees array (pytree) arguments.
                fn = lambda p, s, b, r: base(p, s, b, mode=mode, rng=r)  # noqa: E731
                return jax.checkpoint(fn)(params, model_state, batch, rng)

        return forward

    def _build_train_step(self, objective, tx, report_grad_norm=False) -> None:
        runtime = self._runtime
        accum = runtime.gradient_accumulation_steps
        forward = self._forward()
        # Models may own their fused loss+backward (the 1F1B pipeline
        # schedule computes grads inside ONE pipelined program —
        # TransformerLM.pipelined_value_and_grad). None = standard path.
        custom_vag = None
        vag_builder = getattr(self._model, "pipelined_value_and_grad", None)
        if vag_builder is not None:
            custom_vag = vag_builder(objective)
            if custom_vag is not None:
                self.log_info(
                    "train step: model-provided pipelined value_and_grad "
                    "(1F1B schedule)"
                )
        grad_sync_plan = (
            None if custom_vag is not None else self._grad_sync_plan()
        )
        if grad_sync_plan is not None:
            self.log_info(
                "train step: bucketed async grad reduce-scatter "
                f"(wire={grad_sync_plan['wire_dtype']}, "
                f"bucket={grad_sync_plan['bucket_bytes'] >> 20}MiB)"
            )
        lr_fn = self._lr_fn
        return_out = self._return_outputs == "always"
        ema_decay = self._ema_decay
        batch_transform = self._batch_transform

        # Health sentinels: config captured statically at build time so the
        # compiled step carries no host handles; `health_gate` decides
        # whether the optimizer application is wrapped in lax.cond on the
        # step-ok predicate (skip_step / dump_and_halt keep state finite).
        health_mon = getattr(runtime, "health", None)
        hcfg = (
            health_mon.config
            if health_mon is not None and health_mon.enabled
            else None
        )
        health_gate = hcfg.gated if hcfg is not None else False
        if hcfg is not None:
            from rocket_tpu.obs import health as health_lib

        def ema_update(ema, params):
            # ema += (1-d) * (params - ema) — one fused pass per leaf.
            return jax.tree.map(
                lambda e, p: e + (1.0 - ema_decay) * (p - e), ema, params
            )

        materialize = self._batch_materializer()

        def train_step(state, batch):
            batch = materialize(batch)
            rng = jax.random.fold_in(
                jax.random.wrap_key_data(state["base_key"]), state["step"]
            )
            if batch_transform is not None:
                # On-device augmentation, once per step (outside any remat),
                # on the raw batch before the compute-dtype cast. Salted key
                # domain disjoint from the forward's dropout keys.
                batch = batch_transform(
                    dict(batch), jax.random.fold_in(rng, 0xA9517)
                )

            if custom_vag is not None:
                (loss, (out, mstate)), grads = custom_vag(
                    state["params"], state["model_state"], batch, rng
                )
            elif grad_sync_plan is not None:
                # Bucketed async gradient reduce-scatter: the backward
                # runs inside a manual data region and each bucket's
                # reduction issues as the walk retires it
                # (parallel/grad_sync.py). Grads come back already
                # globally reduced — sharded where the rules shard the
                # param, full elsewhere — so the update below is
                # unchanged.
                from rocket_tpu.parallel import grad_sync as grad_sync_lib

                def loss_fn_gs(params, dbatch):
                    out, mstate = forward(
                        params, state["model_state"], dbatch,
                        mode="train", rng=rng,
                    )
                    loss = objective(out)
                    return loss.astype(jnp.float32), (out, mstate)

                (loss, (out, mstate)), grads = (
                    grad_sync_lib.value_and_grad_sharded(
                        loss_fn_gs, state["params"], batch,
                        has_aux=True, **grad_sync_plan,
                    )
                )
            else:

                def loss_fn(params):
                    out, mstate = forward(
                        params, state["model_state"], batch, mode="train", rng=rng
                    )
                    loss = objective(out)
                    return loss.astype(jnp.float32), (out, mstate)

                (loss, (out, mstate)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(state["params"])

            new_state = dict(state)
            new_state["model_state"] = mstate
            new_state["step"] = state["step"] + 1

            if hcfg is not None:
                # Pre-update sentinels: the gate predicate must exist
                # before any state is touched. Flags and the global grad
                # norm come out of one shared pass over the grads.
                step_ok, loss_ok, grad_branch_ok, health_grad_norm = (
                    health_lib.step_flags(loss, grads)
                )
            else:
                step_ok = None

            if accum == 1:
                ema_in = state["ema_params"] if ema_decay is not None else {}

                def apply_update1(operand):
                    grads, params, opt_state, ema = operand
                    updates, opt_state = tx.update(grads, opt_state, params)
                    # Sentinel update-norm reads the updates while they
                    # are live, inside this branch — computing ‖Δθ‖ from
                    # old-vs-new params outside would pin the donated old
                    # param buffers across the update.
                    unorm = (
                        optax.global_norm(updates)
                        if hcfg is not None
                        else jnp.zeros((), jnp.float32)
                    )
                    params = optax.apply_updates(params, updates)
                    if ema_decay is not None:
                        ema = ema_update(ema, params)
                    return params, opt_state, ema, unorm

                def hold1(operand):
                    _grads, params, opt_state, ema = operand
                    # update_norm 0: a held step moved nothing.
                    return params, opt_state, ema, jnp.zeros((), jnp.float32)

                operand = (grads, state["params"], state["opt_state"], ema_in)
                if health_gate:
                    # A non-finite loss/grad step must not touch params,
                    # moments or the EMA — the whole update is gated on
                    # the health predicate (the skip is counted in the
                    # sentinel state below).
                    params_out, opt_state, ema_out, update_norm = (
                        jax.lax.cond(step_ok, apply_update1, hold1, operand)
                    )
                else:
                    params_out, opt_state, ema_out, update_norm = (
                        apply_update1(operand)
                    )
                new_state["params"] = params_out
                new_state["opt_state"] = opt_state
                opt_step = state["step"]
                if ema_decay is not None:
                    new_state["ema_params"] = ema_out
            else:
                # The accumulation phase is DERIVED from the step counter —
                # host and device compute the same boundary from the same
                # number, so there is no second counter to drift across
                # epochs or resumes.
                if health_gate:
                    # A non-finite microbatch must not poison the window:
                    # its grads are dropped from the accumulator and the
                    # boundary update applies the finite remainder.
                    acc = jax.tree.map(
                        lambda a, g: jnp.where(step_ok, a + g, a),
                        state["grad_accum"], grads,
                    )
                else:
                    acc = jax.tree.map(jnp.add, state["grad_accum"], grads)
                is_boundary = (state["step"] + 1) % accum == 0
                opt_step = state["step"] // accum

                def apply_update(operand):
                    acc, params, opt_state, ema = operand
                    mean_grads = jax.tree.map(lambda g: g / accum, acc)
                    # The pre-clip norm of what the clip actually acts on
                    # (the window's mean grads) — NOT the microbatch grads.
                    gn = (
                        optax.global_norm(mean_grads)
                        if report_grad_norm
                        else jnp.zeros((), jnp.float32)
                    )
                    updates, opt_state = tx.update(mean_grads, opt_state, params)
                    # Sentinel update-norm on the live updates, inside
                    # the branch (donation-friendly — see accum==1).
                    unorm = (
                        optax.global_norm(updates)
                        if hcfg is not None
                        else jnp.zeros((), jnp.float32)
                    )
                    params = optax.apply_updates(params, updates)
                    if ema_decay is not None:
                        ema = ema_update(ema, params)
                    return (_tree_zeros_like(acc), params, opt_state, ema, gn,
                            unorm)

                def hold(operand):
                    acc, params, opt_state, ema = operand
                    zero = jnp.zeros((), jnp.float32)
                    return acc, params, opt_state, ema, zero, zero

                # The EMA rides the cond operands even when off (empty dict)
                # so both branches share one signature.
                ema_in = state["ema_params"] if ema_decay is not None else {}
                (acc, params, opt_state, ema_out, accum_grad_norm,
                 update_norm) = jax.lax.cond(
                    is_boundary,
                    apply_update,
                    hold,
                    (acc, state["params"], state["opt_state"], ema_in),
                )
                new_state["grad_accum"] = acc
                new_state["params"] = params
                new_state["opt_state"] = opt_state
                if ema_decay is not None:
                    new_state["ema_params"] = ema_out

            if accum == 1:
                loss_window = loss
            else:
                loss_contrib = loss / accum
                if health_gate:
                    # Mirror the accumulator gate: a skipped microbatch's
                    # (non-finite) loss must not poison the window mean.
                    loss_contrib = jnp.where(step_ok, loss_contrib, 0.0)
                loss_acc = state["loss_acc"] + loss_contrib
                loss_window = jnp.where(is_boundary, loss_acc, 0.0)
                new_state["loss_acc"] = jnp.where(is_boundary, 0.0, loss_acc)

            metrics = {
                "loss": loss,
                # Mean loss over the just-closed accumulation window; only
                # meaningful on the sync boundary.
                "loss_window": loss_window,
                "lr": jnp.asarray(lr_fn(opt_step), jnp.float32),
            }
            if report_grad_norm:
                # Pre-clip global norm of the gradients the clip acts on:
                # the raw step grads (accum=1, XLA shares the reduction with
                # the clip itself) or the accumulation window's mean grads
                # (boundary only; zero off-boundary, where nothing clips).
                metrics["grad_norm"] = (
                    optax.global_norm(grads) if accum == 1 else accum_grad_norm
                )
            if isinstance(out, dict) and "moe_frac_dropped" in out:
                # MoE capacity-overflow fraction: a scalar worth tracking
                # even when the (large) output batch isn't returned.
                metrics["moe_frac_dropped"] = out["moe_frac_dropped"]
            if hcfg is not None:
                # Post-update sentinel half: fold this step into the
                # on-device EMA/counters and coalesce everything into ONE
                # small health word — the only array the host ever fetches
                # (lagged, explicit). Param flags + norm come from one
                # pass over the NEW params, so an update that corrupted
                # state flags here.
                new_state["health"], health_word, hextras = (
                    health_lib.update_sentinels(
                        state["health"],
                        loss=loss,
                        step=state["step"],
                        step_ok=step_ok,
                        loss_ok=loss_ok,
                        grad_branch_ok=grad_branch_ok,
                        grad_norm=health_grad_norm,
                        update_norm=update_norm,
                        new_params=new_state["params"],
                        gated=health_gate,
                        ema_decay=hcfg.ema_decay,
                        zscore_max=hcfg.zscore_max,
                        zscore_warmup=hcfg.zscore_warmup,
                    )
                )
                metrics["health_word"] = health_word
                # Scalar sentinels ride the step-metrics channel too, so
                # the Optimizer can publish them to the tracker/postfix
                # like lr/grad_norm (device scalars, no sync).
                metrics["health/update_ratio"] = hextras["update_ratio"]
                metrics["health/param_norm"] = hextras["param_norm"]
            if return_out:
                metrics["outputs"] = out
            return new_state, metrics

        self._train_step = jax.jit(train_step, donate_argnums=(0,))

    def _build_eval_step(self) -> None:
        forward = self._forward()
        materialize = self._batch_materializer()

        def eval_step(params, model_state, batch):
            out, _ = forward(
                params, model_state, materialize(batch), mode="eval", rng=None
            )
            return out

        self._eval_step = jax.jit(eval_step)

    # -- launch ------------------------------------------------------------

    def launch(self, attrs: Attributes | None = None) -> None:
        if attrs is None or attrs.batch is None:
            return  # no batch -> skip (module.py:59-60)

        dynamic, static = _split_batch(attrs.batch)
        state = self._prepared.state

        if attrs.mode == "train":
            if self._train_step is None:
                raise RuntimeError(
                    "Module: train launch without Loss/Optimizer children — "
                    "give this Module its post-forward pipeline or run it in "
                    "an eval Looper."
                )
            # Mirror of the device-side step counter, read from the prepared
            # record (maintained host-side; never a device fetch — see
            # PreparedModule.host_step).
            if self._host_step is None:
                self._host_step = int(self._prepared.host_step)
            # The host's side of the step: the first call traces, lowers
            # and compiles (and keeps its compile/ name), every later one
            # only enqueues. A host timer, no device op, strict-guard safe.
            telemetry = self._runtime.telemetry
            if not self._stepped["train"]:
                dispatch = telemetry.span(
                    f"compile/train_step[{type(self._model).__name__}]",
                    cat="compile",
                )
            else:
                dispatch = telemetry.span(
                    "train/step_dispatch", step=self._host_step
                )
            with dispatch:
                new_state, metrics = self._train_step(state, dynamic)
            self._stepped["train"] = True
            self._prepared.state = new_state
            self._host_step += 1
            self._prepared.host_step = self._host_step
            accum = self._runtime.gradient_accumulation_steps
            attrs.sync_gradients = (self._host_step % accum) == 0
            outputs = metrics.pop("outputs", None)
            health_word = metrics.pop("health_word", None)
            attrs.step_metrics = Attributes(metrics)
            if health_word is not None:
                # Hand the (device) health word to the monitor with its
                # host-side step context; the monitor fetches it only once
                # it is fetch_lag steps old (explicit, non-stalling
                # device_get) and applies the anomaly policy — under
                # dump_and_halt this is the call that raises.
                context = {}
                if attrs.looper is not None:
                    context["tag"] = attrs.looper.tag
                if attrs.launcher is not None:
                    context["epoch"] = attrs.launcher.epoch_idx
                if attrs.batch_info is not None and attrs.batch_info.index is not None:
                    context["batch_index"] = attrs.batch_info.index
                self._runtime.health.observe(
                    self._health_label, self._host_step, health_word, context
                )
            strict = self._runtime.strict
            if strict.enabled:
                # Retrace budget: a host-side cache-size read (no device
                # op); surfaced through the Tracker so a creeping recompile
                # shows up on the dashboard before it eats the run.
                step_label = f"train_step[{type(self._model).__name__}]"
                retraces = strict.note_retraces(step_label, self._train_step)
                if attrs.tracker is not None and attrs.sync_gradients:
                    if retraces is not None:  # None: no compile-cache probe
                        attrs.tracker.scalars["retraces"] = retraces
                    # The static SPMD audit's per-step collective count
                    # (strict.note_collectives, fed by
                    # analysis.shard_audit) rides the same channel:
                    # declared communication cost next to the live run
                    # it gates.
                    audited = strict.collective_counts.get(step_label)
                    if audited is not None:
                        attrs.tracker.scalars["audited_collectives"] = audited
            if outputs is not None:
                attrs.batch = _strip_marker(_merge_batch(outputs, static))
        else:
            if self._use_ema:
                # Checked here, not at setup: tree order must not matter
                # (the train module may legitimately set up after this one).
                if "ema_params" not in state:
                    raise RuntimeError(
                        "Module(use_ema=True): no EMA shadow in the model "
                        "state — the train Module wrapping this model must "
                        "set ema_decay."
                    )
                eval_params = state["ema_params"]
            else:
                eval_params = state["params"]
            if not self._stepped["eval"]:
                with self._runtime.telemetry.span(
                    f"compile/eval_step[{type(self._model).__name__}]",
                    cat="compile",
                ):
                    out = self._eval_step(
                        eval_params, state["model_state"], dynamic
                    )
                self._stepped["eval"] = True
            else:
                out = self._eval_step(
                    eval_params, state["model_state"], dynamic
                )
            # forward replaces batch (module.py:73)
            attrs.batch = _strip_marker(_merge_batch(out, static))
            attrs.step_metrics = None
            attrs.sync_gradients = None

        # Post-forward pipeline: Loss/Optimizer/Scheduler log host-side.
        Dispatcher.launch(self, attrs)

    def reset(self, attrs: Attributes | None = None) -> None:
        # NOTE: the host step mirror is NOT reset — accumulation windows are
        # step-aligned and may span epoch boundaries, exactly like the
        # device-side counter they mirror.
        super().reset(attrs)

    def destroy(self, attrs: Attributes | None = None) -> None:
        if self._prepared is not None and self._runtime is not None:
            self._runtime.models.remove(self._model)  # fixes dataset.py:129-142 class of bug
        self._prepared = None
        super().destroy(attrs)

    def __repr__(self) -> str:
        head = f"Module({type(self._model).__name__})"
        if not self._capsules:
            return head
        lines = [head + "("]
        for capsule in self._capsules:
            body = repr(capsule)
            lines.append("\n".join("    " + l for l in body.splitlines()) + ",")
        lines.append(")")
        return "\n".join(lines)
