"""Runtime — the TPU-native replacement for the reference's ``Accelerator``.

The reference delegates device placement, DDP wrapping, collectives, gradient
accumulation bookkeeping, checkpoint object registration, process topology and
rank-aware logging to ``accelerate.Accelerator`` (surface inventoried in
SURVEY.md §2b). Here all of that is owned natively:

* device & distributed runtime = a ``jax.sharding.Mesh`` over the local (or
  multi-host) TPU devices; collectives are XLA-compiled over ICI/DCN — there
  is no NCCL-equivalent code, only sharding declarations;
* the "prepared object" registries (``Accelerator._models`` etc.,
  ``module.py:32``, ``optimizer.py:26``, ``dataset.py:42``) become a
  first-class public :class:`IdentityRegistry`;
* ``register_for_checkpointing`` / ``_custom_objects`` (``capsule.py:46``,
  ``checkpoint.py:34-43``) become an explicit checkpoint stack;
* PRNG state is managed centrally (the reference leans on torch's implicit
  global RNG saved as ``random_states_0.pkl``).
"""

from __future__ import annotations

import logging
import os
from pathlib import Path
from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["Runtime", "IdentityRegistry", "StrictMode"]


class StrictMode:
    """Opt-in runtime enforcement of the fast-path contracts that
    ``rocket_tpu.analysis`` checks statically (docs/analysis.md).

    Two teeth:

    * a **transfer guard**, in two layers. Globally (run-wide), implicit
      *device-to-host* transfers are set to ``transfer_guard`` (default
      ``"disallow"``): a stray ``float(device_scalar)`` raises at the
      offending line instead of silently stalling every step. Inside the
      Looper's per-iteration wave — the steady-state hot path — ALL
      implicit transfer directions are clamped (``Looper.launch``), so a
      numpy batch sneaking into jit per step raises too. Host-to-device
      is not guarded globally because init/setup legitimately create
      arrays (``jnp.ones`` is an implicit H2D). Explicit
      ``jax.device_put`` / ``jax.device_get`` — the framework's own
      transfer points — stay legal everywhere. CAVEAT: on CPU backends
      device memory IS host memory, so jax does not guard D2H reads
      there — the run-wide layer only bites on real accelerators; the
      loop-wave guard (H2D included) is what enforces on a CPU dev box;
    * a **retrace counter**: :meth:`note_retraces` reads a jitted step's
      compile-cache size and raises once it exceeds ``max_retraces`` —
      shape-unstable callers fail loudly instead of silently spending the
      run in XLA. The count is surfaced through the Tracker as a
      ``retraces`` scalar (see ``core/module.py``).

    Plus one audited fact carried along the same channel: the static
    SPMD auditor (``rocket_tpu.analysis.shard_audit``) can
    :meth:`note_collectives` its per-step collective-op count for a
    step label, and the Module publishes it as an
    ``audited_collectives`` tracker scalar next to ``retraces`` — the
    dashboard shows the declared communication cost alongside the
    live run it gates.

    Enable via ``Runtime(strict=True)`` or ``ROCKET_TPU_STRICT=1``.
    """

    _GUARD_KEY = "jax_transfer_guard_device_to_host"

    def __init__(self, transfer_guard: str = "disallow",
                 max_retraces: int = 8) -> None:
        self._transfer_guard = transfer_guard
        self.max_retraces = int(max_retraces)
        self._active = False
        self._prev_guard: Optional[str] = None
        #: label -> last observed compile count, for introspection/tests.
        self.retrace_counts: dict[str, int] = {}
        #: label -> audited per-step collective-op count (note_collectives).
        self.collective_counts: dict[str, int] = {}
        #: Optional Telemetry sink (runtime-wired): retrace / audited
        #: collective counts mirror into its metrics registry.
        self.telemetry = None

    @property
    def enabled(self) -> bool:
        return self._active

    @property
    def transfer_guard(self) -> str:
        """The configured guard level ("disallow", "log", ...) — read by
        the Looper's per-wave guard so both layers honor one knob."""
        return self._transfer_guard

    def activate(self) -> None:
        if self._active:
            return
        self._prev_guard = getattr(jax.config, self._GUARD_KEY, None)
        jax.config.update(self._GUARD_KEY, self._transfer_guard)
        self._active = True

    def deactivate(self) -> None:
        if not self._active:
            return
        jax.config.update(self._GUARD_KEY, self._prev_guard)
        self._active = False

    def note_retraces(self, label: str, jitted_fn) -> Optional[int]:
        """Record the compile count of ``jitted_fn`` under ``label``;
        raise once it exceeds the budget. No-op (returns None) when
        strict mode is off or the fn doesn't expose a compile cache."""
        if not self._active:
            return None
        cache_size = getattr(jitted_fn, "_cache_size", None)
        if not callable(cache_size):  # pragma: no cover - jax internals moved
            return None
        count = int(cache_size())
        self.retrace_counts[label] = count
        if self.telemetry is not None and self.telemetry.enabled:
            # Host-side gauge store — no device op on the step path.
            self.telemetry.registry.gauge(f"strict/retraces/{label}").set(count)
        if count > self.max_retraces:
            raise RuntimeError(
                f"StrictMode: '{label}' has compiled {count} times "
                f"(max_retraces={self.max_retraces}). Every new input "
                "shape/dtype recompiles the step — pad batches to a fixed "
                "shape (DataLoader wrap padding), pin dtypes, or raise "
                "Runtime(strict_max_retraces=...) if the shape set is "
                "genuinely finite."
            )
        return count

    def note_collectives(self, label: str, count: int) -> int:
        """Record a statically-audited per-step collective-op count for
        ``label`` (from ``rocket_tpu.analysis.shard_audit``; label
        convention ``train_step[<ModelClass>]`` matches the Module's
        retrace label). Recorded regardless of :attr:`enabled` — the
        audit runs pre-launch — but only surfaced to the Tracker on
        strict runs (``core/module.py``)."""
        count = int(count)
        self.collective_counts[label] = count
        if self.telemetry is not None and self.telemetry.enabled:
            self.telemetry.registry.gauge(
                f"strict/audited_collectives/{label}"
            ).set(count)
        return count


class IdentityRegistry:
    """Prepare-once registry keyed by object identity.

    Reproduces the reference's dedup scans over ``Accelerator._models /
    _optimizers / _schedulers / _dataloaders`` (``module.py:29-43``,
    ``dataset.py:40-53``): two capsules wrapping the same raw object share one
    prepared artifact, and preparing the same object twice is an error.
    """

    def __init__(self, kind: str) -> None:
        self._kind = kind
        self._entries: dict[int, tuple[Any, Any]] = {}  # id -> (raw, prepared)
        self._refs: dict[Any, int] = {}  # optional holder counts (retain/release)

    def lookup(self, raw: Any, extra_key: Any = None) -> Optional[Any]:
        entry = self._entries.get((id(raw), extra_key))
        return None if entry is None else entry[1]

    def add(self, raw: Any, prepared: Any, extra_key: Any = None) -> Any:
        key = (id(raw), extra_key)
        if key in self._entries:
            raise RuntimeError(
                f"Registry[{self._kind}]: object {type(raw).__name__} is "
                "already prepared; share the prepared handle instead."
            )
        self._entries[key] = (raw, prepared)
        return prepared

    def remove(self, raw: Any, extra_key: Any = None) -> None:
        key = (id(raw), extra_key)
        self._entries.pop(key, None)
        self._refs.pop(key, None)

    def retain(self, raw: Any, extra_key: Any = None) -> None:
        """Count a holder of an existing entry. Entries with holders are
        only truly released when the LAST holder calls :meth:`release` —
        two Dataset capsules sharing one prepared loader must not have its
        worker pool shut down when the first capsule is destroyed (round-3
        advisor finding)."""
        key = (id(raw), extra_key)
        self._refs[key] = self._refs.get(key, 0) + 1

    def release(self, raw: Any, extra_key: Any = None) -> bool:
        """Drop one holder; returns True when this was the last one (the
        entry is then removed and the caller owns teardown). Entries never
        retained release immediately."""
        key = (id(raw), extra_key)
        count = self._refs.get(key, 1) - 1
        if count > 0:
            self._refs[key] = count
            return False
        self._refs.pop(key, None)
        self._entries.pop(key, None)
        return True

    def __len__(self) -> int:
        return len(self._entries)

    def values(self):
        return [prepared for _, prepared in self._entries.values()]


#: Where the persistent XLA compilation cache lives when the environment
#: names no place: one fixed directory at the root of the checkout. The
#: path is part of the cache key, so it must never move between runs.
_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def _enable_compilation_cache() -> None:
    """Persistent XLA compilation cache for every run that builds a
    Runtime. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses
    that directory and nothing is set here; otherwise the cache goes to
    ``.jax_cache`` inside the checkout (git-ignored). A first compile of a
    full train step costs tens of seconds to minutes on the chip; a second
    process finds it on disk."""
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)


def _maybe_initialize_distributed() -> None:
    """Join a multi-host JAX runtime when coordinator env vars are present.

    Mirrors how ``accelerate launch`` wires ``torch.distributed`` from env
    vars; here the transport is the TPU runtime over ICI/DCN.
    """
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not (coord and os.environ.get("JAX_NUM_PROCESSES")):
        return
    # Must not touch the backend before initialize() (jax.process_count()
    # would initialize it!) — probe the distributed client state directly.
    from jax._src import distributed as _distributed

    if getattr(_distributed.global_state, "client", None) is not None:
        return  # already initialized
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
        process_id=int(os.environ.get("JAX_PROCESS_ID", "0")),
    )


class Runtime:
    """Mesh-centric execution context shared by every capsule in a tree.

    Parameters
    ----------
    mesh:
        An existing ``jax.sharding.Mesh``. If None, one is built from
        ``mesh_shape`` over ``devices``.
    mesh_shape:
        Mapping axis name -> size, e.g. ``{"data": 8}`` or
        ``{"data": 4, "model": 2}``. Default: all devices on ``"data"``.
    devices:
        Devices to build the mesh from (default: ``jax.devices()``).
    seed:
        Root PRNG seed; all keys handed to capsules derive from it.
    gradient_accumulation_steps:
        Optimizer update every N micro-steps (reference
        ``Accelerator(gradient_accumulation_steps=N)``; the accumulation
        itself happens inside the jitted step, see ``core/module.py``).
    device_placement:
        When True, ``Dataset`` moves batches onto the mesh automatically
        (reference ``dataset.py:111-118``).
    strict:
        Opt into :class:`StrictMode` (transfer guard + retrace budget).
        None (default) reads ``ROCKET_TPU_STRICT`` from the environment;
        tune with ``strict_transfer_guard`` / ``strict_max_retraces``.
    telemetry:
        Opt into run-wide telemetry (``rocket_tpu.obs``): host span
        tracing, goodput accounting, the metrics registry and (with
        ``watchdog_secs``) the hang watchdog. None (default) reads
        ``ROCKET_TPU_TELEMETRY``; ``telemetry.json`` + the Perfetto span
        file are written at DESTROY into ``telemetry_dir`` (default:
        the Tracker's ``runs/<project>``, else
        ``<project_dir>/runs/telemetry``).
    watchdog_secs:
        Heartbeat deadline for the telemetry watchdog: when no Looper
        iteration completes within this many seconds, all thread stacks
        + the live span stack + live-array totals are dumped (run keeps
        going). None (default) reads ``ROCKET_TPU_WATCHDOG``. An explicit
        value implies ``telemetry=True`` when ``telemetry`` is left
        unset (the env var does not — it only arms the watchdog on runs
        that opted into telemetry).
    health:
        Opt into training-health sentinels (``rocket_tpu.obs.health``):
        a health word — per-branch non-finite flags for loss/grads/
        params, grad/param norms, update ratio, loss z-score vs an
        on-device EMA — computed INSIDE the compiled train step and
        fetched asynchronously ``health_fetch_lag`` steps behind, plus
        the flight recorder's black-box ring and forensic crash dumps.
        None (default) reads ``ROCKET_TPU_HEALTH`` (``1`` enables with
        the default action; ``warn``/``skip_step``/``dump_and_halt``
        enables with that action). An explicit ``health=True`` implies
        ``telemetry=True`` when ``telemetry`` is left unset.
    anomaly_action:
        What a detected anomaly (non-finite loss/grads/params) does:
        ``"warn"`` (log + count), ``"skip_step"`` (the compiled step
        gates the optimizer update with ``lax.cond`` so state stays
        finite; the skip is counted), or ``"dump_and_halt"`` (gate the
        update, write a ``runs/<project>/blackbox/`` forensic bundle and
        raise ``HealthAnomalyError``).
    blackbox_steps:
        Flight-recorder ring size — the last N steps' sentinel snapshots
        kept for the forensic bundle.
    health_fetch_lag:
        How many steps behind the health word is fetched; by then the
        producing step has retired, so the explicit device_get cannot
        stall the dispatch pipeline (sync-free under strict mode).
    export:
        Opt into live telemetry export (``rocket_tpu.obs.export``): a
        daemon thread appends periodic registry snapshots + the goodput
        report as bounded JSONL shards to
        ``<run dir>/telemetry/rank<k>.jsonl`` and evaluates SLO specs
        (``slo=``). None (default) reads ``ROCKET_TPU_EXPORT`` — truthy
        enables, a number enables AND sets the interval. An active
        export implies ``telemetry=True`` when ``telemetry`` is unset.
    export_interval_s:
        Seconds between exporter ticks (default 10).
    metrics_port:
        Mount a Prometheus ``/metrics`` endpoint (text exposition 0.0.4,
        stdlib http.server thread) on this port + the process rank
        (0 = ephemeral). None (default) reads ``ROCKET_TPU_METRICS_PORT``.
        Implies ``telemetry=True`` like ``export``.
    slo:
        SLO spec file path (``rocket_tpu.obs.slo`` grammar) or
        ``default:train`` / ``default:serve`` for the committed specs;
        violations surface as ``obs/slo/*`` gauges, a flight-recorder
        anomaly and a log line. None reads ``ROCKET_TPU_SLO``.
    """

    #: Name of the batch-sharded mesh axis group. Parallel schemes that shard
    #: the batch over more than one axis (dp+fsdp) extend this tuple.
    DATA_AXES: tuple[str, ...] = ("data",)

    #: Most recently constructed Runtime — the ambient-context analogue of
    #: accelerate's AcceleratorState singleton, used by layers that need the
    #: mesh at trace time (ring attention) without threading it explicitly.
    _current: Optional["Runtime"] = None

    @classmethod
    def current(cls) -> Optional["Runtime"]:
        return cls._current

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        mesh_shape: Optional[Mapping[str, int]] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        seed: int = 0,
        gradient_accumulation_steps: int = 1,
        device_placement: bool = True,
        device_cache_bytes: int = 1 << 30,
        project_dir: str = ".",
        seq_axis: Optional[str] = None,
        strict: Optional[bool] = None,
        strict_transfer_guard: str = "disallow",
        strict_max_retraces: int = 8,
        telemetry: Optional[bool] = None,
        telemetry_dir: Optional[str] = None,
        watchdog_secs: Optional[float] = None,
        health: Optional[bool] = None,
        anomaly_action: Optional[str] = None,
        blackbox_steps: int = 256,
        health_fetch_lag: int = 2,
        export: Optional[bool] = None,
        export_interval_s: Optional[float] = None,
        metrics_port: Optional[int] = None,
        slo: Optional[str] = None,
    ) -> None:
        _enable_compilation_cache()
        _maybe_initialize_distributed()

        if mesh is None:
            devices = list(devices if devices is not None else jax.devices())
            if mesh_shape is None:
                mesh_shape = {"data": len(devices)}
            axis_names = tuple(mesh_shape.keys())
            shape = tuple(mesh_shape.values())
            if int(np.prod(shape)) != len(devices):
                raise RuntimeError(
                    f"Runtime: mesh_shape {dict(mesh_shape)} needs "
                    f"{int(np.prod(shape))} devices, have {len(devices)}."
                )
            mesh = Mesh(np.asarray(devices).reshape(shape), axis_names)
        self._mesh = mesh

        # Sequence/context parallelism: when the mesh carries a sequence
        # axis, batches shard their second (token) dimension over it and
        # attention layers with impl="ring" rotate KV blocks around it.
        if seq_axis is None and "seq" in mesh.shape:
            seq_axis = "seq"
        if seq_axis is not None and seq_axis not in mesh.shape:
            raise RuntimeError(
                f"Runtime: seq_axis {seq_axis!r} not in mesh axes "
                f"{tuple(mesh.shape)}."
            )
        self.seq_axis = seq_axis
        Runtime._current = self

        if gradient_accumulation_steps < 1:
            raise RuntimeError("gradient_accumulation_steps must be >= 1")
        self.gradient_accumulation_steps = int(gradient_accumulation_steps)
        self.device_placement = bool(device_placement)
        # HBM budget for Dataset's "auto" device-resident cache.
        self.device_cache_bytes = int(device_cache_bytes)
        self.project_dir = project_dir

        # PRNG: a root key plus a split counter (both checkpointed).
        self._seed = int(seed)
        self._key_counter = 0

        # Prepared-object registries (reference private `_models` etc.).
        self.models = IdentityRegistry("models")
        self.optimizers = IdentityRegistry("optimizers")
        self.schedulers = IdentityRegistry("schedulers")
        self.dataloaders = IdentityRegistry("dataloaders")

        # Checkpoint stack (reference `_custom_objects`, capsule.py:40-46).
        self._checkpoint_stack: list[Any] = []

        # Device-resident dataset caches, keyed by (raw-dataset id,
        # cache dtype) — shared by all loaders over the same dataset at the
        # same precision (see data/device_cache.py).
        self.device_cache_store: dict = {}

        # Tracker backends keyed by name (reference `log_with`/`get_tracker`).
        self.trackers: dict[str, Any] = {}

        # Strict mode (transfer guard + retrace budget, see StrictMode).
        # Default: off; ROCKET_TPU_STRICT=1 opts a whole run in without
        # touching code, an explicit strict= argument wins over the env.
        if strict is None:
            strict = os.environ.get(
                "ROCKET_TPU_STRICT", ""
            ).strip().lower() in ("1", "true", "yes", "on")
        self.strict = StrictMode(
            transfer_guard=strict_transfer_guard,
            max_retraces=strict_max_retraces,
        )
        if strict:
            self.strict.activate()

        # Run-wide telemetry (rocket_tpu.obs): spans + goodput + metrics
        # registry + watchdog, owned here so the whole capsule tree reports
        # into ONE object and teardown has one flush point. Default: off;
        # ROCKET_TPU_TELEMETRY=1 opts a run in without touching code.
        from rocket_tpu.obs import Telemetry
        from rocket_tpu.obs.health import (
            ANOMALY_ACTIONS,
            HealthConfig,
            HealthMonitor,
        )
        from rocket_tpu.obs.spans import (
            install_compile_listener,
            install_gc_listener,
        )

        # Training-health sentinels + flight recorder. Default: off;
        # ROCKET_TPU_HEALTH opts a run in without touching code — "1"
        # enables the default action, an action name ("warn" |
        # "skip_step" | "dump_and_halt") enables AND selects it. An
        # explicit health= / anomaly_action= argument wins over the env.
        env_health = os.environ.get("ROCKET_TPU_HEALTH", "").strip().lower()
        if health is None:
            health = env_health in ("1", "true", "yes", "on") or (
                env_health in ANOMALY_ACTIONS
            )
        if anomaly_action is None:
            anomaly_action = (
                env_health if env_health in ANOMALY_ACTIONS else "warn"
            )

        # Live export plane (rocket_tpu.obs.export): streaming JSONL
        # shards + optional /metrics endpoint + SLO evaluation. Resolved
        # early because an active export implies telemetry below.
        from rocket_tpu.obs.export import ExportConfig, host_identity

        export_cfg = ExportConfig.from_env(
            enabled=export,
            interval_s=export_interval_s,
            metrics_port=metrics_port,
            slo_path=slo,
        )

        if telemetry is None:
            if watchdog_secs is not None or health or export_cfg.active:
                # An explicit watchdog_secs=, health=True or an active
                # export config is an explicit ask for hang protection /
                # health forensics / live metrics; all live inside
                # telemetry, so the ask implies the subsystem
                # rather than silently no-opping.
                telemetry = True
            else:
                telemetry = os.environ.get(
                    "ROCKET_TPU_TELEMETRY", ""
                ).strip().lower() in ("1", "true", "yes", "on")
        elif not telemetry and watchdog_secs is not None:
            self.get_logger("runtime").warning(
                "watchdog_secs=%s ignored: telemetry=False disables the "
                "whole obs subsystem, watchdog included.", watchdog_secs,
            )
        if watchdog_secs is None:
            raw = os.environ.get("ROCKET_TPU_WATCHDOG", "").strip()
            if raw:
                try:
                    watchdog_secs = float(raw)
                except ValueError:
                    self.get_logger("runtime").warning(
                        "ROCKET_TPU_WATCHDOG=%r is not a number — watchdog "
                        "disabled", raw,
                    )
        self.telemetry = Telemetry(
            enabled=telemetry,
            out_dir=telemetry_dir,
            watchdog_secs=watchdog_secs,
            logger=self.get_logger("obs"),
        )
        self.strict.telemetry = self.telemetry

        # Health monitor + flight recorder: the monitor always exists (an
        # inert object when disabled — capsules check `runtime.health
        # .enabled` with no getattr dance); the flight recorder only when
        # health is on (it is the black box the health policy dumps into).
        health_cfg = HealthConfig(
            enabled=bool(health),
            action=anomaly_action,
            fetch_lag=health_fetch_lag,
        )
        self.flight = None
        if health_cfg.enabled:
            from rocket_tpu.obs.flight import FlightRecorder

            self.flight = FlightRecorder(
                max_steps=blackbox_steps,
                telemetry=self.telemetry,
                runtime=self,
                logger=self.get_logger("obs"),
            )
        self.health = HealthMonitor(
            health_cfg,
            registry=self.telemetry.registry,
            flight=self.flight,
            logger=self.get_logger("obs"),
        )
        self.telemetry.flight = self.flight
        self.telemetry.health = self.health
        # Replace the env-guessed rank with the real one before start()
        # hands identity to the watchdog and the exporter stamps shards.
        self.telemetry.identity = host_identity(self.process_index)
        # Compile events become compile/* spans in every run, telemetry
        # or not; collections become */gc spans while spans are on
        # (process-wide, registered once).
        install_compile_listener()
        install_gc_listener()
        self.telemetry.start()
        self.telemetry.start_export(
            export_cfg,
            default_dir=os.path.join(project_dir, "runs", "telemetry"),
        )

        # Resilience plumbing (rocket_tpu.resilience): the drain flag every
        # Looper polls at wave boundaries, deterministic fault injection
        # from ROCKET_TPU_FAULTS, and — under a supervisor — the watchdog
        # escalation turned into a restartable EXIT_WEDGED instead of a
        # hang. The SIGTERM->drain handler installs only when a supervisor
        # is attached (ROCKET_TPU_SUPERVISED, set by
        # `python -m rocket_tpu.launch --supervise`) or the run opts in via
        # ROCKET_TPU_DRAIN=1 — library code must not grab signals from an
        # embedding application that didn't ask.
        from rocket_tpu.resilience.faults import (
            EXIT_WEDGED,
            DrainState,
            FaultInjector,
            env_truthy,
            install_signal_drain,
        )

        self.drain = DrainState()
        #: Live Checkpointers across ALL phases (setup registers, destroy
        #: unregisters): the drain path must find one even when the
        #: draining Looper's own subtree has none (e.g. SIGTERM during an
        #: eval phase while the train phase owns the Checkpointer).
        self.checkpointers: list = []
        self.faults = FaultInjector.from_env(
            process_index=self.process_index,
            logger=self.get_logger("resilience"),
        )
        if self.faults is not None:
            self.faults.install()
        self.supervised = env_truthy("ROCKET_TPU_SUPERVISED")
        if self.supervised:
            self.telemetry.escalation_exit_code = EXIT_WEDGED
        if self.supervised or env_truthy("ROCKET_TPU_DRAIN"):
            install_signal_drain(
                self.drain, logger=self.get_logger("resilience")
            )

        self._warned_replicated_batch = False

    # -- mesh & sharding ---------------------------------------------------

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def data_axis_size(self) -> int:
        return int(
            np.prod([self._mesh.shape[a] for a in self.DATA_AXES if a in self._mesh.shape])
        )

    def sharding(self, *spec) -> NamedSharding:
        """NamedSharding on this runtime's mesh for the given PartitionSpec."""
        return NamedSharding(self._mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self._mesh, P())

    @property
    def batch_sharding(self) -> NamedSharding:
        """Leading-axis sharding over the data axes — the layout of a global
        batch (the TPU analogue of DDP's per-rank split)."""
        axes = tuple(a for a in self.DATA_AXES if a in self._mesh.shape)
        return NamedSharding(self._mesh, P(axes if axes else None))

    def shard_batch(self, batch):
        """Place a host pytree onto the mesh, leading axis over 'data'.

        The TPU analogue of the reference's H2D ``default_move``
        (``dataset.py:116``) — but placement is a *sharding*, not a single
        device copy.
        """
        sharding = self.batch_sharding
        replicated = self.replicated

        n = self.data_axis_size
        seq_axis = self.seq_axis
        seq_n = self._mesh.shape[seq_axis] if seq_axis else 1
        procs = jax.process_count()

        def leaf_sharding(leaf):
            """Target sharding for one leaf, or None for passthrough."""
            if isinstance(leaf, (np.ndarray, jax.Array)) and np.ndim(leaf) >= 1:
                stripe_of = leaf.shape[0] * procs
                if stripe_of % n != 0:
                    if procs > 1:
                        # Host stripes differ — replicating would ship
                        # different values per process and hang/fail the next
                        # collective. The loader's wrap padding should have
                        # prevented this.
                        raise RuntimeError(
                            f"shard_batch: global batch {stripe_of} not "
                            f"divisible over data axis ({n}) in a "
                            f"{procs}-process run."
                        )
                    # Batch not divisible over the data axis (tiny datasets,
                    # trailing batches): replicate rather than fail — but say
                    # so once, because the step then runs at 1/n throughput.
                    if not self._warned_replicated_batch:
                        self._warned_replicated_batch = True
                        self.get_logger("runtime").warning(
                            "shard_batch: batch dim %d not divisible over the "
                            "%d-way data axis; replicating (slow path). Pad "
                            "or drop_last to keep batches even.",
                            leaf.shape[0], n,
                        )
                    return replicated
                if seq_axis and np.ndim(leaf) >= 2 and leaf.shape[1] % seq_n == 0:
                    # Token dim sharded over the sequence axis (ring
                    # attention / long-context path).
                    return NamedSharding(self._mesh, P(self.DATA_AXES, seq_axis))
                return sharding
            if isinstance(leaf, (np.ndarray, jax.Array, int, float, complex, bool)):
                return replicated
            return None  # strings etc. pass through (utils.py:19-27 semantics)

        flat, treedef = jax.tree.flatten(batch)
        out = list(flat)
        idx, leaves, targets = [], [], []
        for i, leaf in enumerate(flat):
            target = leaf_sharding(leaf)
            if target is None:
                continue
            idx.append(i)
            leaves.append(leaf if np.ndim(leaf) else jnp.asarray(leaf))
            targets.append(target)

        if procs == 1:
            if leaves:
                # ONE device_put for the whole batch: one transfer call
                # instead of one per leaf.
                placed = jax.device_put(leaves, targets)
                for i, value in zip(idx, placed):
                    out[i] = value
        else:
            # True multihost: each process holds only its DataLoader stripe.
            # device_put would treat the stripe as the (replicated) global
            # value and fail the cross-process consistency check — the stripe
            # is process-local data, assembled into one global array here.
            for i, leaf, target in zip(idx, leaves, targets):
                if target is replicated:
                    out[i] = jax.device_put(leaf, target)
                    continue
                global_shape = (leaf.shape[0] * procs,) + tuple(leaf.shape[1:])
                out[i] = jax.make_array_from_process_local_data(
                    target, np.asarray(leaf), global_shape
                )
        return jax.tree.unflatten(treedef, out)

    # -- process topology --------------------------------------------------

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def process_count(self) -> int:
        return jax.process_count()

    @property
    def is_main_process(self) -> bool:
        return jax.process_index() == 0

    @property
    def is_local_main_process(self) -> bool:
        # One JAX process per host: local main == this process.
        return True

    @property
    def device(self) -> jax.Device:
        """First local device — host-side convenience handle."""
        return jax.local_devices()[0]

    def wait_for_everyone(self) -> None:
        """Cross-host barrier (reference ``wait_for_everyone``,
        ``checkpoint.py:63`` — run on ALL ranks here, fixing the reference's
        rank-0-only deadlock)."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("rocket_tpu_barrier")

    # -- PRNG --------------------------------------------------------------

    @property
    def seed(self) -> int:
        return self._seed

    def next_key(self) -> jax.Array:
        """A fresh PRNG key; deterministic given (seed, number of prior calls)."""
        key = jax.random.fold_in(jax.random.key(self._seed), self._key_counter)
        self._key_counter += 1
        return key

    def host_key(self, *folds: int) -> jax.Array:
        """Deterministic key for host-side data ops (shuffling), independent
        of the consumption order of :meth:`next_key`."""
        key = jax.random.key(self._seed ^ 0x5EED)
        for fold in folds:
            key = jax.random.fold_in(key, fold)
        return key

    def rng_state_dict(self) -> dict:
        return {"seed": self._seed, "key_counter": self._key_counter}

    def load_rng_state_dict(self, state: dict) -> None:
        self._seed = int(state["seed"])
        self._key_counter = int(state["key_counter"])

    # -- checkpoint stack --------------------------------------------------

    @property
    def checkpoint_stack(self) -> Sequence[Any]:
        return tuple(self._checkpoint_stack)

    def register_for_checkpointing(self, obj: Any) -> None:
        for existing in self._checkpoint_stack:
            if existing is obj:
                raise RuntimeError(
                    f"Runtime: {type(obj).__name__} registered for "
                    "checkpointing twice."
                )
        self._checkpoint_stack.append(obj)

    def unregister_from_checkpointing(self, obj: Any) -> None:
        """Pop the stack, verifying LIFO identity (capsule.py:56-64)."""
        if not self._checkpoint_stack:
            raise RuntimeError(
                f"Runtime: checkpoint stack empty while unregistering "
                f"{type(obj).__name__}."
            )
        top = self._checkpoint_stack.pop()
        if top is not obj:
            raise RuntimeError(
                f"Runtime: checkpoint stack corrupted — expected "
                f"{type(obj).__name__}, found {type(top).__name__}. "
                "Destroy order must unwind setup order."
            )

    # -- logging -----------------------------------------------------------

    def get_logger(self, name: str) -> logging.Logger:
        """Rank-aware logger: INFO+ on the main process, ERROR+ elsewhere
        (reference ``accelerate.logging.get_logger``, ``capsule.py:33``)."""
        logger = logging.getLogger(f"rocket_tpu.{name}")
        if not self.is_main_process:
            logger.setLevel(logging.ERROR)
        return logger

    # -- trackers ----------------------------------------------------------

    def get_tracker(self, name: str):
        return self.trackers.get(name)

    def init_tracker(self, name: str, tracker: Any) -> Any:
        self.trackers[name] = tracker
        return tracker

    # -- teardown ----------------------------------------------------------

    def end_training(self) -> None:
        """Flush/close trackers (reference ``end_training``, ``launcher.py:55``)
        and release strict mode's process-global transfer guard — without
        this, a later non-strict Runtime in the same process would inherit
        the 'disallow' guard and raise on its own (legitimate) implicit
        transfers.

        Backend closes are exception-isolated: one backend's failing
        ``close()`` (a dead wandb socket) must not leak the others' file
        handles or skip the guard release — that leak is exactly the
        JsonlBackend/SummaryWriter handle bug this teardown owns. The
        telemetry flush runs LAST so the span file records the closes."""
        logger = self.get_logger("runtime")
        for name, tracker in list(self.trackers.items()):
            close = getattr(tracker, "close", None)
            if close is None:
                continue
            try:
                close()
            except Exception as exc:  # noqa: BLE001 — isolate per backend
                logger.warning(
                    "tracker backend %r failed to close: %r", name, exc
                )
        self.trackers.clear()
        self.strict.deactivate()
        # Health words still inside their fetch lag are decoded now so a
        # last-steps anomaly is counted (and dumped) before the telemetry
        # record freezes; teardown never raises on one — the run is over.
        try:
            self.health.drain(raise_on_anomaly=False)
        except Exception as exc:  # noqa: BLE001 — teardown must complete
            logger.warning("health drain failed at teardown: %r", exc)
        self.telemetry.close(
            default_dir=os.path.join(self.project_dir, "runs", "telemetry"),
            write=self.is_main_process,
        )
