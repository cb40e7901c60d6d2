"""Checkpointer capsule — periodic save, resume, selective capsule restore.

Reference semantics (``rocket/core/checkpoint.py``):

* priority 100 — runs near-last in the iteration wave (``checkpoint.py:16``);
* ``setup()`` resumes from ``resume_from``; ``resume_capsules=False`` restores
  only model/optimizer state, skipping the capsule stack
  (``checkpoint.py:30-46``);
* ``launch()`` saves every ``save_every`` iterations into
  ``output_dir/<iter_idx>/`` (``checkpoint.py:57-73``);
* stateful ``iter_idx`` (``checkpoint.py:76-82``).

Deliberate fix: the reference early-returns on non-main processes so its
barrier is rank-0-only and non-main ranks never save (``checkpoint.py:53-63``)
— a deadlock in real multiprocess runs. Here every process runs the save path
(the writer is main-process-gated inside, the barrier is global).

Layout per step (analogue of the reference's verified layout, SURVEY §3.3):
``<output_dir>/<iter_idx>/model_{k}/`` (one sharded TrainState directory per
prepared model — params, optimizer moments, model state, PRNG base key, step;
``shard_p{process}.npz`` per host + ``index.json``), ``capsules.pkl`` (the
stateful-capsule stack states, in setup order) and ``rng.json`` (runtime key
counter).

Saves are **non-blocking**: the device→host pull is synchronous (donated
buffers stay safe), the file writes overlap training on a background thread
(``checkpoint_io.AsyncWriter``); ``destroy`` drains the queue.

Trust boundary: model state is pickle-free (npz + json); ``capsules.pkl`` IS
pickle and must only be resumed from checkpoints you wrote — it carries
host-side Python capsule state, the analogue of accelerate's
``custom_checkpoint_{N}.pkl``.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import numpy as np

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.capsule import PRIORITY_CHECKPOINT, Capsule
from rocket_tpu.runtime import checkpoint_io

__all__ = ["Checkpointer"]


class Checkpointer(Capsule):
    def __init__(
        self,
        output_dir: str = "checkpoints",
        save_every: int = 1000,
        resume_from: Optional[str] = None,
        resume_capsules: bool = True,
        keep_last: Optional[int] = None,
        overwrite: bool = True,
        statefull: bool = True,
        priority: int = PRIORITY_CHECKPOINT,
        runtime=None,
    ) -> None:
        super().__init__(statefull=statefull, priority=priority, runtime=runtime)
        self._output_dir = output_dir
        self._save_every = save_every
        self._resume_from = resume_from
        self._resume_capsules = resume_capsules
        self._keep_last = keep_last
        self._overwrite = overwrite
        self._iter_idx = 0
        self._saved_steps: list[int] = []
        self._writer = checkpoint_io.AsyncWriter()

    # -- events ------------------------------------------------------------

    def setup(self, attrs: Attributes | None = None) -> None:
        super().setup(attrs)
        registry = getattr(self._runtime, "checkpointers", None)
        if registry is not None and self not in registry:
            # Runtime-wide registry: the drain path reaches this
            # Checkpointer even from a Looper whose subtree has none.
            registry.append(self)
        flight = getattr(self._runtime, "flight", None)
        if flight is not None:
            # Register as the black-box bundle's emergency writer: on a
            # forensic dump (anomaly halt / loop exception / watchdog
            # escalation) the flight recorder calls save_emergency().
            flight.attach_checkpointer(self)
        if self._resume_from:
            path = self._resolve_resume_path(self._resume_from)
            if path is not None:
                self._load(path)

    def _resolve_resume_path(self, path: str) -> Optional[str]:
        """``resume_from="latest"`` picks the newest COMPLETE step under
        output_dir — the restart-after-preemption idiom (no step number to
        thread through the relauncher). Returns None (fresh start, logged)
        when no checkpoint exists yet, so a relauncher can always pass the
        flag; an explicit path still raises if missing."""
        if path != "latest":
            return path
        # The scan itself is owned by the jax-free resilience module (the
        # supervisor's progress probe and this resume path must agree on
        # "newest restorable step" or they silently diverge); only the
        # per-skip warnings stay local.
        from rocket_tpu.resilience.supervisor import newest_complete_step

        step = newest_complete_step(self._output_dir)
        chosen = -1 if step is None else step
        if os.path.isdir(self._output_dir):
            for skipped in sorted(
                (int(d) for d in os.listdir(self._output_dir) if d.isdigit()),
                reverse=True,
            ):
                if skipped <= chosen:
                    break
                self.log_warning(
                    "skipping incomplete checkpoint "
                    f"{os.path.join(self._output_dir, str(skipped))}"
                )

        # Multi-host: every process must restore the SAME step — a stale
        # filesystem view (NFS attribute cache after a fast restart) could
        # otherwise pick different steps per host and silently diverge.
        # The main process's choice is broadcast to everyone.
        import jax

        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            chosen = int(
                multihost_utils.broadcast_one_to_all(np.int64(chosen))
            )

        if chosen < 0:
            self.log_info(
                f"resume_from='latest': no complete checkpoint under "
                f"{self._output_dir!r} — starting fresh."
            )
            return None
        return os.path.join(self._output_dir, str(chosen))

    @staticmethod
    def _is_complete(candidate: str) -> bool:
        """A checkpoint is complete when the main process's LAST artifact
        (rng.json) exists AND every shard file referenced by each model's
        chunk index is on disk — a torn async write (preemption mid-save)
        fails both per-host holes. The check itself lives in the jax-free
        ``resilience.supervisor`` module so the supervisor parent process
        shares ONE definition of "restorable" with the resume path."""
        from rocket_tpu.resilience.supervisor import is_complete_checkpoint

        return is_complete_checkpoint(candidate)

    def launch(self, attrs: Attributes | None = None) -> None:
        self._iter_idx += 1
        if self._iter_idx % self._save_every != 0:
            return
        self.save()

    # -- save --------------------------------------------------------------

    def save(self, step: Optional[int] = None) -> str:
        """Write one checkpoint directory; returns its path.

        ALL processes run the whole path (fixes the reference's rank-0-only
        barrier, ``checkpoint.py:53-63``): each host snapshots and writes only
        the array chunks it owns — nothing is gathered. The snapshot
        (device→host pull) is synchronous; the file writes run on a
        background thread, drained by the next save / :meth:`destroy`.
        """
        runtime = self._runtime
        step = self._iter_idx if step is None else step
        path = os.path.join(self._output_dir, str(step))
        if not self._overwrite and os.path.exists(path):
            # Reference parity (``checkpoint.py:66-69``): refuse to clobber
            # an existing step directory when overwrite=False.
            raise RuntimeError(
                f"Checkpointer: overwrite is set to False. {path}"
            )

        with runtime.telemetry.span(f"checkpoint/save[{step}]",
                                    cat="checkpoint"):
            return self._save_sync(runtime, step, path)

    def _save_sync(self, runtime, step: int, path: str) -> str:
        # Backpressure: at most one write in flight, and the previous step's
        # files are complete before this one starts (keep_last can prune
        # safely below).
        self._writer.wait()
        # Record this step BEFORE snapshotting capsule states so the
        # checkpoint's own entry survives a resume and gets pruned later.
        self._saved_steps.append(step)

        runtime.wait_for_everyone()
        plans = [
            checkpoint_io.snapshot(prepared.state)
            for prepared in runtime.models.values()
        ]
        capsule_states = None
        rng_state = None
        if runtime.is_main_process:
            capsule_states = [obj.state_dict() for obj in runtime.checkpoint_stack]
            rng_state = runtime.rng_state_dict()

        # Pruning happens INSIDE the write job, after this step is fully on
        # disk — pruning eagerly would leave a window with zero restorable
        # checkpoints if the process dies mid-write.
        prune = []
        if self._keep_last is not None:
            while len(self._saved_steps) > self._keep_last:
                old = self._saved_steps.pop(0)
                if runtime.is_main_process:
                    prune.append(os.path.join(self._output_dir, str(old)))

        def write():
            for k, plan in enumerate(plans):
                checkpoint_io.write_snapshot(os.path.join(path, f"model_{k}"), plan)
            if capsule_states is not None:
                import pickle

                checkpoint_io.atomic_write(
                    os.path.join(path, "capsules.pkl"), pickle.dumps(capsule_states)
                )
                checkpoint_io.atomic_write(
                    os.path.join(path, "rng.json"),
                    json.dumps(rng_state).encode("utf-8"),
                )
            import shutil

            for old_path in prune:
                shutil.rmtree(old_path, ignore_errors=True)

        self._writer.submit(write)
        self.log_info(f"saving checkpoint at {path} (async)")
        return path

    def destroy(self, attrs: Attributes | None = None) -> None:
        """Drain the async writer, then the usual teardown; the trailing
        barrier guarantees every host's shards exist before anyone resumes."""
        if self._runtime is not None:
            registry = getattr(self._runtime, "checkpointers", None)
            if registry is not None and self in registry:
                registry.remove(self)
            flight = getattr(self._runtime, "flight", None)
            if flight is not None:
                flight.detach_checkpointer(self)
            with self._runtime.telemetry.span("checkpoint/drain",
                                              cat="checkpoint"):
                self._writer.wait()
                self._runtime.wait_for_everyone()
        else:
            self._writer.wait()
        super().destroy(attrs)

    # -- emergency (black-box) save ----------------------------------------

    def save_emergency(self, path: str, include_capsules: bool = False) -> str:
        """Synchronous, collective-free state dump into a black-box bundle
        (called by the flight recorder mid-failure, possibly from a
        watchdog thread while other hosts are wedged).

        Deliberately NOT :meth:`save`: no barrier (other processes may be
        hung — that is why we are dumping), no async writer (the process
        may be about to die), no step-directory rotation. Each model's
        state is snapshotted (explicit D2H of the addressable shards) and
        written inline. Single-host bundles are directly resumable via
        ``resume_from=<bundle>/checkpoint``; multi-host bundles carry this
        process's chunks plus the index — every process calling this into
        the same directory (the cooperative drain path) yields a complete,
        resharding-readable checkpoint. Under a gated anomaly action the
        state is the last-good (finite) one, since the anomalous update
        was skipped.

        ``include_capsules=True`` (the drain path, where host state is
        consistent — we are between waves, not mid-crash) also writes
        ``capsules.pkl`` so epoch/batch indices resume exactly; crash
        dumps keep the default False.
        """
        runtime = self._runtime
        for k, prepared in enumerate(runtime.models.values()):
            plan = checkpoint_io.snapshot(prepared.state)
            checkpoint_io.write_snapshot(os.path.join(path, f"model_{k}"), plan)
        if runtime.is_main_process:
            if include_capsules:
                import pickle

                checkpoint_io.atomic_write(
                    os.path.join(path, "capsules.pkl"),
                    pickle.dumps(
                        [obj.state_dict() for obj in runtime.checkpoint_stack]
                    ),
                )
            # rng.json last: its presence is the completeness marker.
            checkpoint_io.atomic_write(
                os.path.join(path, "rng.json"),
                json.dumps(runtime.rng_state_dict()).encode("utf-8"),
            )
        return path

    # -- drain (cooperative preemption) save -------------------------------

    def save_drain(self) -> str:
        """Preemption-drain checkpoint: synchronous, barrier-free, written
        into the regular numbered step layout so a restarted run's
        ``resume_from="latest"`` finds it with no extra plumbing.

        Called by the Looper at a wave boundary after a drain request
        (SIGTERM). Every process writes its own shards concurrently; the
        supervisor waits for all workers to exit before restarting, so
        the checkpoint is complete by resume time. If the cooperating
        processes happened to drain at different wave indices (signal
        skew), the torn directories fail ``_is_complete`` and resume
        falls back to the last periodic checkpoint — never a corrupt
        restore. A step already covered by a complete periodic save is
        not rewritten — but the ``drain.json`` marker is written either
        way (the drain boundary can coincide with a periodic save step,
        and the marker is the record that a drain happened there)."""
        import time

        step = self._iter_idx
        path = os.path.join(self._output_dir, str(step))
        # Don't interleave with an in-flight periodic save's file writes.
        self._writer.wait()
        # Record the step BEFORE snapshotting capsule states (the
        # _save_sync idiom): the pickled saved_steps must include this
        # drain checkpoint, or a resumed run's keep_last rotation never
        # learns about it and the directory leaks forever.
        if step not in self._saved_steps:
            self._saved_steps.append(step)
        if not self._is_complete(path):
            self.save_emergency(path, include_capsules=True)
            self.log_info(f"drain checkpoint written at {path}")
        if self._runtime.is_main_process:
            checkpoint_io.atomic_write(
                os.path.join(path, "drain.json"),
                json.dumps(
                    {"reason": "drain", "step": step, "unix": time.time()}
                ).encode("utf-8"),
            )
        return path

    # -- restore -----------------------------------------------------------

    def _load(self, path: str) -> None:
        runtime = self._runtime
        if not os.path.isdir(path):
            raise RuntimeError(f"Checkpointer: resume_from {path!r} does not exist.")

        with runtime.telemetry.span("checkpoint/load", cat="checkpoint"):
            self._load_inner(runtime, path)

    def _load_inner(self, runtime, path: str) -> None:
        for k, prepared in enumerate(runtime.models.values()):
            model_path = os.path.join(path, f"model_{k}")
            if os.path.isdir(model_path):
                prepared.state = checkpoint_io.load_pytree(
                    model_path, template=prepared.state
                )
                # Host-side step mirror (PreparedModule.host_step): read from
                # the index, NOT the device — no host sync on the restored
                # state. load_pytree above
                # already validated the "step" leaf exists.
                prepared.host_step = int(
                    np.asarray(checkpoint_io.load_leaf(model_path, "step"))
                )
            elif os.path.exists(model_path + ".pkl"):
                raise RuntimeError(
                    f"Checkpointer: {model_path}.pkl is a pre-0.2 pickle "
                    "checkpoint; the sharded npz layout cannot read it. "
                    "Re-save with the current version."
                )
            else:
                # Resuming without model state is almost never intended.
                self.log_warning(
                    f"checkpoint {path} has no model_{k} — model state NOT "
                    "restored."
                )

        rng_path = os.path.join(path, "rng.json")
        if os.path.exists(rng_path):
            with open(rng_path, "r", encoding="utf-8") as f:
                runtime.load_rng_state_dict(json.load(f))

        if self._resume_capsules:
            capsule_path = os.path.join(path, "capsules.pkl")
            if os.path.exists(capsule_path):
                import pickle

                with open(capsule_path, "rb") as f:
                    capsule_states = pickle.load(f)
                stack = runtime.checkpoint_stack
                if len(capsule_states) != len(stack):
                    # Selective restore tolerates tree changes, mirroring the
                    # reference's swallowed count-mismatch (checkpoint.py:38-46)
                    # but loudly.
                    self.log_warning(
                        f"capsule count mismatch: checkpoint has "
                        f"{len(capsule_states)}, tree has {len(stack)}; "
                        "restoring the common prefix."
                    )
                for obj, state in zip(stack, capsule_states):
                    obj.load_state_dict(state)
        self.log_info(f"resumed from {path}")

    # -- checkpoint state --------------------------------------------------

    def state_dict(self) -> dict:
        return {"iter_idx": self._iter_idx, "saved_steps": list(self._saved_steps)}

    def load_state_dict(self, state: dict) -> None:
        self._iter_idx = int(state["iter_idx"])
        # Restore the rotation list so keep_last keeps pruning checkpoints
        # written before the resume.
        self._saved_steps = [int(s) for s in state.get("saved_steps", [])]
