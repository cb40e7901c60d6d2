"""Meter / Metric — gathered evaluation metrics.

Reference semantics (``rocket/core/meter.py``):

* ``Meter`` gathers selected batch keys across replicas with dataloader-padding
  dedup (``gather_for_metrics``, ``meter.py:29-30``), writes the gathered
  values back into a type-preserving clone of the batch (``meter.py:36-90``)
  and dispatches its children — the ``Metric`` capsules — on the gathered
  batch (``meter.py:95``);
* ``Metric`` is the abstract user-subclassed accumulator: implement ``launch``
  (accumulate) and ``reset`` (finalize/clear at epoch end) (``meter.py:98-111``).

TPU substrate: under GSPMD a batch array is already one *global* logical array
sharded over the mesh, so the cross-device gather is a ``jax.device_get`` on
the addressable case and a ``process_allgather`` across hosts. Padding dedup
uses ``attrs.batch_info.size`` — the real global sample count the DataLoader
records when it wrap-pads the last batch (``data/loader.py``).

Deliberate fix: errors inside metric children propagate — the reference's bare
``except:`` masked them as "keys not found" (``meter.py:91-93``).
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence

import jax
import numpy as np

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.capsule import Capsule
from rocket_tpu.core.dispatcher import Dispatcher

__all__ = ["Meter", "Metric"]


class Meter(Dispatcher):
    def __init__(
        self,
        keys: Sequence[str],
        capsules: Iterable[Capsule] = (),
        gather_on: str = "all",
        statefull: bool = False,
        priority: int = 1000,
        runtime=None,
    ) -> None:
        """``gather_on``: where host-path metrics run in MULTIHOST runs.
        "all" (default, reference ``gather_for_metrics`` semantics): every
        host keeps the gathered global batch and dispatches its metric
        children — O(global batch) host RAM retained per host. "main":
        every host still participates in the collective (it must), but
        non-main hosts drop the arrays immediately and skip host-path
        children — only the main process retains the global batch and
        accumulates metrics (read results there). ``Metric.device_reduce``
        children are unaffected (they never gather to host) and remain the
        recommended path at scale."""
        super().__init__(capsules, statefull=statefull, priority=priority, runtime=runtime)
        if gather_on not in ("all", "main"):
            raise ValueError(f"Meter: gather_on must be 'all'|'main', got {gather_on!r}")
        self._keys = tuple(keys)
        self._gather_on = gather_on
        self._reduce_fns: dict = {}  # id(metric) -> jitted device_reduce

    def gather_for_metrics(self, value, real_size: Optional[int]):
        """All-replica gather with padding trim (``gather_for_metrics``)."""
        if isinstance(value, jax.Array):
            if value.is_fully_addressable:
                host = np.asarray(jax.device_get(value))
            else:
                from jax.experimental import multihost_utils

                # tiled=True: the value is already a GLOBAL array sharded
                # over processes — assemble it along its existing leading
                # axis (untiled would try to stack a new process dim and
                # rejects non-fully-addressable inputs).
                host = np.asarray(
                    multihost_utils.process_allgather(value, tiled=True)
                )
        else:
            host = np.asarray(value)
        if real_size is not None and host.ndim >= 1 and host.shape[0] > real_size:
            host = host[:real_size]
        return host

    def launch(self, attrs: Attributes | None = None) -> None:
        if attrs is None or attrs.batch is None:
            return
        batch = attrs.batch
        if isinstance(batch, dict) and (
            "_device_gather" in batch or "_device_slice" in batch
        ):
            # A fused-gather marker reached the Meter un-materialized (no
            # Module replaced the batch — e.g. a train-mode Meter over raw
            # labels): gather the real rows eagerly so key access works.
            from rocket_tpu.data.device_cache import materialize_marker

            batch = attrs.batch = materialize_marker(batch)
        missing = [k for k in self._keys if not self._has_key(batch, k)]
        if missing:
            raise KeyError(
                f"Meter: keys {missing} not found in batch "
                f"(available: {self._available(batch)})"
            )
        real_size = None
        if attrs.batch_info is not None:
            real_size = attrs.batch_info.size

        # Device-reducing metrics: compiled reduction on the (still sharded)
        # device batch of this Meter's keys; only tiny LAZY scalars reach the
        # metric — no full-tensor gather and no per-batch D2H sync (the
        # metric materializes once per epoch in reset()). Host numpy batches
        # take the same path — jit accepts numpy inputs.
        # Shared per-batch operands for ALL device-reducing children,
        # built lazily on the first one. Fast path: the host size scalar
        # uploads during the jit dispatch itself (no extra device_put
        # call). Strict
        # mode's loop guard forbids that implicit upload, so it pays for
        # ONE explicit put per batch, replicated so jit needs no
        # follow-up reshard.
        subset = size_arr = None
        host_kids = []
        for child in self._capsules:
            if (
                isinstance(child, Metric)
                and type(child).device_reduce is not Metric.device_reduce
            ):
                fn = self._reduce_fns.get(id(child))
                if fn is None:
                    fn = self._reduce_fns[id(child)] = jax.jit(
                        child.device_reduce
                    )
                if subset is None:
                    subset = {k: batch[k] for k in self._keys}
                    size = (
                        len(batch[self._keys[0]])
                        if real_size is None else real_size
                    )
                    size_arr = np.int32(size)
                    if (
                        self._runtime is not None
                        and self._runtime.strict.enabled
                    ):
                        size_arr = jax.device_put(
                            size_arr,
                            self._runtime.replicated
                            if jax.device_count() > 1 else None,
                        )
                child.consume(fn(subset, size_arr))
            else:
                host_kids.append(child)
        if not host_kids:
            return

        main_only = (
            self._gather_on == "main"
            and self._runtime is not None
            and self._runtime.process_count > 1
        )
        if main_only and not self._runtime.is_main_process:
            # Participate in the collectives (they're collective), but drop
            # the global arrays immediately and skip host-path children —
            # only the main process retains O(global batch) and accumulates.
            for key in self._keys:
                self.gather_for_metrics(batch[key], real_size)
            return

        gathered = {
            key: self.gather_for_metrics(batch[key], real_size)
            for key in self._keys
        }

        # Host-path children see the gathered batch in a type-preserving
        # clone of the original — Mapping keys or Sequence indices, mutable
        # clones mutated in place, immutables rebuilt (meter.py:36-90) — and
        # the device batch is restored after.
        original = attrs.batch
        attrs.batch = self._clone_with(batch, gathered)
        try:
            for child in host_kids:  # already priority-sorted
                child.launch(attrs)
        finally:
            attrs.batch = original

    @staticmethod
    def _clone_with(batch, gathered: dict):
        """Clone ``batch`` with ``gathered`` values swapped in at their keys
        (dict keys or sequence indices), preserving the container type."""
        import copy
        from collections.abc import Mapping, Sequence as SeqABC

        if isinstance(batch, Mapping):
            # Rebuild from items rather than copy.copy: a Mapping wrapper
            # without __copy__ shares its backing dict, and the key swap
            # below would mutate the ORIGINAL device batch through it.
            items = {k: gathered.get(k, v) for k, v in batch.items()}
            try:
                return type(batch)(items)
            except TypeError:
                originals = {k: batch[k] for k in gathered}
                clone = copy.copy(batch)
                for key, value in gathered.items():
                    clone[key] = value
                if any(batch[k] is gathered[k] for k in gathered):
                    # copy.copy shared the backing storage and the swap wrote
                    # through to the original device batch — undo the writes
                    # and degrade to a plain-dict clone (container type not
                    # preserved, but the training batch stays intact).
                    for k, v in originals.items():
                        batch[k] = v
                    return items
                return clone
        if isinstance(batch, SeqABC) and not isinstance(batch, (str, bytes)):
            elems = list(batch)
            for key, value in gathered.items():
                elems[key] = value
            try:
                return type(batch)(elems)  # tuple-likes take one iterable
            except TypeError:
                return type(batch)(*elems)  # namedtuples take positionals
        # Scalar/opaque batch with a single gathered value: hand it through.
        return gathered

    @staticmethod
    def _has_key(batch, key) -> bool:
        from collections.abc import Mapping, Sequence as SeqABC

        if isinstance(batch, Mapping):
            return key in batch
        if isinstance(batch, SeqABC) and not isinstance(batch, (str, bytes)):
            return isinstance(key, int) and -len(batch) <= key < len(batch)
        try:
            return key in batch
        except TypeError:
            return False

    @staticmethod
    def _available(batch):
        try:
            return sorted(batch.keys())
        except AttributeError:
            return type(batch).__name__


class Metric(Capsule):
    """Abstract accumulator: override ``launch`` and ``reset``
    (``meter.py:98-111``).

    Optionally override :meth:`device_reduce` + :meth:`consume` — then the
    Meter compiles the reduction and pulls only its (tiny) result to host
    instead of device-getting the full gathered tensors every batch (on TPU
    the logits D2H was ~2x eval step time). ``reset`` still finalizes.
    """

    def launch(self, attrs: Attributes | None = None) -> None:
        raise NotImplementedError(
            f"{type(self).__name__}: implement launch(attrs) to accumulate."
        )

    def reset(self, attrs: Attributes | None = None) -> None:
        raise NotImplementedError(
            f"{type(self).__name__}: implement reset(attrs) to finalize/clear."
        )

    #: Sentinel checked by Meter: subclasses overriding device_reduce get the
    #: compiled on-device path; others get the gathered host batch.
    def device_reduce(self, batch, real_size):
        """Pure fn (jit-compiled once): mapping of the Meter's keys to
        (device or host) arrays + real-size scalar -> SMALL pytree of device
        scalars."""
        return None

    def consume(self, reduced) -> None:
        """Accumulate a device_reduce result. ``reduced`` leaves are LAZY
        device scalars — accumulate them lazily (jnp adds) and materialize
        once in ``reset``; a per-batch device_get here would put a D2H sync
        on the eval hot path."""
        raise NotImplementedError

    def publish(self, attrs: Attributes | None, tag: str, value) -> None:
        """Route a finalized scalar to the tracker buffers and the live loop
        state (the reference example's reset shape, examples/mnist.py:20-39).

        With health monitoring on (``Runtime(health=True)``), a finalized
        HOST scalar that comes out non-finite is counted as a health
        signal — an eval metric going NaN is divergence the train-step
        sentinels cannot see. Device scalars are left alone (checking
        them here would put a sync on the eval path; they surface at the
        tracker's flush instead)."""
        if attrs is not None:
            if attrs.tracker is not None:
                attrs.tracker.scalars[tag] = value
            if attrs.looper is not None:
                attrs.looper.state[tag] = value
        health = getattr(self._runtime, "health", None)
        if (
            health is not None
            and health.enabled
            and isinstance(value, (int, float, np.floating))
            and not np.isfinite(value)
        ):
            health.note_nonfinite_metric(tag)
