"""Dataset capsule — produce-if-absent batch source for a Looper phase.

Reference semantics (``rocket/core/dataset.py``):

* wraps any dataset in a loader with rocket collate forced (``dataset.py:30``),
  registered with the runtime exactly once via identity-dedup
  (``dataset.py:40-61``);
* ``set()`` handles mid-epoch resume fast-forward when training
  (``dataset.py:68-73``), exposes the batch total for Looper inference
  (``dataset.py:75``) and makes the iterator (``dataset.py:77``);
* ``launch()`` fills ``attrs.batch`` only when it is ``None``
  (``dataset.py:98-99``); on exhaustion sets ``attrs.looper.terminate``
  (``dataset.py:104-109``); otherwise places the batch on the mesh when
  ``device_placement`` is on (``dataset.py:111-118``), clears terminate and
  advances ``batch_idx`` (``dataset.py:120-124``); stateful ``batch_idx``
  (``dataset.py:145-153``).

Deliberate fixes: ``destroy`` actually unregisters the loader (the reference
nulls the handle before searching, ``dataset.py:129-142``), and ``batch_idx``
returns to zero at epoch end.

TPU substrate: H2D transfer is ``Runtime.shard_batch`` — one *globally sharded*
array over the mesh data axis rather than a per-rank ``.to(device)``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.capsule import Capsule
from rocket_tpu.data.device_cache import DeviceCachedLoader
from rocket_tpu.data.loader import Batch, DataLoader

__all__ = ["Dataset"]


class Dataset(Capsule):
    def __init__(
        self,
        dataset: Any,
        batch_size: int = 1,
        shuffle: bool = False,
        drop_last: bool = False,
        collate_fn: Optional[Callable] = None,
        device_placement: Optional[bool] = None,
        device_cache: str | bool = "auto",
        cache_dtype=None,
        fuse_gather: bool = True,
        num_workers: int = 0,
        worker_start_method: Optional[str] = None,
        prefetch: int = 2,
        statefull: bool = True,
        priority: int = 1000,
        runtime=None,
    ) -> None:
        super().__init__(statefull=statefull, priority=priority, runtime=runtime)
        self._raw_dataset = dataset
        # num_workers: multiprocess batch loading on the STREAMING path
        # (torch DataLoader(num_workers=N) parity, reference
        # dataset.py:52-57); the device-resident cache path has no per-step
        # host work and ignores it. worker_start_method: None (default) ->
        # forkserver/spawn (pickles the dataset into each worker once,
        # never os.fork()s the multithreaded JAX parent); "fork" stays
        # selectable for unpicklable datasets — copy-on-write inheritance,
        # accepting the documented deadlock risk (rocketlint RKT107).
        self._loader_kwargs = dict(
            batch_size=batch_size,
            shuffle=shuffle,
            drop_last=drop_last,
            collate_fn=collate_fn,
            num_workers=int(num_workers),
            worker_start_method=worker_start_method,
        )
        self._device_placement = device_placement
        # Streaming-path lookahead: collate + H2D run on a worker thread,
        # `prefetch` batches deep (0 disables). The device-resident cache
        # path doesn't need it (no per-step H2D at all).
        self._prefetch = int(prefetch)
        # Device-resident cache: "auto" caches map-style datasets that fit
        # the runtime's HBM budget, eliminating per-step H2D traffic (the
        # dominant cost on TPU for small datasets — see data/device_cache.py).
        self._device_cache = device_cache
        # cache_dtype (e.g. "bfloat16"): store float leaves of the device
        # cache at the compute precision — halves cache HBM + per-step
        # gather traffic when the model computes in bf16 anyway. Normalized
        # here so jnp.bfloat16 / "bfloat16" / jnp.dtype("bfloat16") all
        # produce ONE cache-store and registry key.
        if cache_dtype is not None:
            import jax.numpy as jnp

            cache_dtype = jnp.dtype(cache_dtype)
        self._cache_dtype = cache_dtype
        # Fused gather (cached path): attrs.batch is a gather MARKER that
        # the Module materializes inside its compiled step — one device
        # dispatch per step instead of two. Set False if a non-Module
        # capsule consumes attrs.batch directly.
        self._fuse_gather = bool(fuse_gather)
        self._device_resident = False
        self._dataloader: Optional[DataLoader] = None
        self._iterator = None
        self._total: Optional[int] = None
        self._batch_idx = 0

    # -- events ------------------------------------------------------------

    def setup(self, attrs: Attributes | None = None) -> None:
        super().setup(attrs)
        runtime = self._runtime
        # Prepare-once dedup (dataset.py:40-61): one loader per (raw dataset,
        # loader settings). The same raw dataset may back several capsules
        # with different settings (train shuffled / eval sequential) — those
        # get separate loaders but share one device-resident cache.
        self._registry_key = (
            self._loader_kwargs["batch_size"],
            self._loader_kwargs["shuffle"],
            self._loader_kwargs["drop_last"],
            id(self._loader_kwargs["collate_fn"]),
            self._loader_kwargs["num_workers"],
            self._loader_kwargs["worker_start_method"],
            self._fuse_gather,
            str(self._cache_dtype),
        )
        prepared = runtime.dataloaders.lookup(self._raw_dataset, self._registry_key)
        if prepared is None:
            prepared = self._make_loader(runtime)
            runtime.dataloaders.add(self._raw_dataset, prepared, self._registry_key)
        # Holder count: a shared loader is closed only by its LAST capsule.
        # Guarded so a repeated setup without an intervening destroy (e.g. a
        # tree re-dispatched SETUP) can't inflate the count and keep the
        # worker pool alive past the last destroy (round-4 advisor).
        if self._dataloader is None:
            runtime.dataloaders.retain(self._raw_dataset, self._registry_key)
        self._dataloader = prepared
        self._device_resident = isinstance(prepared, DeviceCachedLoader)
        if self._device_placement is None:
            self._device_placement = runtime.device_placement

    def _make_loader(self, runtime):
        # The device cache replicates the dataset per host; with multiple
        # processes the striped streaming loader is used instead (for now).
        if runtime.process_count > 1:
            self._device_cache = False
        if self._device_cache in ("auto", True):
            # One device-resident copy per (raw dataset, cache dtype),
            # shared by every loader over it (train shuffled + eval
            # sequential upload once).
            store = runtime.device_cache_store
            store_key = (id(self._raw_dataset), str(self._cache_dtype))
            data = store.get(store_key)
            if data is None:
                data = self._materialize(runtime)
            if data is not None:
                from rocket_tpu.data.device_cache import pytree_nbytes

                fits = pytree_nbytes(data) <= runtime.device_cache_bytes
                if self._device_cache is True or fits:
                    loader = DeviceCachedLoader(
                        data,
                        batch_size=self._loader_kwargs["batch_size"],
                        runtime=runtime,
                        shuffle=self._loader_kwargs["shuffle"],
                        drop_last=self._loader_kwargs["drop_last"],
                        seed=runtime.seed,
                        fused=self._fuse_gather,
                        cache_dtype=self._cache_dtype,
                    )
                    store[store_key] = loader.cache
                    return loader
        if self._cache_dtype is not None:
            # The streaming loader feeds raw host batches — the cast only
            # exists on the device-cache path. Say so rather than silently
            # changing input precision between single- and multi-host runs.
            runtime.get_logger("dataset").warning(
                "Dataset(cache_dtype=%s) has no effect on the streaming "
                "loader path (multi-process run or device_cache disabled); "
                "inputs stay at their source dtype.",
                self._cache_dtype,
            )
        return DataLoader(
            self._raw_dataset,
            seed=runtime.seed,
            process_index=runtime.process_index,
            process_count=runtime.process_count,
            telemetry=runtime.telemetry,
            **self._loader_kwargs,
        )

    def _materialize(self, runtime):
        """Whole dataset as one collated host pytree, or None if not
        map-style / not array-leaved (then the streaming loader is used)."""
        import numpy as np

        ds = self._raw_dataset
        if not (hasattr(ds, "__len__") and hasattr(ds, "__getitem__")):
            return None
        n = len(ds)
        if n == 0:
            return None
        try:
            if hasattr(ds, "get_batch"):
                data = ds.get_batch(np.arange(n))
            else:
                from rocket_tpu.data.collate import default_collate

                collate = self._loader_kwargs["collate_fn"] or default_collate
                data = collate([ds[i] for i in range(n)])
        except Exception:
            return None
        # Only pure-array pytrees can live on device.
        for leaf in __import__("jax").tree.leaves(data):
            if not isinstance(leaf, np.ndarray) or leaf.shape[:1] != (n,):
                return None
        return data

    def set(self, attrs: Attributes | None = None) -> None:
        super().set(attrs)
        epoch = 0
        if attrs is not None and attrs.launcher is not None:
            epoch = attrs.launcher.epoch_idx or 0
        self._dataloader.set_epoch(epoch)
        # Mid-epoch resume: fast-forward when training (dataset.py:68-73).
        if self._batch_idx > 0 and (attrs is None or attrs.mode == "train"):
            self._dataloader.skip(self._batch_idx)
        self._total = self._dataloader.total
        self._close_iterator()
        iterator = iter(self._dataloader)
        if self._prefetch > 0 and not self._device_resident:
            from rocket_tpu.data.prefetch import PrefetchIterator

            # Worker stays HOST-side (read + collate); the H2D transfer
            # happens on the consumer thread under the dispatch throttle
            # below — device_puts issued from a worker would interleave
            # with the queued steps; one thread owns the device queue.
            iterator = PrefetchIterator(
                iterator, depth=self._prefetch,
                telemetry=self._runtime.telemetry,
            )
        self._iterator = iterator

    def launch(self, attrs: Attributes | None = None) -> None:
        if attrs is None:
            return
        if attrs.batch is not None:
            return  # produce-if-absent (dataset.py:98-99)
        # Telemetry: the time the loop blocks on the input pipeline (queue
        # get / host read+collate) and the explicit H2D placement are the
        # run's "data_wait" — the spans are host timers around calls the
        # step path makes anyway.
        telemetry = self._runtime.telemetry
        try:
            with telemetry.span("data/next", cat="data_wait"):
                batch: Batch = next(self._iterator)
        except StopIteration:
            if attrs.looper is not None:
                attrs.looper.terminate = True  # dataset.py:104-109
            return

        data = batch.data
        # Fault injection (rocket_tpu.resilience): a scheduled poison fault
        # NaN-fills THIS batch before placement, so the health sentinels'
        # anomaly policy is exercised through the real data path.
        faults = getattr(self._runtime, "faults", None)
        if faults is not None:
            data = faults.poison_hook(data)
        if self._device_placement and not self._device_resident:
            with telemetry.span("data/h2d", cat="data_wait"):
                data = self._runtime.shard_batch(data)  # dataset.py:111-118
        attrs.batch = data
        attrs.batch_info = Attributes(size=batch.size, index=batch.index)
        if attrs.looper is not None:
            attrs.looper.terminate = False
        self._batch_idx += 1

    def reset(self, attrs: Attributes | None = None) -> None:
        super().reset(attrs)
        self._close_iterator()
        self._batch_idx = 0

    def destroy(self, attrs: Attributes | None = None) -> None:
        # Unregister before nulling the handle (fixes dataset.py:129-142).
        # The loader may be shared by another capsule still mid-epoch
        # (identity-deduped registry): only the LAST holder closes it and
        # its worker pool (round-3 advisor finding).
        if self._dataloader is not None:
            last = True
            if self._runtime is not None:
                last = self._runtime.dataloaders.release(
                    self._raw_dataset, self._registry_key
                )
            if last and hasattr(self._dataloader, "close"):
                self._dataloader.close()  # stop worker processes promptly
        self._dataloader = None
        self._close_iterator()
        super().destroy(attrs)

    def _close_iterator(self) -> None:
        it, self._iterator = self._iterator, None
        if it is not None and hasattr(it, "close"):
            it.close()  # stop the prefetch worker promptly

    # -- Looper inference --------------------------------------------------

    @property
    def total(self) -> Optional[int]:
        """Batches this phase will iterate (``_total``, ``dataset.py:75``) —
        net of any mid-epoch fast-forward."""
        if self._dataloader is None:
            return None
        total = self._dataloader.total
        if total is None:
            return None
        return total

    # -- checkpoint state --------------------------------------------------

    def state_dict(self) -> dict:
        return {"batch_idx": self._batch_idx}

    def load_state_dict(self, state: dict) -> None:
        self._batch_idx = int(state["batch_idx"])
