"""Background batch prefetch — keep the chip fed on streaming paths.

The reference gets multiprocess workers + prefetch for free from
``torch.utils.data.DataLoader`` (``rocket/core/dataset.py:52-57``). The
TPU-native analogue: a single daemon thread runs the HOST side of the loader
(read + collate), staying ``depth`` batches ahead of the training loop
through a bounded queue, so host data work overlaps step N-1's compute.

Keep ``transform`` host-only. Do NOT issue device work (``device_put`` /
``shard_batch``) from the worker: transfers issued from a second thread
interleave with the main thread's queued step dispatches — the consumer
thread does the H2D after dequeue (``core/dataset.py``), so one thread
owns the device queue.

The device-resident cache (``data/device_cache.py``) covers map-style
datasets that fit HBM; this covers everything else (streaming datasets,
multi-host striping, HBM-exceeding corpora).
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional

__all__ = ["PrefetchIterator"]


class PrefetchIterator:
    """Iterate ``iterable`` on a daemon thread, ``depth`` items ahead.

    ``transform`` runs on the worker thread — host-side work only (see
    module docstring). Exceptions in the worker surface at the consumer's
    ``next()``. ``close()`` stops the worker promptly (also called by
    ``__del__`` and on exhaustion).
    """

    _DONE = object()

    def __init__(
        self,
        iterable: Iterable[Any],
        depth: int = 2,
        transform: Optional[Callable[[Any], Any]] = None,
        telemetry=None,
    ) -> None:
        if depth < 1:
            raise ValueError(f"PrefetchIterator: depth must be >= 1, got {depth}")
        self._iterable = iterable
        self._transform = transform
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        # Optional rocket_tpu.obs.Telemetry: the worker's produce time
        # becomes spans on its own trace thread-line, and the queue depth
        # observed at each dequeue feeds the metrics registry — the two
        # numbers that separate "input-bound" from "chip-bound".
        self._telemetry = telemetry if (
            telemetry is not None and telemetry.enabled
        ) else None
        # Hoisted instrument handle: no registry lock/lookup per dequeue.
        self._depth_hist = (
            self._telemetry.registry.histogram("data/prefetch_depth", base=1.0)
            if self._telemetry is not None
            else None
        )
        self._thread = threading.Thread(
            target=self._fill, name="rocket-tpu-prefetch", daemon=True
        )
        self._thread.start()

    def _fill(self) -> None:
        try:
            telemetry = self._telemetry
            iterator = iter(self._iterable)
            while True:
                if telemetry is not None:
                    # Span covers the real produce work (read + collate +
                    # transform) on the worker's own trace thread-line.
                    with telemetry.span("data/prefetch_produce"):
                        item = self._produce(iterator)
                else:
                    item = self._produce(iterator)
                if item is self._DONE:
                    self._put(self._DONE)
                    return
                if not self._put(item):
                    return
        except BaseException as e:  # re-raised on the consumer side
            self._put(e)

    def _produce(self, iterator: Iterator[Any]) -> Any:
        try:
            item = next(iterator)
        except StopIteration:
            return self._DONE
        if self._transform is not None:
            item = self._transform(item)
        return item

    def _put(self, item: Any) -> bool:
        """Blocking put that aborts when close() was requested."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        if self._stop.is_set():
            raise StopIteration
        if self._depth_hist is not None:
            # Depth seen by the consumer at each dequeue: persistently 0
            # means the pipeline can't keep the chip fed.
            self._depth_hist.observe(self._queue.qsize())
        item = self._queue.get()
        if item is self._DONE:
            self.close()
            raise StopIteration
        if isinstance(item, BaseException):
            self.close()
            raise item
        return item

    def close(self) -> None:
        """Stop the worker and drop queued batches."""
        self._stop.set()
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:
            pass
