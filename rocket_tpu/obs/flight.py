"""Flight recorder — a black box for training runs, with forensic dumps.

A failed multi-hour run that leaves nothing behind must be rerun just to
watch it die. The flight recorder keeps a bounded host-side ring buffer
of the last N steps' sentinel snapshots (decoded health words plus their
step context: phase tag, epoch, batch index — from which the step's RNG
key derives deterministically), and on demand writes a **forensic
bundle** under ``<telemetry dir>/blackbox/``:

* ``blackbox.json`` — reason, anomaly timeline, the full sentinel-history
  ring, last-good step, metrics-registry snapshot, the tail of the span
  stream (what the host was doing right before), RNG state and process
  topology;
* ``checkpoint/`` — an emergency synchronous checkpoint of every
  prepared model's state via the Checkpointer (present when a
  Checkpointer capsule is in the tree). Under gated anomaly actions the
  state is the last-good (finite) one, so the bundle is directly
  resumable on a single host.

Dumps fire on an anomaly under ``anomaly_action="dump_and_halt"``
(:mod:`rocket_tpu.obs.health`), on an uncaught exception escaping the
Looper's iteration loop (``core/loop.py``), and on hang-watchdog stall
escalation (``obs/watchdog.py``). Only the main process writes; the
number of bundles per run is bounded so a dump storm cannot fill the
disk. Render a bundle with ``python -m rocket_tpu.obs blackbox <dir>``.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Optional

__all__ = ["FlightRecorder"]

#: Bundle manifest filename.
BLACKBOX_FILE = "blackbox.json"


def _jsonable(value):
    """Best-effort JSON coercion — a forensic dump must never die on an
    unserializable context value."""
    try:
        json.dumps(value)
        return value
    except (TypeError, ValueError):
        return repr(value)


class FlightRecorder:
    """Bounded sentinel-history ring + forensic bundle writer.

    Parameters
    ----------
    max_steps:
        Ring capacity — the last N decoded sentinel records kept in host
        RAM (``Runtime(blackbox_steps=)``).
    telemetry:
        The run's :class:`~rocket_tpu.obs.telemetry.Telemetry` — supplies
        the output directory, the span tail and the registry snapshot.
    runtime:
        The owning Runtime — supplies process topology, RNG state and the
        main-process write gate.
    """

    def __init__(
        self,
        max_steps: int = 256,
        telemetry=None,
        runtime=None,
        logger=None,
        max_dumps: int = 8,
        spans_tail: int = 200,
    ) -> None:
        if max_steps < 1:
            raise ValueError(f"blackbox_steps must be >= 1, got {max_steps}")
        self.max_steps = int(max_steps)
        self._telemetry = telemetry
        self._runtime = runtime
        self._logger = logger
        self._max_dumps = int(max_dumps)
        self._spans_tail = int(spans_tail)
        self._ring: collections.deque = collections.deque(maxlen=self.max_steps)
        self._anomalies: list[dict] = []
        self._checkpointer = None
        self._lock = threading.Lock()
        #: Paths of bundles written this run (telemetry.json surfaces them).
        self.dumped: list[str] = []

    # -- wiring ------------------------------------------------------------

    def attach_checkpointer(self, checkpointer) -> None:
        """Called by the Checkpointer at setup; the first one wins (one
        emergency writer is enough, and trees rarely carry two). Under
        the lock: setup can race a watchdog-escalation dump reading the
        checkpointer (RKT109)."""
        with self._lock:
            if self._checkpointer is None:
                self._checkpointer = checkpointer

    def detach_checkpointer(self, checkpointer) -> None:
        with self._lock:
            if self._checkpointer is checkpointer:
                self._checkpointer = None

    # -- recording ---------------------------------------------------------

    def record(self, entry: dict) -> None:
        """Append one step's sentinel snapshot to the ring (fed by the
        HealthMonitor as lagged words decode)."""
        with self._lock:
            self._ring.append(entry)

    def note_anomaly(self, entry: dict) -> None:
        with self._lock:
            self._anomalies.append(entry)
            del self._anomalies[:-64]

    def anomalies(self) -> list[dict]:
        """Snapshot of the retained anomaly ring (newest last) — what a
        smoke/test asserts an SLO violation's forensics against without
        forcing a dump."""
        with self._lock:
            return list(self._anomalies)

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def last_good_step(self) -> Optional[int]:
        with self._lock:
            for entry in reversed(self._ring):
                if not entry.get("flag_names"):
                    return entry.get("step")
        return None

    # -- the dump ----------------------------------------------------------

    def _out_root(self) -> str:
        default = None
        if self._runtime is not None:
            default = os.path.join(
                getattr(self._runtime, "project_dir", "."), "runs", "telemetry"
            )
        if self._telemetry is not None:
            base = self._telemetry.resolve_out_dir(default)
        else:
            base = default or os.path.join("runs", "telemetry")
        return os.path.join(base, "blackbox")

    def dump(self, reason: str, extra: Optional[dict] = None) -> Optional[str]:
        """Write one forensic bundle; returns its directory, or None when
        this process is not the writer (non-main) or the per-run bundle
        budget is spent. Never raises — forensics must not mask the
        failure being recorded."""
        runtime = self._runtime
        if runtime is not None and not runtime.is_main_process:
            return None
        try:
            return self._dump_inner(reason, extra)
        except Exception as exc:  # noqa: BLE001 — never mask the real failure
            if self._logger is not None:
                self._logger.error("flight recorder: dump failed: %r", exc)
            return None

    def _dump_inner(self, reason: str, extra: Optional[dict]) -> Optional[str]:
        with self._lock:
            if len(self.dumped) >= self._max_dumps:
                if self._logger is not None:
                    self._logger.warning(
                        "flight recorder: bundle budget (%d) spent — "
                        "skipping dump %r", self._max_dumps, reason,
                    )
                return None
            steps = list(self._ring)
            anomalies = list(self._anomalies)

        safe_reason = "".join(
            c if c.isalnum() or c in "-_." else "_" for c in reason
        )[:80] or "dump"
        root = self._out_root()
        bundle = os.path.join(root, f"{safe_reason}")
        k = 1
        while os.path.exists(bundle):
            bundle = os.path.join(root, f"{safe_reason}.{k}")
            k += 1
        os.makedirs(bundle, exist_ok=True)

        manifest = {
            "version": 1,
            "reason": reason,
            "created_unix": time.time(),
            "last_good_step": self.last_good_step,
            "steps_recorded": len(steps),
            "sentinel_history": steps,
            "anomalies": anomalies,
            "extra": _jsonable(extra) if extra is not None else None,
        }
        telemetry = self._telemetry
        if self._runtime is not None:
            # Rank + hostname ride the manifest so multi-host forensics
            # can attribute the bundle without the launcher's context.
            from rocket_tpu.obs.export import host_identity

            identity = host_identity(self._runtime.process_index)
            manifest["process"] = {
                "index": self._runtime.process_index,
                "count": self._runtime.process_count,
                "rank": identity["rank"],
                "hostname": identity["hostname"],
                "pid": os.getpid(),
            }
            manifest["rng"] = self._runtime.rng_state_dict()
        if telemetry is not None:
            manifest["metrics"] = telemetry.registry.snapshot()
            events = telemetry.spans.events()[-self._spans_tail:]
            manifest["spans_tail"] = [
                {"name": ev.name, "cat": ev.cat,
                 "t": round(ev.start - telemetry.spans.t0, 6),
                 "dur": round(ev.end - ev.start, 6), "tid": ev.tid}
                for ev in events
            ]
            if telemetry.health is not None:
                manifest["health"] = telemetry.health.summary()

        ckpt = self._checkpointer
        if ckpt is not None:
            ckpt_dir = os.path.join(bundle, "checkpoint")
            try:
                ckpt.save_emergency(ckpt_dir)
                manifest["checkpoint"] = "checkpoint"
            except Exception as exc:  # noqa: BLE001 — bundle without it beats none
                manifest["checkpoint_error"] = repr(exc)
        else:
            manifest["checkpoint"] = None

        # json.dump(allow_nan=True) — sentinel records from a NaN anomaly
        # legitimately carry NaN floats; Python's loader round-trips them.
        tmp = os.path.join(bundle, BLACKBOX_FILE + ".tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(manifest, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, os.path.join(bundle, BLACKBOX_FILE))

        with self._lock:
            self.dumped.append(bundle)
        if self._logger is not None:
            self._logger.error(
                "flight recorder: wrote black-box bundle %s (reason: %s)",
                bundle, reason,
            )
        return bundle
