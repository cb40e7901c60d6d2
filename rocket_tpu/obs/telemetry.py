"""Telemetry — the one per-runtime owner of spans, goodput, metrics, watchdog.

The Runtime constructs exactly one :class:`Telemetry`
(``Runtime(telemetry=True)`` or ``ROCKET_TPU_TELEMETRY=1``) and every
instrumented layer reaches it through ``runtime.telemetry``:

* ``Capsule.dispatch`` wraps each event dispatch in a span (the 5-event
  protocol makes that one choke point for the whole tree);
* the Looper wraps iteration waves in ``step``/``compile`` spans plus a
  ``jax.profiler.StepTraceAnnotation`` and beats the watchdog;
* Dataset/PrefetchIterator account data waits, Checkpointer accounts
  saves, the Tracker accounts flushes and snapshots the registry.

Spans go through the one primitive of :mod:`rocket_tpu.obs.spans`: an
enabled Telemetry records them in its own ``SpanRecorder`` (installed as
the process's sink from ``start()`` to ``close()``) and adds goodput and
the watchdog's beat; a disabled one hands ``span()`` to the bare
primitive, which is on only while a profiler session is open. Enabled,
all bookkeeping is host-side arithmetic; the files (``telemetry.json`` +
``spans.trace.json``) are written once, at DESTROY, by
``Runtime.end_training``.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Optional

from rocket_tpu.obs import spans as span_lib
from rocket_tpu.obs.goodput import CATEGORIES, Goodput
from rocket_tpu.obs.registry import MetricsRegistry
from rocket_tpu.obs.spans import SpanRecorder
from rocket_tpu.obs.watchdog import Watchdog

__all__ = ["Telemetry"]

_GOODPUT_CATEGORIES = frozenset(cat for cat in CATEGORIES if cat != "other")


def _json_safe(obj):
    """Replace non-finite floats with their string names so
    telemetry.json stays RFC-valid JSON (a health gauge legitimately
    holds NaN after an anomaly; ``json.dump``'s default would emit a
    bare ``NaN`` token that jq / JSON.parse reject). The flight
    recorder's blackbox.json deliberately keeps raw NaN — it is read
    back by our own Python CLI only."""
    import math

    if isinstance(obj, float) and not math.isfinite(obj):
        return "NaN" if math.isnan(obj) else ("Infinity" if obj > 0
                                              else "-Infinity")
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_json_safe(v) for v in obj]
    return obj


class Telemetry:
    """Owns the span recorder, goodput accountant, metrics registry and
    (optionally) the hang watchdog for one run."""

    TELEMETRY_FILE = "telemetry.json"
    SPANS_FILE = "spans.trace.json"

    def __init__(
        self,
        enabled: bool = False,
        out_dir: Optional[str] = None,
        watchdog_secs: Optional[float] = None,
        max_span_events: int = 200_000,
        logger=None,
    ) -> None:
        self.enabled = bool(enabled)
        self.out_dir = out_dir  # explicit > tracker-suggested > runtime default
        self._suggested_dir: Optional[str] = None
        self._logger = logger
        self.spans = SpanRecorder(max_events=max_span_events)
        self.goodput = Goodput()
        self.registry = MetricsRegistry()
        #: Process identity (rank/hostname/pid) stamped into shard
        #: records, stall-dump headers and black-box manifests. Env-based
        #: here (JAX_PROCESS_ID, pre-backend); the Runtime refreshes the
        #: rank from jax.process_index() once initialized.
        from rocket_tpu.obs.export import host_identity

        self.identity = host_identity()
        #: Live-export plane (rocket_tpu.obs.export), attached via
        #: :meth:`start_export`; None keeps the run post-hoc only.
        self.exporter = None
        #: Runtime-wired (rocket_tpu.obs.flight / .health): the flight
        #: recorder and health monitor for this run, when health sentinels
        #: are enabled. None otherwise — every use below is guarded.
        self.flight = None
        self.health = None
        #: Serve-wired (rocket_tpu.obs.reqtrace): the per-request
        #: timeline tracer a ServeEngine attaches, drained by the
        #: exporter each window (finished timelines + tail exemplars
        #: into the shard dir). None outside serving — guarded
        #: everywhere.
        self.reqtrace = None
        #: Runtime-wired (rocket_tpu.resilience): when a supervisor owns
        #: this process, watchdog ESCALATION (a genuinely wedged step, not
        #: one slow wave) exits with this code after the forensic dump so
        #: the supervisor restarts the worker instead of watching it hang.
        #: None (default) keeps escalation diagnostic-only.
        self.escalation_exit_code: Optional[int] = None
        self.watchdog: Optional[Watchdog] = None
        if self.enabled and watchdog_secs is not None:
            self.watchdog = Watchdog(
                watchdog_secs,
                on_stall=self._on_stall,
                on_escalate=self._on_stall_escalation,
                spans=self.spans,
                registry=self.registry,
                logger=logger,
            )
        self._t0 = time.perf_counter()
        self._stall_reports: list[str] = []
        self._closed = False

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        """Begin the run clock, make this run's recorder the span sink
        (every span of the process is on until :meth:`close`), count the
        events of the process-wide compile listener (which the first
        ``Runtime`` or ``ServeEngine`` registers) and start the watchdog
        thread. No-op when disabled."""
        if not self.enabled:
            return
        self._t0 = time.perf_counter()
        self.spans.t0 = self._t0
        self.spans.on_compile = self._count_compile
        span_lib.install(self.spans)
        if self.watchdog is not None:
            self.watchdog.identity = self.identity
            self.watchdog.start()

    def start_export(self, config, default_dir: Optional[str] = None) -> None:
        """Attach + start the live-export plane (streaming shards, the
        ``/metrics`` endpoint, continuous SLO evaluation) per the
        :class:`~rocket_tpu.obs.export.ExportConfig`. No-op when the
        config is inactive or telemetry is disabled; idempotent."""
        if not self.enabled or self.exporter is not None:
            return
        if not getattr(config, "active", False):
            return
        from rocket_tpu.obs.export import TelemetryExporter

        self.exporter = TelemetryExporter(
            self, config,
            identity=self.identity,
            default_dir=default_dir,
            logger=self._logger,
        )
        self.exporter.start()

    def _count_compile(self, duration: float) -> None:
        self.registry.counter("compile/events").inc()
        self.registry.histogram("compile/secs", base=1e-3).observe(duration)

    # -- spans -------------------------------------------------------------

    def span(self, name: str, cat: Optional[str] = None, **ids):
        """One span through :func:`rocket_tpu.obs.spans.span`. Enabled:
        recorded in this run's recorder, goodput-categorized when ``cat``
        names a phase. Disabled: the bare primitive (on only under a
        profiler session, else the shared ``OFF``)."""
        if not self.enabled:
            return span_lib.span(name, **ids)
        return span_lib.Span(
            name, ids, self.spans, cat,
            self.goodput if cat in _GOODPUT_CATEGORIES else None,
        )

    def step_span(self, tag: str, step_num: int, cat: str = "step"):
        """One Looper iteration wave: the span ``<tag>/wave`` and, inside
        it, the ``StepTraceAnnotation`` that gives a device trace the
        step boundaries."""
        wave = self.span(f"{tag}/wave", cat=cat, step=step_num)
        if wave is span_lib.OFF:
            return wave
        import jax

        stack = contextlib.ExitStack()
        stack.enter_context(wave)
        stack.enter_context(
            jax.profiler.StepTraceAnnotation(tag, step_num=step_num)
        )
        return stack

    # -- heartbeat ---------------------------------------------------------

    def watchdog_arm(self) -> None:
        if self.watchdog is not None:
            self.watchdog.arm()

    def watchdog_disarm(self) -> None:
        if self.watchdog is not None:
            self.watchdog.disarm()

    def beat(self) -> None:
        if self.watchdog is not None:
            self.watchdog.beat()

    def _on_stall(self, report: str) -> None:
        # Keep a bounded tail for telemetry.json + the stall dump file.
        self._stall_reports.append(report)
        del self._stall_reports[:-5]

    def _on_stall_escalation(self, report: str) -> None:
        """Watchdog escalation: several consecutive deadline windows with
        no completed wave. A recoverable slow step never gets here — dump
        the flight recorder so a genuinely wedged run leaves its black
        box even if it is later SIGKILLed."""
        if self.flight is not None:
            self.flight.dump("watchdog_stall", extra={"report": report})
        if self.escalation_exit_code is not None:
            # The wedged main thread cannot be unwound from this watchdog
            # thread (it is blocked inside a C call); with the black box
            # written (main-process-gated, just above), the only honest
            # recovery is a restartable exit — os._exit skips every
            # finally on purpose, a wedged process cannot run teardown.
            if self._logger is not None:
                self._logger.error(
                    "watchdog escalation under supervision: exiting with "
                    "code %d so the supervisor restarts this worker",
                    self.escalation_exit_code,
                )
            os._exit(self.escalation_exit_code)

    def exception_dump(self, exc: BaseException, **context) -> None:
        """Forensic bundle for an exception escaping the step loop
        (``Looper.launch``). HealthAnomalyError already dumped inside the
        anomaly policy — dumping again here would burn a second bundle on
        the same event."""
        if self.flight is None:
            return
        from rocket_tpu.obs.health import HealthAnomalyError

        if isinstance(exc, HealthAnomalyError):
            return
        import traceback

        self.flight.dump(
            f"exception_{type(exc).__name__}",
            extra={
                "exception": repr(exc),
                "traceback": traceback.format_exc(limit=40),
                **context,
            },
        )

    # -- snapshots ---------------------------------------------------------

    def suggest_out_dir(self, path: str) -> None:
        """Tracker-informed default (``runs/<project>``); an explicit
        ``out_dir`` always wins, first suggestion sticks."""
        if self._suggested_dir is None:
            self._suggested_dir = path

    def scalars_snapshot(self) -> dict[str, float]:
        """Flat registry view for tracker backends (``obs/*``), with the
        HBM watermarks and goodput fractions refreshed. Host-only."""
        if not self.enabled:
            return {}
        self.registry.record_device_memory()
        report = self.goodput.report(time.perf_counter() - self._t0)
        for cat, fraction in report["fractions"].items():
            self.registry.gauge(f"goodput/{cat}_fraction").set(fraction)
        # Span drops surface as a first-class metric: a truncated trace
        # must never be mistaken for a complete one.
        self.registry.gauge("obs/spans_dropped").set(self.spans.dropped)
        return self.registry.scalars()

    def live_snapshot(self) -> dict:
        """Registry snapshot with the goodput fractions re-published as
        gauges first — what the /metrics endpoint and the shard exporter
        serve. Unlike :meth:`scalars_snapshot` it skips the device-memory
        refresh: a scrape storm must stay pure host arithmetic."""
        if self.enabled:
            report = self.goodput.report(time.perf_counter() - self._t0)
            for cat, fraction in report["fractions"].items():
                self.registry.gauge(f"goodput/{cat}_fraction").set(fraction)
            self.registry.gauge("goodput/goodput_fraction").set(
                report["goodput_fraction"]
            )
            self.registry.gauge("obs/spans_dropped").set(self.spans.dropped)
        return self.registry.snapshot()

    def summary(self) -> dict:
        """The telemetry.json payload."""
        total = time.perf_counter() - self._t0
        self.registry.record_device_memory()
        self.registry.gauge("obs/spans_dropped").set(self.spans.dropped)
        summary = {
            "version": 1,
            "goodput": self.goodput.report(total),
            "metrics": self.registry.snapshot(),
            "spans": {
                "file": self.SPANS_FILE,
                "events": len(self.spans),
                "dropped": self.spans.dropped,
            },
            "watchdog": {
                "enabled": self.watchdog is not None,
                "deadline_s": (
                    self.watchdog.deadline_s if self.watchdog else None
                ),
                "stalls": self.watchdog.stall_count if self.watchdog else 0,
            },
        }
        if self.health is not None and self.health.enabled:
            summary["health"] = self.health.summary()
        if self.flight is not None:
            summary["blackbox"] = {"bundles": list(self.flight.dumped)}
        return summary

    # -- flush / close -----------------------------------------------------

    def resolve_out_dir(self, default_dir: Optional[str] = None) -> str:
        return self.out_dir or self._suggested_dir or default_dir or os.path.join(
            "runs", "telemetry"
        )

    def flush(self, default_dir: Optional[str] = None) -> Optional[str]:
        """Write ``telemetry.json`` + the span file; returns the directory
        (None when disabled)."""
        if not self.enabled:
            return None
        out_dir = self.resolve_out_dir(default_dir)
        os.makedirs(out_dir, exist_ok=True)
        self.spans.write(os.path.join(out_dir, self.SPANS_FILE))
        payload = self.summary()
        if self._stall_reports:
            stall_path = os.path.join(out_dir, "watchdog_stalls.txt")
            with open(stall_path, "w", encoding="utf-8") as f:
                f.write("\n\n".join(self._stall_reports) + "\n")
            payload["watchdog"]["report_file"] = "watchdog_stalls.txt"
        tmp = os.path.join(out_dir, self.TELEMETRY_FILE + ".tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(_json_safe(payload), f, indent=1, sort_keys=True,
                      allow_nan=False)
            f.write("\n")
        os.replace(tmp, os.path.join(out_dir, self.TELEMETRY_FILE))
        if self._logger is not None:
            self._logger.info(
                "telemetry: wrote %s", os.path.join(out_dir, self.TELEMETRY_FILE)
            )
        return out_dir

    def close(self, default_dir: Optional[str] = None,
              write: bool = True) -> None:
        """Final flush + teardown (idempotent); ``write=False`` on
        non-main processes skips the files but still stops the threads."""
        if self._closed:
            return
        self._closed = True
        if self.exporter is not None:
            # Final shard record + endpoint teardown BEFORE the summary
            # flush: the last snapshot a scraper/shard reader sees is
            # the one telemetry.json freezes.
            self.exporter.stop()
        if self.enabled and self.spans.dropped and self._logger is not None:
            # One loud line at teardown: the span file is a TRUNCATED view.
            self._logger.warning(
                "telemetry: %d span(s) dropped (buffer bound "
                "max_span_events=%d) — the trace file is incomplete",
                self.spans.dropped, self.spans.max_events,
            )
        if self.enabled and write:
            self.flush(default_dir)
        if self.watchdog is not None:
            self.watchdog.stop()
        span_lib.uninstall(self.spans)
        self.spans.on_compile = None
