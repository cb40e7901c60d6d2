"""Spans — the one span primitive of the program, on the profiler's clock.

:func:`span` is placed at the layer boundaries of both hot paths (the serve
tick and its phases, the train wave and its phases). A span that is ON

* opens a ``jax.profiler.TraceAnnotation(name, **ids)``: it lies in the
  profiler's own trace beside the device's events, on ONE clock, with its
  identifiers (``tick=``, ``rid=``, ``seq=``, ``step=``) as stats;
* is recorded as a :class:`SpanEvent` in the current sink, a
  :class:`SpanRecorder`, with the innermost open span of its thread as
  ``parent`` and, among its ``ids``, ``cpu_s``: the CPU seconds its thread
  spent between the span's two instants (``time.thread_time``). CPU near
  the wall time: the host computed; far under it: the thread waited or was
  descheduled.

A span is on while a profiler session is open
(``TraceAnnotation.is_enabled()``) or an enabled
:class:`~rocket_tpu.obs.telemetry.Telemetry` has installed its recorder;
otherwise :func:`span` hands back the shared :data:`OFF` and reads no
clock. There is no option and no environment variable: starting a profiler
trace turns the spans on. :func:`timed` is the same span for the few sites
whose instants the program needs anyway (``last_dispatch_at``,
``harvest_wait_s``): off, it reads the clock twice and records nothing.

One sink at a time: the recorder of the installed ``Telemetry`` (whose
``spans.trace.json`` is the operator's file), else the process-wide
default, which :func:`recorded` snapshots for readers and tests. The
request tracer's legs (``req/*``) and the compile listener's events
(``compile/*``) are added to the same sink after the fact
(:func:`add_span`), whether or not spans are on. The collection listener
(:func:`install_gc_listener`) records each garbage collection that starts
while spans are on as a span of its own, ``<first component of the
interrupted span>/gc`` (``serve/gc`` inside ``serve/tick``, ``host/gc``
outside every span): the interpreter is held for as long as it lasts.

Two clocks: the recorder keeps ``time.perf_counter()`` instants, the
profiler's file counts from ``start_trace``. Durations and order carry
over from one to the other; instants do not.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import os
import threading
import time
from typing import Any, Callable, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "OFF",
    "SpanEvent",
    "SpanRecorder",
    "add_span",
    "install",
    "install_compile_listener",
    "install_gc_listener",
    "load_chrome_trace",
    "recorded",
    "span",
    "timed",
    "uninstall",
]


class SpanEvent(NamedTuple):
    """One completed span. ``start``/``end`` are ``perf_counter`` instants;
    ``parent`` is the ``id`` of the span that was innermost on the thread
    when this one opened (None at the top, and for spans added after the
    fact); ``ids`` are the identifiers the site gave (``rid``, ``seq``…)."""

    name: str
    start: float
    end: float
    parent: Optional[int]
    ids: dict
    tid: int
    id: int
    cat: Optional[str]


class SpanRecorder:
    """Collects completed spans (a ring: the NEWEST ``max_events`` are
    kept, the dropped are counted) and renders them as Chrome-trace JSON.

    The *open*-span stack (``push_open`` / ``pop_open``) gives a span its
    parent, and the watchdog its stall report: :meth:`open_spans` is what
    every thread was inside when the run stopped making progress.
    """

    def __init__(self, max_events: int = 200_000) -> None:
        self.max_events = int(max_events)
        self.t0 = time.perf_counter()
        self._events: collections.deque[SpanEvent] = collections.deque(
            maxlen=self.max_events
        )
        self.dropped = 0
        # Re-entrant: a garbage collection can start while this thread holds
        # the lock, and the collection listener records its span here.
        self._lock = threading.RLock()
        self._ids = itertools.count()
        # tid -> stack of (name, id) for live (unfinished) spans.
        self._open: dict[int, list[tuple]] = {}
        #: Called with each compile event's seconds by the process-wide
        #: compile listener while this recorder is the sink (an enabled
        #: Telemetry counts ``compile/events`` / ``compile/secs`` here).
        self.on_compile: Optional[Callable[[float], None]] = None

    # -- recording ---------------------------------------------------------

    def add(self, name: str, cat: Optional[str], t_start: float,
            duration: float, tid: Optional[int] = None, *,
            parent: Optional[int] = None, ids: Optional[dict] = None,
            span_id: Optional[int] = None) -> None:
        event = SpanEvent(
            name, t_start, t_start + duration, parent, ids or {},
            threading.get_ident() if tid is None else tid,
            next(self._ids) if span_id is None else span_id, cat,
        )
        with self._lock:
            if len(self._events) == self.max_events:
                self.dropped += 1  # the deque lets go of its oldest
            self._events.append(event)

    def push_open(self, name: str) -> tuple:
        """Open ``name`` on this thread; returns ``(id, parent id)``."""
        tid = threading.get_ident()
        stack = self._open.get(tid)
        if stack is None:
            with self._lock:
                stack = self._open.setdefault(tid, [])
        span_id = next(self._ids)
        parent = stack[-1][1] if stack else None
        stack.append((name, span_id))
        return span_id, parent

    def pop_open(self) -> None:
        stack = self._open.get(threading.get_ident())
        if stack:
            stack.pop()

    def open_spans(self) -> dict[int, list[str]]:
        """Live span stack per thread id, innermost last (watchdog dump)."""
        out = {}
        for tid, stack in list(self._open.items()):
            if stack:
                out[tid] = [name for name, _id in list(stack)]
        return out

    # -- introspection -----------------------------------------------------

    def __len__(self) -> int:
        return len(self._events)

    def events(self) -> list[SpanEvent]:
        with self._lock:
            return list(self._events)

    # -- chrome trace ------------------------------------------------------

    def to_chrome_trace(self) -> dict[str, Any]:
        pid = os.getpid()
        trace_events = []
        thread_names = {t.ident: t.name for t in threading.enumerate()}
        for ev in self.events():
            event = {
                "name": ev.name,
                "cat": ev.cat or "span",
                "ph": "X",
                "ts": round((ev.start - self.t0) * 1e6, 3),
                "dur": round((ev.end - ev.start) * 1e6, 3),
                "pid": pid,
                "tid": ev.tid,
            }
            if ev.ids:
                event["args"] = dict(ev.ids)
            trace_events.append(event)
        for tid, tname in thread_names.items():
            if tid is None:
                continue
            trace_events.append({
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": tname},
            })
        return {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "rocket_tpu.obs", "dropped": self.dropped},
        }

    def write(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        return path


# -- the sink -----------------------------------------------------------------

_default = SpanRecorder()
#: The recorder of the enabled Telemetry that installed itself, or None:
#: the sink is then the process-wide default.
_installed: Optional[SpanRecorder] = None


def install(recorder: SpanRecorder) -> None:
    """Make ``recorder`` the sink and turn every span on until
    :func:`uninstall` (``Telemetry.start`` / ``close``)."""
    global _installed
    _installed = recorder


def uninstall(recorder: SpanRecorder) -> None:
    global _installed
    if _installed is recorder:
        _installed = None


def _sink() -> SpanRecorder:
    # Not `_installed or _default`: an empty recorder is falsy.
    return _default if _installed is None else _installed


def recorded() -> list[SpanEvent]:
    """A snapshot of the process-wide default recorder, oldest first."""
    return _default.events()


def add_span(name: str, start: float, end: float, **ids) -> None:
    """Add a span measured elsewhere (a request's leg, a compile event)
    to the current sink; recorded whether or not spans are on."""
    _sink().add(name, None, start, end - start, ids=ids)


# -- the primitive ------------------------------------------------------------


class _Off:
    """The span that is off: nothing opened, nothing recorded, no clock."""

    __slots__ = ()
    on = False
    start = None
    end = None

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **ids) -> None:
        return None


OFF = _Off()


class _Clock:
    """:func:`timed` with spans off: the two clock reads, nothing else."""

    __slots__ = ("start", "end")
    on = False

    def __enter__(self):
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()

    def set(self, **ids) -> None:
        return None


class Span:
    """A span that is on. ``goodput`` (with ``cat`` naming one of its
    phases) is what ``Telemetry.span`` adds."""

    __slots__ = ("name", "ids", "cat", "start", "end", "_sink", "_goodput",
                 "_annotation", "_id", "_parent", "_cpu")
    on = True

    def __init__(self, name: str, ids: dict, sink: SpanRecorder,
                 cat: Optional[str] = None, goodput=None) -> None:
        self.name = name
        self.ids = ids
        self.cat = cat
        self.end = None
        self._sink = sink
        self._goodput = goodput

    def __enter__(self) -> "Span":
        self._id, self._parent = self._sink.push_open(self.name)
        self._annotation = TraceAnnotation(self.name, **self.ids)
        self._annotation.__enter__()
        self._cpu = time.thread_time()
        self.start = time.perf_counter()
        if self._goodput is not None:
            self._goodput.push(self.cat, self.start)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end = time.perf_counter()
        self.ids["cpu_s"] = time.thread_time() - self._cpu
        if self._goodput is not None:
            self._goodput.pop(self.end)
        self._annotation.__exit__(exc_type, exc, tb)
        self._sink.pop_open()
        self._sink.add(
            self.name, self.cat, self.start, self.end - self.start,
            parent=self._parent, ids=self.ids, span_id=self._id,
        )

    def set(self, **ids) -> None:
        """Identifiers known only inside the span (``admitted=``,
        ``tokens=``): onto the record and the profiler's event."""
        self.ids.update(ids)
        self._annotation.set_metadata(**ids)


def span(name: str, **ids):
    """The span ``name`` with its identifiers, or :data:`OFF`."""
    if _installed is None and not TraceAnnotation.is_enabled():
        return OFF
    return Span(name, ids, _sink())


def timed(name: str, **ids):
    """:func:`span` for a site that needs ``.start``/``.end`` anyway."""
    sp = span(name, **ids)
    return _Clock() if sp is OFF else sp


# -- compile events -----------------------------------------------------------

#: jax.monitoring duration events -> span names. ``compile/cache_load``
#: lies INSIDE the ``compile/backend`` event of the same program, and an
#: inner jit's ``compile/trace`` inside its caller's: add these up as a
#: union of intervals, never as a plain sum.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile/cache_load",
}
_compile_listener = None


def _on_compile_event(event: str, duration: float, **kwargs) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is None:
        return
    end = time.perf_counter()
    sink = _sink()
    fun = kwargs.get("fun_name")
    sink.add(name, "compile", end - duration, duration,
             ids={} if fun is None else {"fun": str(fun)})
    if sink.on_compile is not None:
        sink.on_compile(duration)


def install_compile_listener() -> None:
    """Register the process-wide compile listener, once (the first
    ``Runtime`` or ``ServeEngine`` does). It costs nothing between
    compilations; every event becomes a ``compile/<phase>`` span."""
    global _compile_listener
    if _compile_listener is not None:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
    _compile_listener = _on_compile_event


# -- garbage collections -----------------------------------------------------

#: The collection under way while spans were on when it started:
#: ``(sink, name, parent, annotation, cpu at start, start)``, else None.
#: Collections neither nest nor overlap (the interpreter runs one at a time).
_gc_open: Optional[tuple] = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        if _installed is None and not TraceAnnotation.is_enabled():
            return
        sink = _sink()
        stack = sink._open.get(threading.get_ident())
        outer, parent = stack[-1] if stack else ("host", None)
        name = outer.split("/", 1)[0] + "/gc"
        annotation = TraceAnnotation(name, gen=info["generation"])
        annotation.__enter__()
        _gc_open = (sink, name, parent, annotation, time.thread_time(),
                    time.perf_counter())
    elif _gc_open is not None:
        end = time.perf_counter()
        sink, name, parent, annotation, cpu, start = _gc_open
        cpu = time.thread_time() - cpu
        _gc_open = None
        annotation.__exit__(None, None, None)
        sink.add(name, "gc", start, end - start, parent=parent, ids={
            "gen": info["generation"], "collected": info["collected"],
            "cpu_s": cpu,
        })


def install_gc_listener() -> None:
    """Register the process-wide collection listener, once (the first
    ``Runtime`` or ``ServeEngine`` does). With spans off it costs one check
    a collection; on, every collection becomes a ``*/gc`` span whose parent
    is the span it interrupted, with ``gen``, ``collected`` and ``cpu_s``."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def load_chrome_trace(path: str) -> list[dict]:
    """Load and structurally validate a Chrome-trace JSON file; returns the
    event list. Accepts both the object form (``{"traceEvents": [...]}``,
    what :meth:`SpanRecorder.write` emits) and the bare-array form."""
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a Chrome-trace file (no event list)")
    for event in events:
        if not isinstance(event, dict) or "ph" not in event:
            raise ValueError(f"{path}: malformed trace event {event!r}")
    return events
