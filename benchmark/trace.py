"""From a profiler trace to numbers: the one reduction every PR is read with.

A trace is normalised to ``{plane: {line: [[name, start_ns, dur_ns], ...]}}``
(:func:`load_xplane` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``;
the recorded fixture in ``benchmark/tests`` is the same structure as JSON).
Everything below works on that structure, so it is checked on the CPU.

Device planes are those named ``/device:TPU:<n>``. On each, the line
``XLA Ops`` holds one event per executed operation (the busy time is the
UNION of their intervals) and ``XLA Modules`` one event per executed jitted
program. Host planes (``/host:CPU``) carry the benchmark's own
``TraceAnnotation`` spans, named ``bench/...``, and the program's
(``serve/...``, ``train/...``, ``data/...``: ``rocket_tpu.obs.spans``). A
serving run marks its traced stretch with one span, ``bench/stretch``;
:func:`cut_to_span` cuts the whole trace to it.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Host spans kept: the benchmark's own and the program's.
HOST_SPAN_PREFIXES = ("bench/", "serve/", "train/", "data/")
STRETCH_SPAN = "bench/stretch"
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


@functools.lru_cache(maxsize=None)
def short_op_name(text: str) -> str:
    """The profiler names an operation by its whole HLO line,
    ``%name = shape opcode(operands...)``: keep ``name opcode``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120]
    m = _OPCODE.search(rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str) -> dict:
    """Read an ``.xplane.pb`` into the normalised structure, keeping the
    device planes whole and, of the host planes, the spans whose names
    start with one of ``HOST_SPAN_PREFIXES``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines: dict = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ops = device and line.name == OPS_LINE
            events = [
                [short_op_name(e.name) if ops else e.name,
                 int(e.start_ns), int(e.duration_ns)]
                for e in line.events
                if device or e.name.startswith(HOST_SPAN_PREFIXES)
            ]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            out[plane.name] = lines
    return out


def cut_to_span(trace: dict, name: str = STRETCH_SPAN) -> dict:
    """The trace cut to the host span ``name``: events that start inside
    it, one that straddles its end shortened to it. The serving drivers
    stop the profiler after their loop has drained, some time past the
    close of the window; what the readers see is ``[t0, t1]``. A trace
    without the span is returned as it is."""
    found = [(s, e) for n, s, e in host_spans(trace) if n == name]
    if not found:
        return trace
    lo, hi = found[0]
    out: dict = {}
    for plane, lines in trace.items():
        kept = {}
        for line, events in lines.items():
            events = [[n, s, min(d, hi - s)] for n, s, d in events
                      if lo <= s < hi and n != name]
            if events:
                kept[line] = events
        if kept:
            out[plane] = kept
    return out


# -- intervals ----------------------------------------------------------------

def union(intervals: Iterable[tuple]) -> list[tuple]:
    """Merge ``(start, end)`` intervals into a sorted disjoint list."""
    merged: list[list] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Iterable[tuple], lo: float, hi: float) -> list[tuple]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: Iterable[tuple]) -> float:
    return float(sum(e - s for s, e in intervals))


# -- reductions ---------------------------------------------------------------

def device_planes(trace: dict) -> list[str]:
    return sorted(p for p in trace if DEVICE_PLANE.match(p))


def _events(trace: dict, plane: str, line: str) -> list:
    return trace.get(plane, {}).get(line, [])


def window_ns(trace: dict) -> tuple[int, int]:
    """First start and last end over the device planes' events."""
    starts, ends = [], []
    for plane in device_planes(trace):
        for line in trace[plane].values():
            for _, s, d in line:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        raise ValueError("the trace holds no device event")
    return min(starts), max(ends)


def busy_intervals(trace: dict, plane: str) -> list[tuple]:
    return union((s, s + d) for _, s, d in _events(trace, plane, OPS_LINE))


def busy_s(trace: dict, window: Optional[tuple] = None) -> float:
    """Seconds in which an operation ran, averaged over the device planes
    (clipped to ``window`` = (lo_ns, hi_ns) if given)."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    sums = []
    for plane in planes:
        iv = busy_intervals(trace, plane)
        if window is not None:
            iv = clip(iv, *window)
        sums.append(total(iv))
    return sum(sums) / len(sums) / 1e9


def module_durations_s(trace: dict, pattern: str) -> list[float]:
    """Device durations (seconds) of every executed program whose name
    matches ``pattern`` (a regex, searched), over all device planes."""
    rx = re.compile(pattern)
    return [
        d / 1e9
        for plane in device_planes(trace)
        for name, _, d in _events(trace, plane, MODULES_LINE)
        if rx.search(name)
    ]


def op_durations_s(trace: dict, pattern: str) -> list[float]:
    """Device durations (seconds) of every operation whose name matches."""
    rx = re.compile(pattern)
    return [
        d / 1e9
        for plane in device_planes(trace)
        for name, _, d in _events(trace, plane, OPS_LINE)
        if rx.search(name)
    ]


def top_ops(trace: dict, n: int = 10) -> list[list]:
    """``[[name, seconds], ...]``: the operations that took most device
    time, summed by name over the device planes."""
    sums: dict = {}
    for plane in device_planes(trace):
        for name, _, d in _events(trace, plane, OPS_LINE):
            sums[name] = sums.get(name, 0) + d
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:120], d / 1e9] for name, d in ranked]


def host_spans(trace: dict) -> list[tuple]:
    """``(name, start_ns, end_ns)`` of the host spans kept."""
    return sorted(
        (name, s, s + d)
        for plane, lines in trace.items() if not DEVICE_PLANE.match(plane)
        for line in lines.values()
        for name, s, d in line
        if name.startswith(HOST_SPAN_PREFIXES)
    )


def idle_gaps(trace: dict, n: int = 10, window: Optional[tuple] = None) -> list[list]:
    """``[[what, seconds], ...]``: idle time of the first device plane
    inside the window, summed by what the host was doing in it: the
    INNERMOST host span around the gap (the shortest of the spans that
    cover half of it or more: ``serve/harvest_wait`` inside ``serve/tick``
    inside ``bench/step``), else the span that covers most of it,
    ``host/other`` where none touches it."""
    planes = device_planes(trace)
    if not planes:
        return []
    lo, hi = window or window_ns(trace)
    busy = clip(busy_intervals(trace, planes[0]), lo, hi)
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    spans = sorted((s, e, name) for name, s, e in host_spans(trace))
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    sums: dict = {}
    for gs, ge in gaps:
        around, most = None, None       # (span length, name), (cover, name)
        first = bisect.bisect_left(starts, gs - longest)
        for s, e, name in spans[first:bisect.bisect_left(starts, ge)]:
            cover = min(e, ge) - max(s, gs)
            if cover <= 0:
                continue
            if 2 * cover >= ge - gs and (around is None or e - s < around[0]):
                around = (e - s, name)
            if most is None or cover > most[0]:
                most = (cover, name)
        what = (around or most or (0, "host/other"))[1]
        sums[what] = sums.get(what, 0) + (ge - gs)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, d / 1e9] for name, d in ranked]


def summary_lines(trace: dict, n: int = 25) -> list[str]:
    """What a person reads after a traced run (standard error): device
    seconds by opcode, by operation name without its number, and by
    jitted program."""
    by_opcode: dict = {}
    by_stem: dict = {}
    for name, seconds in top_ops(trace, 10**9):
        head, _, opcode = name.rpartition(" ")
        by_opcode[opcode] = by_opcode.get(opcode, 0.0) + seconds
        stem = head.rstrip("0123456789").rstrip(".") + " " + opcode
        count, total_s = by_stem.get(stem, (0, 0.0))
        by_stem[stem] = (count + 1, total_s + seconds)
    by_module: dict = {}
    for plane in device_planes(trace):
        for name, _, d in _events(trace, plane, MODULES_LINE):
            key = name.partition("(")[0]
            count, total_s = by_module.get(key, (0, 0.0))
            by_module[key] = (count + 1, total_s + d / 1e9)
    lines = [
        f"opcode {seconds:10.6f}s {opcode}"
        for opcode, seconds in sorted(by_opcode.items(), key=lambda kv: -kv[1])[:12]
    ]
    lines += [
        f"ops {total_s:10.6f}s x{count} {stem}"
        for stem, (count, total_s) in sorted(by_stem.items(), key=lambda kv: -kv[1][1])[:n]
    ]
    lines += [
        f"module {total_s:10.6f}s x{count} {key}"
        for key, (count, total_s) in sorted(by_module.items(), key=lambda kv: -kv[1][1])
    ]
    return lines
