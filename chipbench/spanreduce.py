"""From the program's spans in a profiler trace to the numbers the span
metrics read.

The program opens a span at each layer boundary of the campaign path
(``repro/core/spans.py``): a ``jax.profiler.TraceAnnotation`` named
``repro.<layer>``, on the host thread that does the work and on the clock
of the device's operations.  Here a span is ``(name, start_ns, end_ns,
counts)``, and a trace's spans are one list per trace line (a host
thread), sorted by start.

* :func:`of_trace` takes them from a :class:`tracereduce.Trace`: the lines
  on which the Python tracer also recorded calls, without the counts;
* :func:`load` reads them, counts included, from every host line of an
  ``.xplane.pb``, whatever else the line holds.

The reducers work on those tuples, so the tests drive them with lists made
by hand.  A program without spans gives no span lists, and each metric
then reads None.
"""

from __future__ import annotations

import warnings
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench.tracereduce import WINDOW_SPAN, clip, union

PREFIX = "repro."
TASK = PREFIX + "task"
OUTSIDE = "outside any task"

Span = Tuple[str, int, int, Dict[str, object]]
Interval = Tuple[int, int]


def of_trace(trace) -> List[List[Span]]:
    """The spans on the host lines that ``tracereduce.load`` kept."""
    lines = [[(n, s, e, {}) for n, s, e in line if n.startswith(PREFIX)]
             for line in trace.host]
    return [line for line in lines if line]


def load(path: str) -> Tuple[List[List[Span]], Optional[Interval]]:
    """(span lines with their counts, the window) of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lines: List[List[Span]] = []
    window = None
    with warnings.catch_warnings():
        # the profiler's stats type warns on attribute lookups
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                spans = []
                for e in line.events:
                    start = int(e.start_ns)
                    end = start + int(e.duration_ns)
                    if e.name == WINDOW_SPAN:
                        window = (start, end)
                    elif e.name.startswith(PREFIX):
                        spans.append((e.name, start, end, dict(e.stats)))
                if spans:
                    spans.sort(key=lambda sp: (sp[1], -sp[2]))
                    lines.append(spans)
    return lines, window


# -- interval arithmetic -------------------------------------------------------
def _merged(spans: Iterable[Span], window: Interval) -> List[Interval]:
    """The window's time covered by any of ``spans``, merged and sorted."""
    return union(clip(((n, s, e) for n, s, e, _ in spans), window))


def _length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def _overlap(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Nanoseconds in both of two merged, sorted interval lists."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _covered(line: Sequence[Span], name: str, window: Interval
             ) -> List[Interval]:
    return _merged((sp for sp in line if sp[0] == name), window)


# -- reducers ------------------------------------------------------------------
def found(lines: Sequence[Sequence[Span]], name: str) -> bool:
    return any(n == name for line in lines for n, _, _, _ in line)


def thread_ns(lines: Sequence[Sequence[Span]], name: str,
              window: Interval) -> int:
    """Nanoseconds inside spans ``name``, clipped to the window, summed
    over lines: threads that work at once each count."""
    return sum(_length(_covered(line, name, window)) for line in lines)


def self_ns(lines: Sequence[Sequence[Span]], name: str,
            window: Interval) -> int:
    """Nanoseconds inside spans ``name`` but inside no other span opened
    within them on the same line, clipped to the window."""
    total = 0
    for line in lines:
        spans = [sp for sp in line if sp[0] == name]
        if not spans:
            continue
        nested = [sp for sp in line if sp[0] != name
                  and any(o[1] <= sp[1] and sp[2] <= o[2] for o in spans)]
        outer = _merged(spans, window)
        total += _length(outer) - _overlap(outer, _merged(nested, window))
    return total


def outside_ns(lines: Sequence[Sequence[Span]], name: str,
               window: Interval) -> int:
    """Nanoseconds of the window in which no line is inside a span
    ``name``."""
    inside = _merged((sp for line in lines for sp in line
                      if sp[0] == name), window)
    return window[1] - window[0] - _length(inside)


def count(lines: Sequence[Sequence[Span]], name: str, key: str,
          window: Interval) -> int:
    """The count ``key`` summed over the spans ``name`` that lie wholly in
    the window."""
    lo, hi = window
    return sum(int(stats.get(key, 0)) for line in lines
               for n, s, e, stats in line if n == name and lo <= s
               and e <= hi)


def _innermost(line: Sequence[Span]) -> List[Tuple[int, int, str]]:
    """Where one line was inside a task, as ``(start, end, name)`` pieces
    named by the innermost span open then."""
    pieces: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []  # (end, name), innermost last
    now: Optional[int] = None

    def advance(to: int) -> None:
        nonlocal now
        if (now is not None and to > now
                and any(n == TASK for _, n in stack)):
            pieces.append((now, to, stack[-1][1]))
        now = to if now is None else max(now, to)

    for name, s, e, _ in sorted(line, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][0] <= s:
            advance(stack[-1][0])
            stack.pop()
        advance(s)
        stack.append((e, name))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return pieces


def span_gaps(lines: Sequence[Sequence[Span]], gaps: Iterable[Interval],
              n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest stretches of the device's idle ``gaps``, in
    seconds, each named by the innermost span open on a task's line
    meanwhile, or "outside any task".  Where tasks run at once the first
    to have opened its span names the time."""
    timeline: List[Tuple[int, int, str]] = []
    for s, e, label in sorted(p for line in lines if found([line], TASK)
                              for p in _innermost(line)):
        s = max(s, timeline[-1][1]) if timeline else s
        if e > s:
            timeline.append((s, e, label))
    named: List[List] = []  # [label, start, end], in time order

    def add(label: str, s: int, e: int) -> None:
        if e <= s:
            return
        if named and named[-1][0] == label and named[-1][2] == s:
            named[-1][2] = e
        else:
            named.append([label, s, e])

    i = 0
    for lo, hi in sorted(gaps):
        while i < len(timeline) and timeline[i][1] <= lo:
            i += 1
        cursor, j = lo, i
        while j < len(timeline) and timeline[j][0] < hi:
            s, e, label = timeline[j]
            s, e = max(s, cursor), min(e, hi)
            add(OUTSIDE, cursor, s)
            add(label, s, e)
            cursor = max(cursor, e)
            j += 1
        add(OUTSIDE, cursor, hi)
    named.sort(key=lambda x: x[1] - x[2])
    return [(label, (e - s) / 1e9) for label, s, e in named[:n]]


# -- what the metric readers call ----------------------------------------------
def _run_spans(run):
    if run.trace is None or not run.tiles_done:
        return None
    return of_trace(run.trace)


def thread_s_per_tile(run, layer: str) -> Optional[float]:
    """Seconds per tile inside ``repro.<layer>``, summed over threads; None
    where the program opened no such span."""
    lines = _run_spans(run)
    name = PREFIX + layer
    if not lines or not found(lines, name):
        return None
    return thread_ns(lines, name, run.trace.window) / 1e9 / run.tiles_done


def task_self_s_per_tile(run) -> Optional[float]:
    """Seconds per tile inside a task but inside none of its layers."""
    lines = _run_spans(run)
    if not lines or not found(lines, TASK):
        return None
    return self_ns(lines, TASK, run.trace.window) / 1e9 / run.tiles_done


def outside_task_s_per_tile(run) -> Optional[float]:
    """Seconds per tile of the window outside every task."""
    lines = _run_spans(run)
    if not lines or not found(lines, TASK):
        return None
    return outside_ns(lines, TASK, run.trace.window) / 1e9 / run.tiles_done
