"""From a profiler trace to the numbers the per-layer metrics read.

A traced run records one ``.xplane.pb`` with ``jax.profiler`` and
``python_tracer_level=1``: the device's operations and the program's
Python calls on one clock.  :func:`load` reads it with nothing but JAX and
keeps what the metrics need:

* ``device_ops``: every operation the device ran (the "XLA Ops" line of
  each ``/device:TPU:n`` plane), and ``modules``: every program run (the
  "XLA Modules" line), as ``(name, start_ns, end_ns)``;
* ``host``: the Python calls of each host thread, as
  ``(name, start_ns, end_ns)``, where a name reads
  ``$<file basename>:<first line> <function>``;
* ``window``: the interval of the harness's window annotation.

Everything else (the arithmetic of busy time, outermost calls, idle gaps)
works on those tuples, so the tests drive it with a small recorded trace.
"""

from __future__ import annotations

import dataclasses
import importlib
import inspect
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: the annotation the harness wraps its measured window in
WINDOW_SPAN = "chipbench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, int, int]  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    host: List[List[Event]]
    window: Tuple[int, int]

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append((e.name, start, start + int(e.duration_ns)))
    out.sort(key=lambda ev: (ev[1], -ev[2]))
    return out


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler.stop_trace``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[List[Event]] = []
    window: Optional[Tuple[int, int]] = None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = _events(line)
                for name, start, end in events:
                    if name == WINDOW_SPAN:
                        window = (start, end)
                if any(name.startswith("$") for name, _, _ in events):
                    host.append(events)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} annotation in the trace")
    return Trace(device_ops, modules, host, window)


# -- device ------------------------------------------------------------------
def clip(events: Iterable[Event], window: Tuple[int, int]) -> List[Event]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """Merged, sorted intervals covered by any of ``events``."""
    merged: List[List[int]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(trace: Trace) -> float:
    """Nanoseconds in which an operation ran, averaged over the devices."""
    if not trace.device_ops:
        return 0.0
    per_device = [sum(e - s for s, e in union(clip(ops, trace.window)))
                  for ops in trace.device_ops.values()]
    return sum(per_device) / len(per_device)


def idle_gaps(trace: Trace) -> List[Tuple[int, int]]:
    """Intervals of the window in which the first device ran nothing."""
    if not trace.device_ops:
        return [trace.window]
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    lo, hi = trace.window
    gaps, cursor = [], lo
    for s, e in union(clip(ops, trace.window)):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def module_ns(trace: Trace, prefix: str) -> Tuple[int, int]:
    """(count, total ns) of the program runs whose name starts with
    ``prefix`` (``jit_<function>`` for a jitted function), over the
    window, summed over devices."""
    count = total = 0
    for events in trace.modules.values():
        for name, s, e in clip(events, trace.window):
            if name == prefix or name.startswith(prefix + "("):
                count += 1
                total += e - s
    return count, total


def op_name(name: str) -> str:
    """An operation's short name: ``%fusion.3 = f32[...] fusion(...)``
    (the device's event name is the whole HLO instruction) -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` device operations that took most time, in seconds."""
    total: Dict[str, int] = defaultdict(int)
    for events in trace.device_ops.values():
        for name, s, e in clip(events, trace.window):
            total[op_name(name)] += e - s
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in ranked]


# -- host --------------------------------------------------------------------
def event_name(qualname: str) -> str:
    """The python tracer's name for the function at ``qualname``
    (``package.module.Class.function``), resolved from the code under
    test: ``$<file basename>:<first line> <function>``."""
    parts = qualname.split(".")
    for cut in range(len(parts) - 1, 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:cut]))
        except ImportError:
            continue
        for attr in parts[cut:]:
            obj = getattr(obj, attr)
        code = inspect.unwrap(obj).__code__
        base = code.co_filename.replace("\\", "/").rsplit("/", 1)[-1]
        return f"${base}:{code.co_firstlineno} {code.co_name}"
    raise ImportError(f"cannot resolve {qualname!r}")


def outermost_ns(trace: Trace, names: Sequence[str]) -> int:
    """Inclusive nanoseconds spent in any of the functions ``names``
    (tracer names) within the window, counting only the outermost call
    where one of them calls another."""
    wanted = set(names)
    total = 0
    for events in trace.host:
        end_kept = -1
        for name, s, e in clip(events, trace.window):
            if name in wanted and s >= end_kept:
                total += e - s
                end_kept = e
    return total


def _timeline(events: List[Event], in_program, entry: str):
    """Where one thread was while inside ``entry``, as ``(start, end,
    label)`` pieces: a label names the program calls open then below
    ``entry``, as ``first>last`` (or ``entry`` itself).  A sweep over the
    thread's nested calls.  A trace line can hold several threads in turn
    (thread ids are reused), so only time inside ``entry`` counts."""
    pieces: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []  # (end, function), innermost last
    now = None

    def advance(to: int) -> None:
        nonlocal now
        fns = [f for _, f in stack]
        if now is not None and entry in fns and to > now:
            below = fns[fns.index(entry) + 1:] or [entry]
            label = (below[0] if len(below) == 1
                     else f"{below[0]}>{below[-1]}")
            pieces.append((now, to, label))
        now = to

    for name, s, e in events:
        if not in_program(name):
            continue
        while stack and stack[-1][0] <= s:
            advance(stack[-1][0])
            stack.pop()
        advance(s)
        stack.append((e, name.rsplit(" ", 1)[-1]))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return pieces


def name_gaps(trace: Trace, gaps: Sequence[Tuple[int, int]],
              program_files: Iterable[str], entry: str = "handler",
              n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest stretches of the device's idle ``gaps``, each named
    by where the host was meanwhile: the program calls open below
    ``entry`` (the campaign handler) on the thread running it, as
    ``first>last``, or "outside the handler"."""
    files = set(program_files)

    def in_program(name: str) -> bool:
        return name.startswith("$") and name[1:].split(":", 1)[0] in files

    pieces: List[Tuple[int, int, str]] = []
    for events in trace.host:
        if any(in_program(name) and name.endswith(" " + entry)
               for name, _, _ in events):
            pieces += _timeline(events, in_program, entry)
    pieces.sort()
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
        while i < len(pieces) and pieces[i][1] <= lo:
            i += 1
        cursor, j = lo, i
        while j < len(pieces) and pieces[j][0] < hi:
            s, e, label = pieces[j]
            add("outside the handler", cursor, max(cursor, min(s, hi)))
            add(label, max(s, lo), min(e, hi))
            cursor = max(cursor, min(e, hi))
            j += 1
        add("outside the handler", cursor, hi)
    named.sort(key=lambda x: x[1] - x[2])
    return [(label, (e - s) / 1e9) for label, s, e in named[:n]]
