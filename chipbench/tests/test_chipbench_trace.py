"""The reduction from a profiler trace to per-layer numbers, on event lists
made by hand and on small traces recorded on a TPU v5e
(``record_trace.py``: the two small test cells, traced)."""

from pathlib import Path

import numpy as np
import pytest

from chipbench import tracereduce as tr

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def make(ops, host, window):
    return tr.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": []}, host,
                    window)


def test_union_and_busy_clip_to_the_window():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 95, 120)]
    trace = make(ops, [], (10, 100))
    assert tr.union(ops) == [(0, 20), (30, 40), (95, 120)]
    assert tr.busy_ns(trace) == 10 + 10 + 5
    assert tr.idle_gaps(trace) == [(20, 30), (40, 95)]


def test_outermost_calls_count_once():
    host = [[("$a.py:1 outer", 0, 100), ("$b.py:5 inner", 10, 40),
             ("$a.py:1 outer", 20, 30),  # recursion
             ("$b.py:5 inner", 150, 170)],
            [("$b.py:5 inner", 50, 60)]]
    trace = make([], host, (0, 1000))
    both = ["$a.py:1 outer", "$b.py:5 inner"]
    assert tr.outermost_ns(trace, both) == 100 + 20 + 10
    assert tr.outermost_ns(trace, ["$b.py:5 inner"]) == 30 + 20 + 10


def test_gaps_are_split_and_named_by_the_program_calls_below_the_handler():
    host = [[("$cluster.py:9 loop", 0, 100),
             ("$composite.py:3 handler", 0, 100),
             ("$imagery.py:7 read_scene_stack", 0, 50),
             ("$chunkstore.py:2 read_region", 10, 50),
             ("$composite.py:8 cloud_score", 50, 90)]]
    trace = make([("k", 90, 95)], host, (0, 100))
    gaps = tr.idle_gaps(trace)
    assert gaps == [(0, 90), (95, 100)]
    named = tr.name_gaps(trace, gaps, {"composite.py", "imagery.py",
                                       "chunkstore.py", "cluster.py"})
    assert named == [("read_scene_stack>read_region", 40e-9),
                     ("cloud_score", 40e-9), ("read_scene_stack", 10e-9),
                     ("handler", 5e-9)]
    # no thread in the handler: the gaps are outside it
    bare = make([("k", 90, 95)], [], (0, 100))
    assert tr.name_gaps(bare, gaps, {"composite.py"}) == [
        ("outside the handler", 90e-9), ("outside the handler", 5e-9)]


def test_module_runs_match_by_name():
    trace = make([], [], (0, 100))
    trace.modules["/device:TPU:0"] = [("jit_composite", 1, 11),
                                      ("jit_composite(3)", 20, 25),
                                      ("jit_composite_weights", 30, 40)]
    assert tr.module_ns(trace, "jit_composite") == (2, 15)


def test_event_names_come_from_the_code_under_test():
    import repro.data.imagery as imagery

    code = imagery.read_scene_stack.__code__
    assert (tr.event_name("repro.data.imagery.read_scene_stack")
            == f"$imagery.py:{code.co_firstlineno} read_scene_stack")
    assert tr.event_name("repro.core.chunkstore.ChunkedArray.write_region"
                         ).endswith(" write_region")


@pytest.fixture(scope="module", params=["composite", "segment"])
def recorded(request):
    path = TESTDATA / f"{request.param}.xplane.pb"
    return request.param, tr.load(str(path))


def brute_busy(trace, step=1000):
    """Busy nanoseconds of the first device, on a grid of ``step`` ns."""
    lo, hi = trace.window
    grid = np.zeros((hi - lo) // step + 1, bool)
    for _, s, e in next(iter(trace.device_ops.values())):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            grid[(s - lo) // step:(e - lo) // step] = True
    return grid.sum() * step


def test_recorded_trace_has_device_and_host(recorded):
    kind, trace = recorded
    assert trace.window_ns > 0
    assert len(trace.device_ops) == 1 and trace.host
    busy = tr.busy_ns(trace)
    assert 0 < busy < trace.window_ns
    assert abs(busy - brute_busy(trace)) <= 2000 * len(tr.union(
        next(iter(trace.device_ops.values()))))
    program = "jit_composite" if kind == "composite" else "jit_grad_mag"
    runs, ns = tr.module_ns(trace, program)
    assert runs >= 1 and 0 < ns < trace.window_ns
    read = tr.outermost_ns(trace, [tr.event_name(
        "repro.data.imagery.read_scene_stack")])
    assert 0 < read < trace.window_ns
    gaps = tr.idle_gaps(trace)
    assert sum(e - s for s, e in gaps) == trace.window_ns - busy
    files = {p.name for p in (
        Path(__file__).resolve().parents[2] / "src" / "repro").rglob("*.py")}
    named = tr.name_gaps(trace, gaps, files)
    assert 1 <= len(named) <= 10
    assert all(name for name, _ in named)
    # the named stretches split the idle time, no more and no less
    every = tr.name_gaps(trace, gaps, files, n=len(trace.device_ops) * 10**6)
    assert sum(sec for _, sec in every) == pytest.approx(
        (trace.window_ns - busy) / 1e9)
    assert tr.top_ops(trace)[0][1] > 0
