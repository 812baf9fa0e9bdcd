"""The reduction from the program's ``repro.*`` spans to the span metrics:
on span lists made by hand, on traced CPU runs of the small test cells,
and on the two recorded traces, which hold no span."""

import json
import math
import time
from pathlib import Path

import pytest

from chipbench import harness, spanreduce as sr
from chipbench import tracereduce as tr
from chipbench.tests import tinyroot

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
METRICS = ["queue_s_per_tile", "unattributed_s_per_tile",
           "fetch_thread_s_per_tile", "decode_thread_s_per_tile",
           "h2d_s_per_tile", "dispatch_s_per_tile", "device_wait_s_per_tile",
           "pyramid_s_per_tile"]
#: what the program sends to the device a tile at the test size: composite
#: the stack twice, the cloud score, nir and red (f32); segmentation the
#: stack, valid, the score, the edges and their complement
PX, T, C = tinyroot.PX, tinyroot.DEPTH, 4
H2D_BYTES = {
    "tiny-composite": 4 * (2 * T * PX * PX * C + 3 * T * PX * PX),
    "tiny-segment": 4 * T * PX * PX * C + T * PX * PX + 4 * T * PX * PX
    + 2 * PX * PX,
}


def sp(name, s, e, **counts):
    return ("repro." + name, s, e, counts)


def test_thread_seconds_sum_threads_and_clip_to_the_window():
    lines = [[sp("fetch", 0, 30), sp("fetch", 10, 20),  # nested: once
              sp("decode", 30, 40)],
             [sp("fetch", 20, 60)],
             [sp("fetch", 90, 130)]]
    assert sr.thread_ns(lines, "repro.fetch", (5, 100)) == 25 + 40 + 10
    assert sr.thread_ns(lines, "repro.decode", (5, 100)) == 10
    assert sr.thread_ns(lines, "repro.h2d", (5, 100)) == 0
    assert sr.found(lines, "repro.fetch") and not sr.found(lines, "repro.h2d")


def test_self_time_leaves_out_the_spans_nested_in_it():
    task = [sp("task", 0, 100), sp("read", 10, 40), sp("fetch", 20, 30),
            sp("h2d", 35, 50), sp("write", 120, 130)]
    pool = [sp("fetch", 0, 100)]  # another thread's span does not count
    assert sr.self_ns([task, pool], "repro.task", (0, 200)) == 100 - 40
    assert sr.self_ns([task], "repro.task", (45, 200)) == 55 - 5
    assert sr.self_ns([task], "repro.read", (0, 200)) == 30 - 10


def test_time_outside_every_task():
    lines = [[sp("task", 10, 40), sp("read", 10, 20)],
             [sp("task", 30, 60)],
             [sp("fetch", 70, 80)]]
    assert sr.outside_ns(lines, "repro.task", (0, 100)) == 100 - 50
    assert sr.outside_ns([], "repro.task", (0, 100)) == 100


def test_counts_are_summed_over_spans_wholly_in_the_window():
    lines = [[sp("h2d", 0, 10, bytes=7), sp("h2d", 20, 30, bytes=5)],
             [sp("h2d", 40, 50, bytes=3), sp("dispatch", 50, 60)]]
    assert sr.count(lines, "repro.h2d", "bytes", (0, 100)) == 15
    assert sr.count(lines, "repro.h2d", "bytes", (5, 100)) == 8


def test_idle_gaps_are_named_by_the_innermost_open_span():
    task = [sp("task", 10, 100), sp("read", 10, 40), sp("h2d", 50, 60),
            sp("dispatch", 60, 70), sp("device_wait", 70, 90)]
    pool = [sp("fetch", 15, 35)]  # not a task's line
    gaps = [(0, 65), (80, 120)]
    named = sr.span_gaps([task, pool], gaps)
    assert named == [("repro.read", 30e-9), ("outside any task", 20e-9),
                     ("outside any task", 10e-9), ("repro.task", 10e-9),
                     ("repro.h2d", 10e-9), ("repro.device_wait", 10e-9),
                     ("repro.task", 10e-9), ("repro.dispatch", 5e-9)]
    # the named stretches split the idle time, no more and no less
    assert sum(s for _, s in named) == pytest.approx(105e-9)
    assert sr.span_gaps([], gaps) == [("outside any task", 65e-9),
                                      ("outside any task", 40e-9)]


def test_overlapping_tasks_name_each_instant_once():
    a = [sp("task", 0, 50), sp("h2d", 0, 50)]
    b = [sp("task", 25, 100), sp("read", 25, 100)]
    named = sr.span_gaps([a, b], [(0, 100)], n=5)
    assert named == [("repro.h2d", 50e-9), ("repro.read", 50e-9)]


def test_spans_come_from_the_host_lines_of_a_trace():
    trace = tr.Trace({}, {}, [[("$a.py:1 f", 0, 9), ("repro.read", 1, 5)],
                              [("$b.py:2 g", 0, 9)]], (0, 10))
    assert sr.of_trace(trace) == [[("repro.read", 1, 5, {})]]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced run of each small cell, with its trace kept, in a checkout
    whose span metrics also list the small cells."""
    base = tmp_path_factory.mktemp("spans")
    root = tinyroot.make(base)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for metric in manifest["per_layer"]:
        if metric["name"] in METRICS:
            metric["workloads"] += ["tiny-composite", "tiny-segment"]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    runs = {}
    for cell in ("tiny-composite", "tiny-segment"):
        keep = base / f"{cell}.xplane.pb"
        result = harness.main(["--workload", cell, "--seed", str(2**31 + 3),
                               "--seconds", "0.3", "--trace", "1"],
                              root=root, started=time.monotonic(),
                              require_chip=False, keep_trace=keep)
        runs[cell] = (result, *sr.load(str(keep)))
    return runs


CELLS = ["tiny-composite", "tiny-segment"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_every_span_metric(traced, cell):
    result, _, _ = traced[cell]
    assert result["correct"], result
    metrics = result["metrics"]
    wanted = [m for m in METRICS
              if cell == "tiny-composite" or m != "pyramid_s_per_tile"]
    for name in wanted:
        value = metrics[name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    for name in ("fetch_thread_s_per_tile", "decode_thread_s_per_tile",
                 "h2d_s_per_tile", "unattributed_s_per_tile"):
        assert metrics[name]["value"] > 0, name
    if cell == "tiny-segment":
        assert "pyramid_s_per_tile" not in metrics


@pytest.mark.parametrize("cell", CELLS)
def test_layer_spans_nest_in_tasks_and_reads(traced, cell):
    _, lines, window = traced[cell]
    assert window is not None
    # the handler's spans open on the task's thread, inside the task; a
    # trace line holds one thread at a time, its id reused by later ones
    chunk = ("repro.fetch", "repro.decode")
    tasks = 0
    for line in lines:
        for name, s, e, counts in line:
            if name == sr.TASK:
                tasks += 1
                assert set(counts) == {"task", "worker"}
            elif name not in chunk and window[0] <= s < window[1]:
                assert any(ts <= s and e <= te
                           for n, ts, te, _ in line if n == sr.TASK), name
    assert tasks >= 1
    # chunk reads happen for the stack's read and the pyramid's read-back,
    # on the chunk store's threads
    readers = [(s, e) for line in lines for n, s, e, _ in line
               if n in ("repro.read", "repro.pyramid")]
    chunk_spans = [(n, s, e) for line in lines for n, s, e, _ in line
                   if n in chunk and window[0] <= s < window[1]]
    assert chunk_spans
    for n, s, e in chunk_spans:
        assert any(rs <= s and e <= re for rs, re in readers), (n, s, e)


@pytest.mark.parametrize("cell", CELLS)
def test_h2d_bytes_are_what_the_program_sends(traced, cell):
    result, lines, window = traced[cell]
    tiles = sum(1 for line in lines for n, s, e, _ in line
                if n == sr.TASK and window[0] <= s and e <= window[1])
    assert tiles == result["attempted"] >= 1
    sent = sr.count(lines, "repro.h2d", "bytes", window)
    assert sent == H2D_BYTES[cell] * tiles


@pytest.mark.parametrize("name", ["composite", "segment"])
def test_a_program_without_spans_reads_none(name):
    """The recorded traces come from a program with no span: every span
    metric reads None and nothing raises."""
    import importlib.util

    class Run:
        trace = tr.load(str(TESTDATA / f"{name}.xplane.pb"))
        tiles_done = 1

    assert sr.of_trace(Run.trace) == []
    for metric in METRICS:
        path = Path(sr.__file__).parent / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(metric, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read(Run) is None, metric
