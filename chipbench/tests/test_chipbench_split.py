"""The split segmentation cell's per-layer metrics, read from traces made
by hand: four chips, each running the split programs and their
collectives."""

import json
import types
from pathlib import Path

import pytest

from chipbench import harness, hlo
from chipbench import tracereduce as tr

BENCH = Path(__file__).resolve().parents[1]
CHIPS = 4
PROGRAMS = ("jit_split_grad_mag", "jit_split_components")
#: device op names as a v5e trace gives them: the whole HLO instruction,
#: whose name need not tell its opcode (the psum is ``psum_invariant``)
START = ("%collective-permute-start.{n} = (s32[256]{{0:T(256)S(1)}}, "
         "s32[256]{{0:T(256)S(1)}}, u32[]{{:S(2)}}, u32[]{{:S(2)}}) "
         "collective-permute-start(%bitcast.14), channel_id=1, "
         "source_target_pairs={{{{0,2}},{{1,3}}}}")
DONE = ("%collective-permute-done.{n} = {t}[256]{{0:T(256)S(1)}} "
        "collective-permute-done(%collective-permute-start.{n}), "
        'metadata={{op_name="jit(split_components)/while/body/ppermute"}}')
PSUM = ("%psum_invariant.8 = s32[]{:T(128)} all-reduce(%convert.4), "
        "channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_5.5")
FUSION = ("%broadcast_select_fusion.14 = s32[256]{0:T(256)S(1)} "
          "fusion(%collective-permute-done.2, %and.22), kind=kLoop, "
          'metadata={op_name="jit(split_components)/all-reduce"}')


def metric(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py",
                               f"chipbench_metric_{name}")


def run_of(trace, tiles=2):
    config = json.loads(
        (BENCH / "configs" / "segment_v5b_d16_2x2.json").read_text())
    cell = types.SimpleNamespace(config=config, traffic={}, chips=CHIPS)
    run = harness.Run(cell, "TPU v5 lite",
                      json.loads((BENCH / "peaks.json").read_text()))
    run.tiles = [harness.TileRecord(i, 0, f"out/w{i}") for i in range(tiles)]
    run.trace = trace
    return run


def chip_trace(window=(0, 10_000)):
    """Each chip, each of two tiles: the gradient program (1,000 ns), then
    the components (2,000 ns) with three rounds of four exchanges (start
    and done, 10 ns each) and a flag of change (20 ns) in each."""
    ops, modules = {}, {}
    for chip in range(CHIPS):
        plane = f"/device:TPU:{chip}"
        ops[plane], modules[plane] = [], []
        for base in (0, 5_000):
            modules[plane] += [(f"{PROGRAMS[0]}(1)", base, base + 1_000),
                               (f"{PROGRAMS[1]}(2)", base + 1_000,
                                base + 3_000)]
            ops[plane].append((DONE.format(n=9, t="f32"),
                               base + 10, base + 20))  # in the gradient
            for r in range(3):
                t = base + 1_100 + 300 * r
                for d in range(4):
                    ops[plane] += [
                        (START.format(n=d), t + 40 * d, t + 40 * d + 10),
                        (DONE.format(n=d, t="s32"), t + 40 * d + 20,
                         t + 40 * d + 30)]
                ops[plane].append((PSUM, t + 200, t + 220))
            ops[plane].append((FUSION, base + 2_500, base + 2_900))
    return tr.Trace(ops, modules, [], window)


@pytest.mark.parametrize("name, opcode", [
    (START.format(n=0), "collective-permute-start"),
    (DONE.format(n=1, t="f32"), "collective-permute-done"),
    (PSUM, "all-reduce"),
    (FUSION, "fusion"),
    ("%x.1 = f32[1] parameter(0)", "parameter"),
    ("jit_split_components(2)", "")])
def test_opcode_is_read_from_the_instruction_not_its_name(name, opcode):
    assert hlo.opcode(name) == opcode


@pytest.mark.parametrize("name, own, operand", [
    (START.format(n=3), "%collective-permute-start.3", "%bitcast.14"),
    (DONE.format(n=3, t="f32"), "%collective-permute-done.3",
     "%collective-permute-start.3"),
    ("%c.1 = f32[] constant(0)", "%c.1", ""),
    ("jit_split_components(2)", "", "")])
def test_a_done_names_its_start(name, own, operand):
    assert hlo.result(name) == own
    assert hlo.first_operand(name) == operand


def test_exchanges_count_the_done_events_inside_the_components():
    value = metric("cc_merge_exchanges_per_tile").read(run_of(chip_trace()))
    assert value == 3 * 4  # rounds x directions, per chip and tile


def test_halo_time_is_collectives_in_flight_per_chip_and_tile():
    # per chip and tile: 1 permute done in the gradient whose start is not
    # in the trace (10 ns), 3 rounds of 4 exchanges from start to done
    # (30 ns each) and a flag (20 ns)
    want_ns = 10 + 3 * (4 * 30 + 20)
    value = metric("halo_ms_per_tile").read(run_of(chip_trace()))
    assert value == pytest.approx(want_ns / 1e6)


def test_halo_time_holds_a_transfer_that_overlaps_other_ops():
    """A start and its done 500 ns apart with a fusion between them: the
    500 ns count once, the fusion adds nothing, and a done that names
    another start does not close this one."""
    plane = "/device:TPU:0"
    ops = [(START.format(n=1), 100, 110), (FUSION, 150, 400),
           (DONE.format(n=2, t="s32"), 420, 430),
           (DONE.format(n=1, t="s32"), 590, 600)]
    trace = tr.Trace({plane: ops}, {plane: []}, [], (0, 1_000))
    run = run_of(trace, tiles=1)
    run.cell.chips = 1
    # start.1 -> done.1 is 500 ns; done.2 has no start and counts itself
    assert metric("halo_ms_per_tile").read(run) == pytest.approx(500 / 1e6)


def test_components_time_is_per_chip_and_tile():
    value = metric("split_cc_ms_per_tile").read(run_of(chip_trace()))
    assert value == pytest.approx(2_000 / 1e6)


def test_roofline_counts_one_shard_per_call():
    reader = metric("split_grad_mag_roofline")
    config = run_of(chip_trace()).config
    flops, nbytes = reader.cost(config["depth"], config["tile_px"],
                                config["bands"], config["mesh"])
    shard = 3072 * 3072
    assert flops == 8 * 16 * shard * 4 + 8 * 16 * shard
    assert nbytes == (4 * 16 * (shard + 2 * 3072) * 4
                      + 16 * (shard + 2 * 3072) + 8 * shard + shard)
    value = reader.read(run_of(chip_trace()))
    # 8 calls of 1,000 ns over the chip's peaks
    least = max(flops / 197e12, nbytes / 819e9)
    assert value == pytest.approx(100 * least / 1e-6)


@pytest.mark.parametrize("name", ["cc_merge_exchanges_per_tile",
                                  "halo_ms_per_tile", "split_cc_ms_per_tile",
                                  "split_grad_mag_roofline"])
def test_a_program_without_the_split_reads_nothing(name):
    """The parent's trace of a one-chip segmentation: no split programs,
    no collectives."""
    plane = "/device:TPU:0"
    trace = tr.Trace({plane: [("%fusion.1 = f32[1] a", 0, 50)]},
                     {plane: [("jit_grad_mag(1)", 0, 100)]}, [], (0, 1_000))
    assert metric(name).read(run_of(trace)) is None
    assert metric(name).read(run_of(None)) is None
