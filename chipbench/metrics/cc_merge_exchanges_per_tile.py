"""Label exchanges per chip per tile: the collective-permutes completed
(opcode ``collective-permute-done``, or ``collective-permute`` where it is
not split into start and done) inside runs of the jitted split connected
components.  Each merge round exchanges in four directions, so this reads
four times the rounds that the program's ``repro.cc_merge`` span
counts."""

from chipbench import hlo, tracereduce

PROGRAM = "jit_split_components"
EXCHANGES = ("collective-permute-done", "collective-permute")


def read(run):
    if run.trace is None or not run.tiles_done:
        return None
    window = run.trace.window
    count = 0
    for device, modules in run.trace.modules.items():
        runs = [(s, e) for n, s, e in tracereduce.clip(modules, window)
                if n == PROGRAM or n.startswith(PROGRAM + "(")]
        if not runs:
            continue
        for name, s, e in tracereduce.clip(
                run.trace.device_ops.get(device, []), window):
            if (hlo.opcode(name) in EXCHANGES
                    and any(a <= s and e <= b for a, b in runs)):
                count += 1
    if not count:
        return None
    return count / run.cell.chips / run.tiles_done
