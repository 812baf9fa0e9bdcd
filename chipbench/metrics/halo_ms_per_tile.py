"""Milliseconds per chip per tile in which a collective (opcode
``collective-permute``, ``all-reduce`` or ``all-gather``) is in flight:
the halo exchanges, the merge's label exchanges and its flag of change,
over the traced window.  A collective split into start and done counts
from the start's beginning to its done's end, so the transfer between
them counts even where other ops run meanwhile; a half whose other half
lies outside the window counts as itself."""

from chipbench import hlo, tracereduce


def in_flight(events):
    """The intervals of one chip's collectives: each sync op as itself,
    each start joined to the done that names it as its operand."""
    started, spans = {}, []
    for name, s, e in sorted(events, key=lambda ev: ev[1]):
        op = hlo.opcode(name)
        if not op.startswith(hlo.COLLECTIVES):
            continue
        if op.endswith("-start"):
            started[hlo.result(name)] = (name, s, e)
        elif op.endswith("-done") and hlo.first_operand(name) in started:
            spans.append((name, started.pop(hlo.first_operand(name))[1], e))
        else:
            spans.append((name, s, e))
    return spans + list(started.values())


def read(run):
    if run.trace is None or not run.tiles_done:
        return None
    ns = 0
    for events in run.trace.device_ops.values():
        mine = in_flight(tracereduce.clip(events, run.trace.window))
        ns += sum(e - s for s, e in tracereduce.union(mine))
    if not ns:
        return None
    return ns / 1e6 / run.cell.chips / run.tiles_done
