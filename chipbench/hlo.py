"""What a device op's event name says of its HLO instruction.

On a TPU the device trace names each op by its whole instruction,
``%name = type opcode(operands), attributes``.  The name before `` = ``
is the compiler's and need not say what the op does (a cross-chip sum can
read ``%psum_invariant.8 = s32[] all-reduce(...)``); the opcode does."""

import re

#: the opcode and the first operand: the first word right before a "("
#: that follows a space, and the first ``%name`` inside the parentheses
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\((%[^,)\s]+)?")
#: opcodes of ops that move data between chips, with their async halves
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather")


def opcode(name: str) -> str:
    """The instruction's opcode, or "" where the name holds none."""
    found = _OPCODE.search(name.partition(" = ")[2])
    return found.group(1) if found else ""


def result(name: str) -> str:
    """The instruction's own name (``%collective-permute-start.3``), or ""
    where the event name is no instruction."""
    own, eq, _ = name.partition(" = ")
    return own.strip() if eq else ""


def first_operand(name: str) -> str:
    """The name of the instruction's first operand, or "": the ``-start``
    that a ``-done`` completes."""
    found = _OPCODE.search(name.partition(" = ")[2])
    return (found.group(2) or "") if found else ""
