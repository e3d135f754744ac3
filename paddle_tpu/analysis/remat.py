"""Involuntary-rematerialization detector.

When GSPMD cannot reshard a tensor between two incompatible layouts it
falls back to replicate-then-repartition — "Involuntary full
rematerialization" — the bandwidth cliff the zero-remat invariant (the
fused-LCE hybrid recipe's protected property)
forbids. XLA only reports it as an error line on fd 2 during SPMD
partitioning, so the detector greps the stderr captured while THIS
target compiled (ir.LoweredTarget records it) and returns one
structured event per warning. This generalizes the one-off capfd
assertions that tests/test_zero_ir.py used to hand-roll per model
shape.
"""
from __future__ import annotations

import re

__all__ = ["RematEvent", "detect_involuntary_remat", "REMAT_MARKER"]

REMAT_MARKER = "Involuntary full rematerialization"

# "... for HLO operation: %param = f32[64,64]{1,0} parameter(20), ..."
_OP_RE = re.compile(r"for HLO operation:\s*(%[^\n]+)")
_SHARDING_RE = re.compile(
    r"go from sharding (\{[^}]*\}(?:[^\n]*?\})?) to "
    r"(\{[^}]*\}(?:[^\n]*?\})?)")


class RematEvent:
    """One involuntary-remat fallback: the HLO op XLA replicated and the
    (from, to) shardings it could not bridge."""

    __slots__ = ("hlo_op", "from_sharding", "to_sharding", "raw")

    def __init__(self, hlo_op, from_sharding, to_sharding, raw):
        self.hlo_op = hlo_op
        self.from_sharding = from_sharding
        self.to_sharding = to_sharding
        self.raw = raw

    def __repr__(self):
        return (f"RematEvent(op={self.hlo_op!r}, "
                f"from={self.from_sharding!r}, to={self.to_sharding!r})")


def detect_involuntary_remat(compile_stderr):
    """Parse the fd-2 text captured during compilation into a list of
    :class:`RematEvent` (empty list = the zero-remat invariant holds)."""
    events = []
    for line in compile_stderr.splitlines():
        if REMAT_MARKER not in line:
            continue
        op = _OP_RE.search(line)
        sh = _SHARDING_RE.search(line)
        events.append(RematEvent(
            hlo_op=op.group(1).strip() if op else "",
            from_sharding=sh.group(1) if sh else "",
            to_sharding=sh.group(2) if sh else "",
            raw=line.strip(),
        ))
    return events
