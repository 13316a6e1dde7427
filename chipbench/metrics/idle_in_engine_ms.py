"""Device: idle time per call, in ms, that falls inside the engine's
``repro.matmul`` spans, with each chip's operations moved onto the host's
clock by the midpoint of its offset bracket (``chipbench/clock.py``);
mean over the cell's chips.  The part of each idle gap in which the chip
waits on the engine's own host code rather than the caller's.  The note
gives each chip's bracket and the value at both ends of it.  Nothing to
read without ``repro.matmul`` spans or without a bracket."""
from __future__ import annotations

from chipbench import clock, xplane


def _idle_in(run, calls, shifts):
    per_chip = []
    outside = xplane.subtract([run.window], calls)
    for ops, d in zip(run.device_ops(), shifts):
        idle = xplane.subtract([run.window], [(e.start + d, e.end + d) for e in ops])
        per_chip.append(xplane.total(xplane.subtract(idle, outside)))
    return 1e3 * sum(per_chip) / len(per_chip) / run.calls


def read(run):
    lo, hi = run.window
    calls = [(e.start, e.end) for e in run.trace.spans("repro.matmul") if lo <= e.start and e.end <= hi]
    if not calls or not run.calls:
        return None
    offsets = clock.offsets(run)
    if offsets is None:
        return None
    value = _idle_in(run, calls, [o.mid for o in offsets])
    at_lo = _idle_in(run, calls, [o.lo for o in offsets])
    at_hi = _idle_in(run, calls, [o.hi for o in offsets])
    brackets = "; ".join(f"chip {i}: {o}" for i, o in enumerate(offsets))
    return value, f"offset {brackets}; {at_lo:.4f} ms at the low ends, {at_hi:.4f} ms at the high ends"
