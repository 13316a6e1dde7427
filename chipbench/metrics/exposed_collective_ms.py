"""SUMMA executor (``core/summa.py``): the part of the collectives' device
time per call, in ms, in which no other operation runs on that chip; mean
over the cell's chips.  What multiple issue has not hidden.  Nothing to
read where the program runs no collective."""
from __future__ import annotations

from chipbench import xplane


def read(run):
    per_device = []
    has_collectives = False
    for ops in run.device_ops():
        coll = [(e.start, e.end) for e in ops if xplane.is_collective(e)]
        other = [(e.start, e.end) for e in ops if not xplane.is_collective(e)]
        has_collectives = has_collectives or bool(coll)
        per_device.append(xplane.total(xplane.subtract(coll, other)))
    if not has_collectives:
        return None
    return 1e3 * sum(per_device) / len(per_device) / run.calls
