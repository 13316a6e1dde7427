"""SUMMA executor (``core/summa.py``): device time of collective
operations (the panel broadcasts' all-reduces) per call, in ms, from the
profiler trace; mean over the cell's chips.  Nothing to read where the
program runs no collective, as on a 1x1 mesh."""
from __future__ import annotations

from chipbench import xplane


def read(run):
    per_device = [
        xplane.total([(e.start, e.end) for e in ops if xplane.is_collective(e)])
        for ops in run.device_ops()
    ]
    if not any(per_device):
        return None
    return 1e3 * sum(per_device) / len(per_device) / run.calls
