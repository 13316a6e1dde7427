"""Device: the share of the window, in %, in which no operation runs on a
chip, from the profiler trace; mean over the cell's chips."""
from __future__ import annotations

from chipbench import xplane


def read(run):
    ops = run.device_ops()
    if not any(ops):
        return None
    idle = [1.0 - xplane.total([(e.start, e.end) for e in d]) / run.window_s for d in ops]
    return 100.0 * sum(idle) / len(idle)
