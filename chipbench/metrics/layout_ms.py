"""Data layout around the kernel (``core/api.py``, ``core/summa.py``):
device time per call, in ms, of every operation that is neither the
``tiled_matmul`` kernel nor a collective (padding gathers and selects,
compaction, the fp32 accumulator's cast to the output dtype, zeroing and
adding panels), from the profiler trace; mean over the cell's chips.
Nothing to read where every operation is a kernel or a collective."""
from __future__ import annotations

from chipbench import xplane

KERNEL = "tiled_matmul"


def _layout(e) -> bool:
    return KERNEL not in e.op and not xplane.is_collective(e)


def read(run):
    per_device = [
        xplane.total([(e.start, e.end) for e in ops if _layout(e)])
        for ops in run.device_ops()
    ]
    if not any(per_device):
        return None
    return 1e3 * sum(per_device) / len(per_device) / run.calls
