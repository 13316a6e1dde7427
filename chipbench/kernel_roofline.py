"""A kernel's share of its roofline, read from the profiler trace.

Per chip: the least time for its share of the window's products (the
larger of operations over peak FLOP/s and bytes over HBM bandwidth,
``chipbench/work.py``) over the summed device time of the kernel's
events, which are found by name (``xplane.matching``).  The chips' least
times and kernel times are summed before the ratio.  Nothing to read
where the kernel never ran.
"""
from __future__ import annotations

from chipbench import work, xplane


def read(run, kernel: str):
    if run.peak is None:
        return None
    t_least = t_kernel = 0.0
    bounds = set()
    for (flops, nbytes), ops in zip(work.device_work(run.config), run.device_ops()):
        t_kernel += sum(e.duration for e in xplane.matching(ops, kernel))
        least, bound = work.roofline_seconds(flops, nbytes, run.peak)
        t_least += least * run.calls
        bounds.add(bound)
    if t_kernel <= 0.0:
        return None
    return 100.0 * t_least / t_kernel, f"{'/'.join(sorted(bounds))}-bound"
