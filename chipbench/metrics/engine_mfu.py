"""Whole call: useful FLOPs of the window's products over the window, the
cell's chips and their bf16 peak, in %.  Useful FLOPs are 2 N^3
(``chipbench/work.py``); padding counts nothing."""
from __future__ import annotations

from chipbench import work


def read(run):
    if run.peak is None or not run.calls:
        return None
    flops = work.useful_flops(run.config) * run.calls
    return 100.0 * flops / (run.window_s * run.chips * run.peak["bf16_flops_per_s"])
