"""Kernels (``kernels/tiled_matmul.py``): the least time the chip could
take for each chip's share of the window's products, over the summed
device time of the ``tiled_matmul`` Pallas kernel, in %.  Operations and
bytes from the call's shapes (``chipbench/work.py``)."""
from __future__ import annotations

from chipbench import kernel_roofline

KERNEL = "tiled_matmul"


def read(run):
    return kernel_roofline.read(run, KERNEL)
