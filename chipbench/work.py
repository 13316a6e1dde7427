"""Operations and bytes of one product, per device, from the call's shapes.

These are the work the algorithm needs, never what a kernel happens to
move: a device of a ``p_row x p_col`` grid owns an ``(n/p_row, n/p_col)``
block of C and has to multiply its row stripe of A by its column stripe
of B.  Bytes count those stripes read once and C written once in its own
dtype.  So a kernel's share of its roofline can only be understated,
never overstated.  Useful FLOPs are ``2 N^3``; padding counts nothing.
"""
from __future__ import annotations

import numpy as np

__all__ = ["device_work", "useful_flops", "roofline_seconds"]


def device_work(config: dict) -> list[tuple[float, float]]:
    """``(flops, bytes)`` of one product on each device, row-major over
    the mesh."""
    n = int(config["n"])
    p_row, p_col = config["mesh"]
    item = np.dtype(config["dtype"]).itemsize
    out_item = np.dtype(config["out_dtype"]).itemsize
    m_loc, n_loc = n // p_row, n // p_col
    flops = 2.0 * m_loc * n * n_loc
    nbytes = (m_loc * n + n * n_loc) * item + m_loc * n_loc * out_item
    return [(flops, nbytes)] * (p_row * p_col)


def useful_flops(config: dict) -> float:
    """FLOPs of one whole product that the answer needs."""
    return sum(f for f, _ in device_work(config))


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
