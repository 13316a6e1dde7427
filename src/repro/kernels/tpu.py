"""What the TPU's Pallas compiler asks of a kernel launch.

Every ``*_pallas`` entry point takes ``interpret``: ``True`` runs the
kernel body in Python (CPU tests), ``False`` lowers it through Mosaic to
a ``tpu_custom_call``.  :func:`interpret_mode` is the one place the
choice is made from the backend, and :func:`check_blocks` refuses a block
the chip cannot tile before Mosaic sees it, naming the rule.
"""
from __future__ import annotations

import jax

__all__ = ["interpret_mode", "check_blocks"]


def interpret_mode() -> bool:
    """Run Pallas kernels interpreted unless the backend is a TPU."""
    return jax.default_backend() != "tpu"


def check_blocks(kernel: str, *blocks) -> None:
    """Raise unless each ``(block_shape, array_shape)`` pair tiles on TPU.

    Mosaic's rule: each of the last two block dimensions is divisible by
    8 (second-to-last) and 128 (last), or spans that whole array
    dimension.  Interpret mode has no such rule, so callers check only
    when compiling for the chip.
    """
    for block, shape in blocks:
        (b1, b0), (a1, a0) = tuple(block)[-2:], tuple(shape)[-2:]
        if (b0 % 128 and b0 != a0) or (b1 % 8 and b1 != a1):
            raise ValueError(
                f"{kernel}: block {tuple(block)} of a {tuple(shape)} operand "
                "cannot be tiled on TPU: the last two block dimensions must "
                "be divisible by 8 and 128 respectively, or equal the "
                "array's dimensions"
            )
