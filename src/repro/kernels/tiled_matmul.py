"""Dense tiled matmul Pallas TPU kernel.

The local block-multiply engine of the task-based SUMMA: the intra-node
"tasks" of the paper (TBB threads working on sub-blocks of the local
result, Fig. 3) map onto the Pallas grid — each (i, j) grid cell owns one
C sub-block, the K dimension is the innermost ("arbitrary") grid axis and
accumulates into a VMEM scratch, so different C sub-blocks are independent
exactly like the paper's decomposed rank-k-update tasks.

Block shapes are MXU-aligned (multiples of 128 on the minor dims by
default); fp32 accumulation in VMEM scratch; output cast to the operand
dtype.  A launch whose blocks need more VMEM than Mosaic's default scoped
limit asks for what they need (:func:`vmem_bytes`) and a quarter more for
Mosaic's own scratch, up to ``MAX_VMEM``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import check_blocks

__all__ = ["tiled_matmul_kernel", "tiled_matmul_pallas", "vmem_bytes"]

DEFAULT_BM = 256
DEFAULT_BK = 256
DEFAULT_BN = 256

#: VMEM Mosaic gives a kernel on a v5e core unless it asks for more.
DEFAULT_SCOPED_VMEM = 16 * 2**20
#: The most VMEM one launch asks for, of a v5e core's 128 MiB.
MAX_VMEM = 64 * 2**20


def vmem_bytes(bm: int, bk: int, bn: int, in_itemsize: int, out_itemsize: int) -> int:
    """VMEM one launch holds: double-buffered A and B blocks, the
    double-buffered C block and the fp32 accumulator."""
    return (
        2 * (bm * bk + bk * bn) * in_itemsize
        + 2 * bm * bn * out_itemsize
        + 4 * bm * bn
    )


def tiled_matmul_kernel(a_ref, b_ref, c_ref, acc_ref, *, k_tiles: int):
    """One (i, j, k) grid cell: acc += A[i,k] @ B[k,j]."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_tiles - 1)
    def _flush():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bn", "interpret", "out_dtype")
)
def tiled_matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with explicit VMEM tiling. Shapes must divide the tiles."""
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    if m % bm or k % bk or n % bn:
        raise ValueError(
            f"shape ({m},{k},{n}) must divide tiles ({bm},{bk},{bn}); "
            "use kernels.ops.tiled_matmul for auto-padding"
        )
    if not interpret:
        check_blocks(
            "tiled_matmul",
            ((bm, bk), a.shape), ((bk, bn), b.shape), ((bm, bn), (m, n)),
        )
    out_dtype = out_dtype or a.dtype
    need = vmem_bytes(
        bm, bk, bn, a.dtype.itemsize, jnp.dtype(out_dtype).itemsize
    )
    if need > MAX_VMEM:
        raise ValueError(
            f"tiles ({bm},{bk},{bn}) need {need} bytes of VMEM, "
            f"over the {MAX_VMEM} a launch may ask for"
        )
    limit = need + need // 4  # Mosaic's own scratch: 2-12% more, v5e
    k_tiles = k // bk
    grid = (m // bm, n // bn, k_tiles)
    return pl.pallas_call(
        functools.partial(tiled_matmul_kernel, k_tiles=k_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=(
                min(limit, MAX_VMEM) if limit > DEFAULT_SCOPED_VMEM else None
            ),
        ),
        interpret=interpret,
        name="tiled_matmul",
    )(a, b)
