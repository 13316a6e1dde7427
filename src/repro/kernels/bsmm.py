"""Block-sparse matmul (BSMM) Pallas TPU kernel.

The compute payload of the paper's "block-sparse tensor computing": C =
A·B where A carries a block-level sparsity structure.  The block map is a
padded CSR-of-blocks (core.sparsity.BlockCSR) delivered through *scalar
prefetch*, so the kernel's BlockSpec index_maps chase the sparse column
indices and only nonzero A blocks are ever copied into VMEM or multiplied
— FLOPs and HBM traffic scale with the block fill-in, not the dense
shape.

Grid layout: ``(M_blocks, N_blocks, S)`` with ``S`` = max nonzeros per
block row (padded with ``-1`` sentinels).  The S axis is "arbitrary"
(sequential) and accumulates into VMEM scratch; sentinel steps are
masked with ``pl.when`` and their (deduped) loads point at block 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import check_blocks

__all__ = ["bsmm_kernel", "bsmm_pallas"]


def bsmm_kernel(
    cols_ref,  # scalar prefetch: (M_blocks, S) int32, -1 padded
    a_ref,
    b_ref,
    c_ref,
    acc_ref,
    *,
    s_steps: int,
):
    i = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(cols_ref[i, s] >= 0)
    def _accum():
        acc_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(s == s_steps - 1)
    def _flush():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "interpret", "out_dtype"),
)
def bsmm_pallas(
    a: jax.Array,
    b: jax.Array,
    cols: jax.Array,  # (M_blocks, S) int32 padded col map
    *,
    bm: int,
    bk: int,
    bn: int,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B where A's block-sparsity is given by the padded col map.

    ``a``: (M, K) dense-stored, blocks of (bm, bk); blocks absent from
    ``cols`` are *skipped* (never loaded / multiplied).  ``cols[i, s]`` is
    the s-th nonzero block column of block row i, or -1.
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    if m % bm or k % bk or n % bn:
        raise ValueError(f"shape must divide tiles ({bm},{bk},{bn})")
    m_blocks = m // bm
    s_steps = cols.shape[1]
    if cols.shape[0] != m_blocks:
        raise ValueError(
            f"col map rows {cols.shape[0]} != M blocks {m_blocks}"
        )
    if not interpret:
        check_blocks(
            "bsmm", ((bm, bk), a.shape), ((bk, bn), b.shape), ((bm, bn), (m, n))
        )
    out_dtype = out_dtype or a.dtype
    grid = (m_blocks, n // bn, s_steps)

    def a_index(i, j, s, cols_ref):
        kk = jnp.maximum(cols_ref[i, s], 0)  # sentinel -> block 0 (masked)
        return (i, kk)

    def b_index(i, j, s, cols_ref):
        kk = jnp.maximum(cols_ref[i, s], 0)
        return (kk, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_index),
            pl.BlockSpec((bk, bn), b_index),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s, cols_ref: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(bsmm_kernel, s_steps=s_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="bsmm",
    )(cols, a, b)
