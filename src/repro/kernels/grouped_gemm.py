"""Grouped (block-diagonal) GEMM Pallas TPU kernel for MoE experts.

MoE expert compute *is* block-sparse matrix multiplication — the paper's
target domain: tokens routed to expert e multiply only W[e], i.e. a
block-diagonal sparsity over the (token-group × expert) grid with
*nonuniform* group sizes (the router decides), exactly the irregular
blocking the paper simulates with random block sizes.

Layout contract (MegaBlocks-style, TPU-adapted): tokens arrive sorted by
expert and padded so every ``bt``-row tile is owned by a single expert;
``tile_expert[t]`` names that expert and is scalar-prefetched so the W
BlockSpec chases it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import check_blocks

__all__ = ["grouped_gemm_kernel", "grouped_gemm_pallas"]


def grouped_gemm_kernel(te_ref, x_ref, w_ref, y_ref, acc_ref, *, k_tiles):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_tiles - 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bt", "bk", "bn", "interpret", "out_dtype")
)
def grouped_gemm_pallas(
    x: jax.Array,  # (T, D) tokens, tile-aligned groups
    w: jax.Array,  # (E, D, F) expert weights
    tile_expert: jax.Array,  # (T // bt,) int32
    *,
    bt: int,
    bk: int,
    bn: int,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    t, d = x.shape
    e, d2, f = w.shape
    if d != d2:
        raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
    if t % bt or d % bk or f % bn:
        raise ValueError(f"shape must divide tiles ({bt},{bk},{bn})")
    if tile_expert.shape != (t // bt,):
        raise ValueError("tile_expert must have one entry per token tile")
    if not interpret:
        check_blocks(
            "grouped_gemm",
            ((bt, bk), x.shape), ((bk, bn), (d, f)), ((bt, bn), (t, f)),
        )
    out_dtype = out_dtype or x.dtype
    k_tiles = d // bk
    grid = (t // bt, f // bn, k_tiles)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bk), lambda ti, n, k, te: (ti, k)),
            pl.BlockSpec((1, bk, bn), lambda ti, n, k, te: (te[ti], k, n)),
        ],
        out_specs=pl.BlockSpec((bt, bn), lambda ti, n, k, te: (ti, n)),
        scratch_shapes=[pltpu.VMEM((bt, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(grouped_gemm_kernel, k_tiles=k_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, f), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="grouped_gemm",
    )(tile_expert, x, w)
