"""The plain reference, the comparison that decides ``correct``, and its
control.

The reference is ``jnp.dot`` of the same bf16 operands at HIGHEST
precision with an fp32 result.  It imports
nothing of the engine.  Each device checks its own shard of C: it gathers
B's column stripe once, and then, one block of its rows at a time, the
matching rows of A, so that beside A, B and C only B's stripe and one
block of reference rows are alive.

Two numbers are compared, both over every tile of ``block`` x ``block``
elements of C on every device's shard, and both the largest over all
tiles (NaN and inf read as inf):

* ``worst_tile_rel_err``: ``||C_t - R_t||_F / ||R_t||_F``.  A wrong tile,
  a missing panel or a lost exchange shows in it at full size, where a
  global norm would dilute it by the number of tiles.
* ``worst_element_err``: ``max |C_ij - R_ij|`` over the tile's RMS,
  ``||R_t||_F / block``.  One altered element shows here, where it moves
  its tile's norm by less than rounding does.

The control is the step below the configuration's bf16: int8 operands
(symmetric, one scale per row of A and per column of B), an int32
product, dequantised and rounded to the output dtype.  It has to fail a
limit; ``chipbench/control.py`` measures both on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = ["compare", "int8_control", "row_block", "NUMBERS"]

#: the numbers ``compare`` returns, in order
NUMBERS = ("worst_tile_rel_err", "worst_element_err")
SHARDED = P("data", "model")
AXES = ("data", "model")


def row_block(n: int, block: int, mesh) -> int:
    """Rows of a device's shard of C checked at a time: an eighth of the
    shard's rows or less, a multiple of ``block`` that divides them."""
    m_loc = n // mesh.shape["data"]
    rows = max(block, (m_loc // 8) // block * block)
    while m_loc % rows:
        rows -= block
    return rows


def _rows_of_a(a_loc, r0, rows: int):
    """Rows ``r0:r0+rows`` of this device's stripe of A, whole across K."""
    a_r = jax.lax.dynamic_slice_in_dim(a_loc, r0, rows, 0)
    return jax.lax.all_gather(a_r, "model", axis=1, tiled=True)


@functools.lru_cache(maxsize=None)
def _compare_fn(rows: int, block: int, mesh):
    def local(a_loc, b_loc, c_loc):
        b_col = jax.lax.all_gather(b_loc, "data", axis=0, tiled=True)
        n_loc = c_loc.shape[1]
        shape = (rows // block, block, n_loc // block, block)

        def body(step, worst):
            r0 = step * rows
            ref = jnp.dot(
                _rows_of_a(a_loc, r0, rows), b_col,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            got = jax.lax.dynamic_slice_in_dim(c_loc, r0, rows, 0).astype(jnp.float32)
            diff = (got - ref).reshape(shape)
            r_norm = jnp.sqrt(jnp.sum(jnp.square(ref).reshape(shape), axis=(1, 3)))
            errs = jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(diff), axis=(1, 3))) / r_norm,
                jnp.max(jnp.abs(diff), axis=(1, 3)) * block / r_norm,
            ])
            errs = jnp.where(jnp.isnan(errs), jnp.inf, errs)
            return jnp.maximum(worst, jnp.max(errs, axis=(1, 2)))

        worst = jax.lax.fori_loop(
            0, c_loc.shape[0] // rows, body, jnp.zeros((len(NUMBERS),), jnp.float32)
        )
        return jax.lax.pmax(worst, AXES)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(SHARDED,) * 3, out_specs=P(),
        check_vma=False,
    ))


def compare(a, b, c, block: int, mesh) -> dict:
    """``{number: value}`` of ``c`` against the reference A @ B."""
    rows = row_block(a.shape[0], block, mesh)
    worst = np.asarray(_compare_fn(rows, block, mesh)(a, b, c))
    return {name: float(v) for name, v in zip(NUMBERS, worst)}


def _quantize(x, axis: int):
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8), scale


@functools.lru_cache(maxsize=None)
def _int8_fn(rows: int, out_dtype: str, mesh):
    def local(a_loc, b_loc):
        b_q, b_scale = _quantize(jax.lax.all_gather(b_loc, "data", axis=0, tiled=True), 0)
        out = jnp.zeros((a_loc.shape[0], b_q.shape[1]), out_dtype)

        def body(step, out):
            r0 = step * rows
            a_q, a_scale = _quantize(_rows_of_a(a_loc, r0, rows), 1)
            acc = jnp.dot(a_q, b_q, preferred_element_type=jnp.int32)
            c_r = (acc.astype(jnp.float32) * a_scale * b_scale).astype(out_dtype)
            return jax.lax.dynamic_update_slice_in_dim(out, c_r, r0, 0)

        return jax.lax.fori_loop(0, a_loc.shape[0] // rows, body, out)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(SHARDED, SHARDED), out_specs=SHARDED,
        check_vma=False,
    ))


def int8_control(a, b, block: int, out_dtype, mesh):
    """C computed from int8 operands: the control that has to fail."""
    rows = row_block(a.shape[0], block, mesh)
    return _int8_fn(rows, jnp.dtype(out_dtype).name, mesh)(a, b)
