"""Collective matmuls: the paper's engine embedded in the LM stack.

``project`` is the single entry point the model code uses for its big
projections (models/ffn.py).  It routes by ``ctx.matmul_strategy``:

* ``"xla"`` — plain einsum; GSPMD picks the collectives.  The default.
* ``"summa"`` — the task-based multiple-issue SUMMA schedule
  (core.summa, paper §3.2) over the (dp x tp) mesh slice, via the
  ``DistributedMatmul`` built by ``ctx.matmul()``.
* ``"allgather"`` — ``allgather_matmul`` below: a ring collective matmul
  over the TP axis that overlaps the activation all-gather with the
  per-chunk GEMMs using the same multiple-issue lookahead idiom as
  ``core.summa._exec_taskbased`` (paper Eq. (1)); it is the ``I = K``
  communication pattern realised as a pipeline instead of one bulk
  gather.  See EXPERIMENTS.md §Perf for the trade-off between the two
  non-XLA strategies.
* ``"auto"`` — per-shape pick by *simulated time*: the schedule
  autotuner (repro.sched.tuner) searches lookahead x k_blocks x strategy
  over the discrete-event simulator and executes the winner (its tuned
  lookahead included); the ring is routed to when its pipeline estimate
  beats the tuned SUMMA-family makespan.  This replaces the old static
  bytes tie-break — the ``MatmulPlan`` cost model remains the byte
  source, the simulator adds overlap and imbalance.

``project`` also accepts an optional block mask over the weight
(``w_mask``, or one registered in ``ctx.weight_block_masks``): the
planned schedule then prunes dead K panels and, with the Pallas local
kernel, runs the per-device block-CSR BSMM — the paper's block-sparse
path embedded in the LM.  The xla path zeroes masked blocks so every
strategy computes the same masked product.

All strategies accumulate in fp32 and return the activation dtype, so
swapping them changes only the schedule, not the arithmetic contract.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["project", "allgather_matmul"]


def _mask_weight(w: jax.Array, w_mask: np.ndarray) -> jax.Array:
    """Zero masked blocks of a (d_in, d_out) weight (einsum-path parity)."""
    from repro.core.summa import _apply_block_mask

    return _apply_block_mask(w, np.asarray(w_mask, dtype=bool))


def _ring_eligible(ctx, x2: jax.Array, w: jax.Array) -> bool:
    return (
        ctx.tp_size > 1
        and x2.shape[0] % (ctx.dp_size * ctx.tp_size) == 0
        and w.shape[-1] % ctx.tp_size == 0
    )


def project(
    x: jax.Array,
    w: jax.Array,
    ctx,
    *,
    w_mask: np.ndarray | None = None,
) -> jax.Array:
    """``x @ w`` with the context's matmul strategy.

    ``x``: (..., d_in) activations; ``w``: (d_in, d_out) kernel.  Leading
    dims are flattened into SUMMA's M dimension and restored afterwards.
    ``w_mask`` is an optional (Kblk, Nblk) block mask over the weight;
    when omitted, ``ctx.weight_block_masks`` is consulted for the weight
    shape.  Meshless contexts always take the einsum path so smoke tests
    and eval_shape tracing never build collectives.
    """
    if w_mask is None:
        w_mask = ctx.weight_mask(w.shape)
    if ctx.matmul_strategy == "xla" or not ctx.has_mesh or ctx.pure_dp:
        if w_mask is not None:
            w = _mask_weight(w, w_mask)
        return jnp.einsum(
            "...d,df->...f", x, w, preferred_element_type=jnp.float32
        ).astype(x.dtype)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    strategy = ctx.matmul_strategy
    ring_ok = _ring_eligible(ctx, x2, w)
    tune = False
    if strategy == "auto":
        if w_mask is not None:
            # Masked plans always execute the planned broadcast schedule
            # (DAG or BSMM) — the gather-style executors are sparsity-
            # blind; the tuner still picks the lookahead window.
            strategy = "summa"
            tune = True
        else:
            # One cached tuned plan per shape: the simulator-searched
            # schedule (strategy x k_blocks x lookahead), vs. the ring's
            # pipeline estimate when the ring is eligible.
            from repro.sched.tuner import ring_makespan

            plan = ctx.matmul().plan(
                x2.shape[0], x2.shape[1], w.shape[1],
                itemsize=x2.dtype.itemsize, tune=True,
            )
            if ring_ok and ring_makespan(plan) < plan.tuned["makespan_s"]:
                strategy = "ring"
            else:
                strategy = "summa"
                tune = True
    if strategy in ("allgather", "ring") and ring_ok and w_mask is None:
        out = allgather_matmul(
            x2, w, mesh=ctx.mesh, axis=ctx.tp_axis, batch_axes=ctx.dp_axes
        )
    else:
        summa_strategy = {"summa": None, "ring": None}.get(strategy, strategy)
        out = ctx.matmul()(
            x2, w, b_mask=w_mask, strategy=summa_strategy, tune=tune
        )
    return out.reshape(*lead, w.shape[-1])


def allgather_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    mesh: Mesh,
    axis: str,
    batch_axes: tuple[str, ...] = (),
    lookahead: int = 2,
    accum_dtype=jnp.float32,
) -> jax.Array:
    """Ring all-gather matmul with multiple-issue lookahead.

    The sequence-parallel <-> tensor-parallel boundary matmul: ``x``
    (M, K) arrives with M sharded over ``(*batch_axes, axis)`` and ``w``
    (K, N) column-sharded over ``axis`` (P shards).  Instead of one bulk
    all-gather of ``x`` followed by one GEMM, the activation chunks
    travel the ring one hop per step while each device multiplies the
    chunk it already holds against its weight columns — transfer ``g+1``
    is issued before GEMM ``g`` consumes its buffer, so the two overlap
    exactly as the prefetch pipeline in
    ``core.summa._exec_taskbased``.  ``lookahead`` is the pipeline
    depth I of paper Eq. (1): ``I`` ring hops are in flight at any time
    (clamped to the shard count).

    There is no redundant compute: each device produces the
    (M / |batch_axes|, N / P) output tile of its (batch, ring-group)
    coordinate, so global FLOPs are exactly 2·M·K·N.  Under reverse-mode
    AD the transpose of the activation all-gather is a reduce-scatter of
    the cotangent, so the backward pass is the matching overlapped
    reduce-scatter matmul for free.

    Returns (M, N), M sharded over ``batch_axes`` and N over ``axis``,
    in ``x.dtype``.
    """
    (m, k), (k2, n) = x.shape, w.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    p = mesh.shape[axis]
    b_size = math.prod(mesh.shape[a] for a in batch_axes)
    if m % (b_size * p):
        raise ValueError(
            f"M={m} must be divisible by the M sharding "
            f"({b_size} x {p} shards)"
        )
    if n % p:
        raise ValueError(
            f"N={n} must be divisible by the {axis!r} axis size ({p})"
        )
    m_loc = m // (b_size * p)  # ring-chunk rows held per device
    la = max(1, min(lookahead, p))
    perm = [(i, (i + 1) % p) for i in range(p)]

    def fn(x_loc, w_loc):
        me = jax.lax.axis_index(axis)

        # Prologue: put ``la`` ring hops in flight before any GEMM.
        bufs = [x_loc]
        for _ in range(la - 1):
            bufs.append(jax.lax.ppermute(bufs[-1], axis, perm))
        buf = jnp.stack(bufs)  # (I, m_loc, k)

        def partial(acc, g, x_chunk):
            src = (me - g) % p  # original owner of the chunk in hand
            tile = jnp.matmul(x_chunk, w_loc, preferred_element_type=accum_dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                acc, tile, src * m_loc, axis=0
            )

        def body(carry, g):
            acc, b = carry
            nxt = jax.lax.ppermute(b[-1], axis, perm)  # hop g+I: independent
            acc = partial(acc, g, b[0])
            b = jnp.concatenate([b[1:], nxt[None]], axis=0)
            return (acc, b), None

        acc = jnp.zeros((p * m_loc, w_loc.shape[1]), accum_dtype)
        steady = p - la
        if steady > 0:
            (acc, buf), _ = jax.lax.scan(
                body, (acc, buf), jnp.arange(steady)
            )
        # Epilogue: drain the I buffered chunks.
        for i in range(la):
            acc = partial(acc, steady + i, buf[i])
        return acc.astype(x.dtype)

    m_entry = (*batch_axes, axis) if batch_axes else axis
    out_m_entry = (
        batch_axes if len(batch_axes) > 1 else batch_axes[0]
    ) if batch_axes else None
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(m_entry, None), P(None, axis)),
        out_specs=P(out_m_entry, axis),
        check_vma=False,
    )(x, w)
