"""Task-based 2D SUMMA, adapted to static SPMD on TPU meshes.

Implements the paper's algorithm family as `shard_map` programs over a
2-D slice ``(row_axis, col_axis)`` of a device mesh.  Since the
``MatmulPlan`` refactor every entry point builds one static plan
(``core.plan.plan_matmul``) and hands it to ``execute_plan``; the
strategies below are *plan interpreters*:

* ``_exec_procedural`` — the paper's *baseline* (its Algorithm 1 without
  the non-blocking part): a sequential K-step loop; each step broadcasts
  one column-panel of A along grid rows and one row-panel of B along grid
  columns, then performs the rank-k update.  Iterations are serialized
  through the loop carry — collectives cannot overlap compute of other
  iterations, mirroring procedural SUMMA's sequence dependencies (paper
  Fig. 1, dashed edges).

* ``_exec_taskbased`` — the paper's contribution (§3.2), statically
  scheduled: *multiple-issue* lookahead of ``I`` iterations (paper Eq. 1)
  realised as an ``I``-deep panel-prefetch pipeline.  The broadcast for
  step ``k+I`` is issued in iteration ``k`` and is data-independent of
  every rank-k update in flight, so XLA's latency-hiding scheduler
  overlaps ICI transfers with MXU compute — the static analogue of
  MADNESS tasks firing on data availability.

* ``_exec_allgather`` — the ``I = K_steps`` extreme of Eq. 1 (every
  broadcast issued up-front), i.e. one all-gather per operand followed by
  a local GEMM.  Maximum memory, minimum exposure to per-step latency.

* ``_exec_sparse_dag`` — static block-sparsity: panels the plan marks
  globally dead are *skipped at trace time* (no broadcast, no compute),
  and surviving rank-k updates run on masked operands.  Communication
  volume shrinks with the block fill-in.

* ``_exec_sparse_bsmm`` — the plan's per-device refinement: live panels
  are gathered once, then the Pallas scalar-prefetch BSMM kernel
  (kernels/bsmm.py) consumes *this device's* CSR column map — blocks
  dead for this grid row/column are never loaded or multiplied, so local
  FLOPs scale with the per-device fill-in, finer than global pruning.

* ``_exec_sparse_pull`` — the one-sided SpGEMM route
  (``plan.comm_mode="pull"``, repro.spgemm): gather-by-index emulation
  of RDMA panel gets; the fetch cost model lives in the task graph.
  A-/B-stationary plans (``plan.stationarity``) run a single local
  contraction with a C reduce-scatter instead of the K pipeline, and
  ``plan.c_mask`` zeroes dead output blocks on every route.

Broadcast realisation: a panel broadcast from its owner is expressed as a
masked ``psum`` ("broadcast-as-allreduce"), the standard static-SPMD
idiom.  It costs ~2× the bytes of an optimal tree broadcast; the
``allgather`` strategy is the bandwidth-optimal endpoint.  See
EXPERIMENTS.md §Perf for the measured trade-off.

Data layout: A is ``(M, K)`` sharded (row_axis, col_axis); B is ``(K, N)``
sharded (row_axis, col_axis); C is ``(M, N)`` sharded (row_axis,
col_axis).  The K dimension is split into ``k_blocks`` panels, each
contained within a single device's shard (``k_blocks`` must be a multiple
of both grid dims unless it equals them).  Over-decomposition (paper
§3.2) = choosing ``k_blocks`` > grid dim, giving finer pipeline slots.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Callable, Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.profiler import TraceAnnotation, annotate_function
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.tpu import interpret_mode

__all__ = [
    "SummaConfig",
    "multi_issue_limit",
    "resolve_multi_issue",
    "reference_matmul",
    "reference_blocksparse_matmul",
    "reference_ranksparse_matmul",
    "execute_plan",
    "execute_rank_plan",
    "rank_operands",
    "summa_matmul",
    "summa_blocksparse_matmul",
    "summa_25d_matmul",
    "executable_cache_stats",
    "clear_executable_cache",
    "warm_plan_executable",
]

Strategy = Literal["procedural", "taskbased", "allgather"]


def multi_issue_limit(p_row: int, p_col: int, k_steps: int) -> int:
    """Paper Eq. (1): the number of concurrently scheduled iterations I."""
    if p_row < 2 or p_col < 2:
        return 2
    if p_row >= k_steps and p_col >= k_steps:
        return k_steps
    return min(p_row, p_col)


def resolve_multi_issue(
    p_row: int, p_col: int, k_steps: int, lookahead: int | None = None
) -> int:
    """The executed multiple-issue window: ``lookahead`` when given, Eq. (1)
    otherwise — always clamped to ``[1, max(k_steps, 1)]`` so degenerate
    schedules (k_steps of 0 or 1, windows beyond the panel count) stay
    well-formed.  The single clamp shared by ``SummaConfig``,
    ``MatmulPlan``, and the ``repro.sched`` graph builders."""
    cap = max(k_steps, 1)
    if lookahead is not None:
        return max(1, min(lookahead, cap))
    return max(1, min(multi_issue_limit(p_row, p_col, k_steps), cap))


@dataclasses.dataclass(frozen=True)
class SummaConfig:
    """Configuration for a distributed SUMMA matmul.

    ``row_axis``/``col_axis`` may be a single mesh-axis name or a tuple of
    names (e.g. ``("pod", "data")`` — the grid dimension is their product).
    """

    mesh: Mesh
    row_axis: str | tuple[str, ...] = "data"
    col_axis: str | tuple[str, ...] = "model"
    strategy: Strategy = "taskbased"
    k_blocks: int | None = None  # number of K panels (over-decomposition)
    lookahead: int | None = None  # None => paper Eq. (1)
    accum_dtype: Any = jnp.float32
    # Local block-multiply implementation: "xla" (jnp.dot) or "pallas"
    # (kernels.tiled_matmul dense / kernels.bsmm block-sparse).
    local_matmul: Literal["xla", "pallas"] = "xla"

    def _axis_size(self, axis) -> int:
        if isinstance(axis, tuple):
            out = 1
            for a in axis:
                out *= self.mesh.shape[a]
            return out
        return self.mesh.shape[axis]

    @property
    def p_row(self) -> int:
        return self._axis_size(self.row_axis)

    @property
    def p_col(self) -> int:
        return self._axis_size(self.col_axis)

    def resolve_k_blocks(self, k: int) -> int:
        kb = self.k_blocks
        if kb is None:
            # default: one panel per grid column (classic SUMMA)
            kb = max(self.p_col, self.p_row)
        lcm = math.lcm(self.p_row, self.p_col)
        if kb % lcm and kb not in (self.p_row, self.p_col):
            raise ValueError(
                f"k_blocks={kb} must be a multiple of lcm(grid)={lcm}"
            )
        if k % kb:
            raise ValueError(f"K={k} not divisible by k_blocks={kb}")
        return kb

    def resolve_lookahead(self, k_steps: int) -> int:
        """The executed multiple-issue window (see ``resolve_multi_issue``)."""
        return resolve_multi_issue(
            self.p_row, self.p_col, k_steps, self.lookahead
        )


# ---------------------------------------------------------------------------
# Pure-jnp oracles
# ---------------------------------------------------------------------------


def reference_matmul(a: jax.Array, b: jax.Array, accum_dtype=jnp.float32):
    """Oracle: plain matmul with fp32 accumulation."""
    out = jnp.matmul(a, b, preferred_element_type=accum_dtype)
    return out.astype(a.dtype)


def _expand_mask(mask: np.ndarray, bm: int, bn: int) -> np.ndarray:
    return np.kron(np.asarray(mask, dtype=bool), np.ones((bm, bn), dtype=bool))


def reference_blocksparse_matmul(
    a: jax.Array,
    b: jax.Array,
    a_mask: np.ndarray,
    b_mask: np.ndarray,
    accum_dtype=jnp.float32,
):
    """Oracle for block-sparse matmul: zero masked blocks, then matmul."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    mb, kb_a = a_mask.shape
    kb_b, nb = b_mask.shape
    assert kb_a == kb_b, "A col-blocks must equal B row-blocks"
    am = _expand_mask(a_mask, m // mb, k // kb_a)
    bm_ = _expand_mask(b_mask, k // kb_b, n // nb)
    a_z = jnp.where(jnp.asarray(am), a, 0)
    b_z = jnp.where(jnp.asarray(bm_), b, 0)
    return reference_matmul(a_z, b_z, accum_dtype)


def reference_ranksparse_matmul(
    a_ranks,
    b: jax.Array,
    b_mask: np.ndarray | None = None,
    accum_dtype=jnp.float32,
):
    """Oracle for rank-sparse matmul: densify the ``RankCSR``, then matmul
    (optionally with B's block mask applied)."""
    a = jnp.asarray(a_ranks.to_dense()).astype(b.dtype)
    if b_mask is not None:
        mb, kb = a_ranks.rank_map().ranks.shape
        return reference_blocksparse_matmul(
            a, b, np.ones((mb, kb), dtype=bool), b_mask, accum_dtype
        )
    return reference_matmul(a, b, accum_dtype)


# ---------------------------------------------------------------------------
# shard_map building blocks
# ---------------------------------------------------------------------------


def _bcast_panel(local_slab, owner, axis_name):
    """Broadcast ``local_slab`` from ``owner`` to the whole axis group.

    Static-SPMD broadcast-as-allreduce: non-owners contribute zeros.
    ``owner`` may be a traced int32.
    """
    idx = jax.lax.axis_index(axis_name)
    contrib = jnp.where(idx == owner, local_slab, jnp.zeros_like(local_slab))
    return jax.lax.psum(contrib, axis_name)


def _local_dot(a_panel, b_panel, accum, cfg: SummaConfig):
    """Local panel product; consults the kernel autotune cache.

    ``cfg.local_matmul`` is the static policy, but when the autotune
    cache (``kernels.autotune``) holds a measured winner for this panel
    shape's bucket, the cached route overrides the generic choice —
    lookup-only, so a cold or disabled cache reproduces the pre-autotune
    trace bitwise (the cache fingerprint is part of the executable key).
    """
    from repro.kernels.autotune import autotune_cache

    route = "pallas" if cfg.local_matmul == "pallas" else "xla"
    entry = autotune_cache().lookup(
        a_panel.shape[0], a_panel.shape[1], b_panel.shape[1],
        dtype=a_panel.dtype,
    )
    tiles = None
    if entry is not None and entry["winner"] in ("pallas", "xla"):
        route = entry["winner"]
        tiles = entry.get("tiles")
    if route == "pallas":
        from repro.kernels import ops as kops

        tile_kw = (
            {"bm": tiles[0], "bk": tiles[1], "bn": tiles[2]}
            if tiles else {}
        )
        prod = kops.tiled_matmul(
            a_panel, b_panel, out_dtype=cfg.accum_dtype, **tile_kw
        )
        return accum + prod
    prod = jnp.matmul(a_panel, b_panel, preferred_element_type=cfg.accum_dtype)
    return accum + prod


def _panel_slices(a_loc, b_loc, k, kb_width, t_a, t_b):
    """Extract the k-th K-panel slices + their owners from local shards.

    Global panel k lives in A's grid-column ``k // t_a`` at local panel
    index ``k % t_a`` and in B's grid-row ``k // t_b`` at local index
    ``k % t_b`` (contiguous panel schedule).
    """
    owner_col = k // t_a
    owner_row = k // t_b
    a_panel = jax.lax.dynamic_slice_in_dim(a_loc, (k % t_a) * kb_width, kb_width, 1)
    b_panel = jax.lax.dynamic_slice_in_dim(b_loc, (k % t_b) * kb_width, kb_width, 0)
    return a_panel, b_panel, owner_col, owner_row


# ---------------------------------------------------------------------------
# Plan interpreters (local, inside shard_map)
# ---------------------------------------------------------------------------


def _exec_procedural(a_loc, b_loc, plan, *, k_steps=None, k_start=0):
    """Paper baseline: sequential iterations, no cross-iteration overlap."""
    cfg = plan.cfg
    kb_width = plan.kb_width
    k_steps = plan.k_steps if k_steps is None else k_steps
    m_loc, n_loc = a_loc.shape[0], b_loc.shape[1]
    t_a = a_loc.shape[1] // kb_width
    t_b = b_loc.shape[0] // kb_width

    def body(k, c_acc):
        a_panel, b_panel, owner_col, owner_row = _panel_slices(
            a_loc, b_loc, k + k_start, kb_width, t_a, t_b
        )
        a_bc = _bcast_panel(a_panel, owner_col, cfg.col_axis)
        b_bc = _bcast_panel(b_panel, owner_row, cfg.row_axis)
        return _local_dot(a_bc, b_bc, c_acc, cfg)

    c0 = jnp.zeros((m_loc, n_loc), cfg.accum_dtype)
    return jax.lax.fori_loop(0, k_steps, body, c0)


def _exec_taskbased(a_loc, b_loc, plan, *, k_steps=None, k_start=0):
    """Multiple-issue SUMMA: I-deep panel prefetch pipeline (paper §3.2).

    The carry holds ``I`` broadcast panels.  Iteration ``k`` consumes the
    buffer head (panel ``k``) and issues the broadcast for panel ``k+I``;
    the two are data-independent, so the collective overlaps the GEMM.
    ``k_start`` (possibly traced) offsets the panel range — the 2.5D
    variant gives each replica pod its own K sub-range.
    """
    cfg = plan.cfg
    kb_width = plan.kb_width
    k_steps = plan.k_steps if k_steps is None else k_steps
    m_loc, n_loc = a_loc.shape[0], b_loc.shape[1]
    t_a = a_loc.shape[1] // kb_width
    t_b = b_loc.shape[0] // kb_width
    # Per-plan window (tuner-chosen) wins over the config's Eq.-(1) default.
    lookahead = plan.resolve_lookahead(k_steps)

    def fetch(k):
        k = k + k_start
        a_panel, b_panel, owner_col, owner_row = _panel_slices(
            a_loc, b_loc, k, kb_width, t_a, t_b
        )
        return (
            _bcast_panel(a_panel, owner_col, cfg.col_axis),
            _bcast_panel(b_panel, owner_row, cfg.row_axis),
        )

    # Prologue: issue the first I broadcasts (multiple-issue).  Unrolled at
    # trace time; mutually independent.
    a_buf = []
    b_buf = []
    for k in range(lookahead):
        a_bc, b_bc = fetch(k)
        a_buf.append(a_bc)
        b_buf.append(b_bc)
    a_buf = jnp.stack(a_buf)  # (I, m_loc, kb)
    b_buf = jnp.stack(b_buf)  # (I, kb, n_loc)

    steady = k_steps - lookahead

    def body(carry, k):
        c_acc, a_b, b_b = carry
        a_head, b_head = a_b[0], b_b[0]
        # Issue broadcast for step k + I (independent of the GEMM below).
        a_next, b_next = fetch(k + lookahead)
        c_acc = _local_dot(a_head, b_head, c_acc, cfg)
        a_b = jnp.concatenate([a_b[1:], a_next[None]], axis=0)
        b_b = jnp.concatenate([b_b[1:], b_next[None]], axis=0)
        return (c_acc, a_b, b_b), None

    c0 = jnp.zeros((m_loc, n_loc), cfg.accum_dtype)
    if steady > 0:
        (c_acc, a_buf, b_buf), _ = jax.lax.scan(
            body, (c0, a_buf, b_buf), jnp.arange(steady)
        )
    else:
        c_acc = c0
    # Epilogue: drain the remaining I buffered panels (unrolled).
    for i in range(lookahead):
        c_acc = _local_dot(a_buf[i], b_buf[i], c_acc, cfg)
    return c_acc


def _exec_allgather(a_loc, b_loc, plan, *, k_steps=None, k_start=0):
    """I = K extreme of Eq. (1): gather every panel up-front."""
    cfg = plan.cfg
    a_full = jax.lax.all_gather(a_loc, cfg.col_axis, axis=1, tiled=True)
    b_full = jax.lax.all_gather(b_loc, cfg.row_axis, axis=0, tiled=True)
    c0 = jnp.zeros((a_loc.shape[0], b_loc.shape[1]), cfg.accum_dtype)
    return _local_dot(a_full, b_full, c0, cfg)


def _bcast_live_panels(a_loc, b_loc, plan):
    """Broadcast every globally-live panel (static unroll).

    One (A, B) broadcast pair per live panel, sliced and owner-addressed
    through ``_panel_slices`` so the sparse executors share the dense
    pipeline's panel layout.  Returns the two lists of broadcast panels.
    """
    cfg = plan.cfg
    kb_width = plan.kb_width
    t_a = a_loc.shape[1] // kb_width
    t_b = b_loc.shape[0] // kb_width
    a_parts = []
    b_parts = []
    for kk in plan.live_panels:
        a_panel, b_panel, owner_col, owner_row = _panel_slices(
            a_loc, b_loc, kk, kb_width, t_a, t_b
        )
        a_parts.append(_bcast_panel(a_panel, owner_col, cfg.col_axis))
        b_parts.append(_bcast_panel(b_panel, owner_row, cfg.row_axis))
    return a_parts, b_parts


def _exec_sparse_dag(a_loc, b_loc, plan):
    """Globally-live panels as a fully unrolled static task DAG.

    The closest XLA analogue of the paper's task graph: every surviving
    broadcast is independent of every rank-k update except its own, giving
    the scheduler maximal freedom to overlap (multiple-issue falls out for
    free).  Dead panels are absent from the trace entirely.
    """
    cfg = plan.cfg
    m_loc, n_loc = a_loc.shape[0], b_loc.shape[1]
    c = jnp.zeros((m_loc, n_loc), cfg.accum_dtype)
    a_parts, b_parts = _bcast_live_panels(a_loc, b_loc, plan)
    for a_bc, b_bc in zip(a_parts, b_parts):
        c = _local_dot(a_bc, b_bc, c, cfg)
    return c


def _exec_sparse_pull(a_loc, b_loc, plan):
    """One-sided pull route (``plan.comm_mode == "pull"``).

    RDMA-SpGEMM-style gets (each surviving gemm pulling exactly the
    panels it reads from their owners) are not expressible in static
    SPMD, so this route *emulates* them: one all-gather per operand, then
    static indexed reads of exactly the live panels — dead panels are
    never touched by compute.  The fetch-level cost model (factor-1.0
    bytes, owner-clock contention) lives in ``sched.taskgraph`` /
    ``sched.simulator``.  Numerically this accumulates the same panels in
    the same order as the masked DAG, so pull and broadcast plans pin
    bitwise-equal in the differential oracle.
    """
    cfg = plan.cfg
    kb = plan.kb_width
    m_loc, n_loc = a_loc.shape[0], b_loc.shape[1]
    a_full = jax.lax.all_gather(a_loc, cfg.col_axis, axis=1, tiled=True)
    b_full = jax.lax.all_gather(b_loc, cfg.row_axis, axis=0, tiled=True)
    c = jnp.zeros((m_loc, n_loc), cfg.accum_dtype)
    for kk in plan.live_panels:
        a_panel = jax.lax.slice_in_dim(a_full, kk * kb, (kk + 1) * kb, axis=1)
        b_panel = jax.lax.slice_in_dim(b_full, kk * kb, (kk + 1) * kb, axis=0)
        c = _local_dot(a_panel, b_panel, c, cfg)
    return c


def _exec_sparse_bsmm(a_loc, b_loc, cols_loc, plan):
    """Per-device block-sparse rank-k update via the Pallas BSMM kernel.

    Gathers the globally-live panels (same broadcast traffic as the DAG
    executor), then runs ONE scalar-prefetch kernel over the gathered
    operands with this device's CSR column map: blocks dead for this grid
    row/column are never copied to VMEM nor multiplied, so local FLOPs
    follow the per-device fill-in the planner computed.
    """
    from repro.kernels.bsmm import bsmm_pallas

    cfg = plan.cfg
    a_parts, b_parts = _bcast_live_panels(a_loc, b_loc, plan)
    a_g = jnp.concatenate(a_parts, axis=1)  # (m_loc, L*kb)
    b_g = jnp.concatenate(b_parts, axis=0)  # (L*kb, n_loc)
    bm, bk, bn = plan.local_block
    c = bsmm_pallas(
        a_g,
        b_g,
        cols_loc,
        bm=bm,
        bk=bk,
        bn=bn,
        out_dtype=cfg.accum_dtype,
        interpret=interpret_mode(),
    )
    return c.astype(cfg.accum_dtype)


def _rank_panel_widths(plan) -> dict[int, int]:
    """Static per-live-panel factor width: the max block rank in that
    panel's (padded) column of the rank grid (>= 1 on live panels)."""
    return {
        kk: max(int(plan.a_ranks[:, kk].max()), 1)
        for kk in plan.live_panels
    }


def _exec_ranksparse(u_loc, v_loc, b_loc, plan, *, r_pad: int):
    """Block-rank-sparse rank-k updates from factorized A panels.

    A's blocks arrive as stacked factors (``rank_operands`` layout): for
    live panel ``kk`` this broadcasts a width-``r_k`` U panel, the matching
    V rows, and B's dense panel, then evaluates every local block row as
    ``U @ (V @ B)`` — two skinny gemms whose FLOPs follow the panel rank.
    Two independent per-panel fallbacks (static, shared with the planner's
    comm model and the task graph):

    * comm — past r* = bm·bk/(bm+bk) the factors outweigh the dense
      panel, so the owner column reconstructs locally and the dense panel
      is broadcast instead;
    * compute — near the threshold the fused dense dot beats the
      two-stage contraction (``RANK_COMPUTE_MARGIN``); factors may still
      travel (they're smaller) and be reconstructed receiver-side.

    Rank raggedness *within* a panel is carried by zero factor columns
    (the executed width is the panel max — the plan's ``flops_sparse``
    stays per-block useful work, the same padding-vs-useful gap
    ``NonuniformMatmul.padding_waste`` documents for block extents).
    """
    from repro.core.sparsity import (
        rank_panel_factored_comm,
        rank_panel_factored_compute,
    )

    cfg = plan.cfg
    bk = plan.kb_width
    k_steps = plan.k_steps
    m_loc, n_loc = u_loc.shape[0], b_loc.shape[1]
    t_a = k_steps // max(cfg.p_col, 1) or 1  # A-side panels per grid column
    t_b = b_loc.shape[0] // bk
    mb_loc = v_loc.shape[0] // r_pad
    bm = m_loc // mb_loc
    widths = _rank_panel_widths(plan)

    c = jnp.zeros((m_loc, n_loc), cfg.accum_dtype)
    u_parts = []  # factored panels: (mb_loc, bm, r_k) U factors ...
    w_parts = []  # ... and their (mb_loc, r_k, n_loc) V·B intermediates
    for kk in plan.live_panels:
        r_k = min(widths[kk], r_pad)
        owner_col = kk // t_a
        owner_row = kk // t_b
        u_panel = jax.lax.dynamic_slice_in_dim(
            u_loc, (kk % t_a) * r_pad, r_k, 1
        )
        v_panel = jax.lax.dynamic_slice_in_dim(
            v_loc, (kk % t_a) * bk, bk, 1
        ).reshape(mb_loc, r_pad, bk)[:, :r_k, :]
        b_panel = jax.lax.dynamic_slice_in_dim(
            b_loc, (kk % t_b) * bk, bk, 0
        )
        b_bc = _bcast_panel(b_panel, owner_row, cfg.row_axis)
        if rank_panel_factored_comm(r_k, bm, bk):
            u_bc = _bcast_panel(u_panel, owner_col, cfg.col_axis)
            v_bc = _bcast_panel(v_panel, owner_col, cfg.col_axis)
            if rank_panel_factored_compute(r_k, bm, bk, n_loc):
                u_parts.append(u_bc.reshape(mb_loc, bm, r_k))
                w_parts.append(
                    jnp.einsum(
                        "irk,kn->irn", v_bc, b_bc,
                        preferred_element_type=cfg.accum_dtype,
                    )
                )
            else:
                # factors travel (smaller), receivers reconstruct the
                # dense panel and run the fused dot
                a_panel = jnp.einsum(
                    "ibr,irk->ibk", u_bc.reshape(mb_loc, bm, r_k), v_bc,
                    preferred_element_type=cfg.accum_dtype,
                ).reshape(m_loc, bk).astype(u_loc.dtype)
                c = _local_dot(a_panel, b_bc, c, cfg)
        else:
            # Owner-side reconstruction: every device rebuilds the dense
            # panel from its *local* factors (garbage off the owner
            # column, zeroed by the masked psum), so only dense panel
            # bytes travel.
            u3 = u_panel.reshape(mb_loc, bm, r_k)
            a_panel = jnp.einsum(
                "ibr,irk->ibk", u3, v_panel,
                preferred_element_type=cfg.accum_dtype,
            ).reshape(m_loc, bk).astype(u_loc.dtype)
            a_bc = _bcast_panel(a_panel, owner_col, cfg.col_axis)
            c = _local_dot(a_bc, b_bc, c, cfg)
    if u_parts:
        # All factored panels resolve in ONE batched contraction over the
        # concatenated rank axis — per local block row, a (bm, sum r_k) x
        # (sum r_k, n_loc) gemm.  Panel-at-a-time accumulation would run
        # sum-r_k skinny gemms instead, which is ~17x slower on CPU BLAS
        # and wastes MXU occupancy on TPU.
        u_cat = jnp.concatenate(u_parts, axis=2)
        w_cat = jnp.concatenate(w_parts, axis=1)
        c = c + jnp.einsum(
            "ibR,iRn->ibn", u_cat, w_cat,
            preferred_element_type=cfg.accum_dtype,
        ).reshape(m_loc, n_loc)
    return c


def _exec_ranksparse_pull(u_loc, v_loc, b_loc, plan, *, r_pad: int):
    """One-sided pull of *factorized* A panels (``comm_mode="pull"``).

    The RDMA-SpGEMM gets fetch the U/V factors themselves — bytes follow
    the per-block rank, never the dense panel, until a panel crosses
    r* = bm·bk/(bm+bk) (``rank_panel_factored_comm``), where the owner
    would serve the reconstructed dense panel instead.  Like
    ``_exec_sparse_pull`` this *emulates* the gets in static SPMD: one
    all-gather per factor operand, then static indexed reads of exactly
    the live panels; the fetch-level cost model (factor-1.0 rank-sized
    bytes, owner-clock contention) lives in ``sched.taskgraph``.  The
    per-panel compute decisions mirror ``_exec_ranksparse`` term for
    term — same panels, same order, same batched factored contraction —
    so pull pins bitwise-equal against the broadcast rank path in the
    differential oracle.
    """
    from repro.core.sparsity import (
        rank_panel_factored_comm,
        rank_panel_factored_compute,
    )

    cfg = plan.cfg
    bk = plan.kb_width
    m_loc, n_loc = u_loc.shape[0], b_loc.shape[1]
    mb_loc = v_loc.shape[0] // r_pad
    bm = m_loc // mb_loc
    widths = _rank_panel_widths(plan)
    u_full = jax.lax.all_gather(u_loc, cfg.col_axis, axis=1, tiled=True)
    v_full = jax.lax.all_gather(v_loc, cfg.col_axis, axis=1, tiled=True)
    b_full = jax.lax.all_gather(b_loc, cfg.row_axis, axis=0, tiled=True)

    c = jnp.zeros((m_loc, n_loc), cfg.accum_dtype)
    u_parts = []
    w_parts = []
    for kk in plan.live_panels:
        r_k = min(widths[kk], r_pad)
        u_panel = jax.lax.slice_in_dim(
            u_full, kk * r_pad, kk * r_pad + r_k, axis=1
        )
        v_panel = jax.lax.slice_in_dim(
            v_full, kk * bk, (kk + 1) * bk, axis=1
        ).reshape(mb_loc, r_pad, bk)[:, :r_k, :]
        b_panel = jax.lax.slice_in_dim(
            b_full, kk * bk, (kk + 1) * bk, axis=0
        )
        if rank_panel_factored_comm(r_k, bm, bk) and (
            rank_panel_factored_compute(r_k, bm, bk, n_loc)
        ):
            u_parts.append(u_panel.reshape(mb_loc, bm, r_k))
            w_parts.append(
                jnp.einsum(
                    "irk,kn->irn", v_panel, b_panel,
                    preferred_element_type=cfg.accum_dtype,
                )
            )
        else:
            # dense-panel fetch (past the comm crossover) or fused-dot
            # compute preference: reconstruct and run the dense dot —
            # identical arithmetic to the broadcast executor's fallbacks
            a_panel = jnp.einsum(
                "ibr,irk->ibk", u_panel.reshape(mb_loc, bm, r_k), v_panel,
                preferred_element_type=cfg.accum_dtype,
            ).reshape(m_loc, bk).astype(u_loc.dtype)
            c = _local_dot(a_panel, b_panel, c, cfg)
    if u_parts:
        u_cat = jnp.concatenate(u_parts, axis=2)
        w_cat = jnp.concatenate(w_parts, axis=1)
        c = c + jnp.einsum(
            "ibR,iRn->ibn", u_cat, w_cat,
            preferred_element_type=cfg.accum_dtype,
        ).reshape(m_loc, n_loc)
    return c


def _exec_ranksparse_grouped(u_loc, v_loc, b_loc, plan, *, r_pad: int):
    """Rank-sparse update through the grouped-gemm Pallas kernel.

    Gathers the live factor panels (full ``r_pad`` width — the kernel
    wants uniform tiles), then runs stage 1 (every block's ``V @ B_panel``,
    ragged across panels) as ONE grouped gemm: V rows are the tokens,
    each ``r_pad``-row tile's "expert" is its gathered panel position, and
    the B panels are the expert weights.  Stage 2 (``U @ ·`` + the segment
    sum into C rows) is a batched contraction over local block rows.

    Panels past the comm crossover (``rank_panel_factored_comm`` on the
    broadcast width ``r_pad``) are densified owner-side and run as dense
    dots outside the grouped stage, exactly like the jnp executor — the
    kernel's uniform ``r_pad`` padding (vs the model's per-panel ``r_k``)
    is the only remaining model-vs-executed comm gap.
    """
    from repro.core.sparsity import rank_panel_factored_comm
    from repro.kernels.grouped_gemm import grouped_gemm_pallas

    cfg = plan.cfg
    bk = plan.kb_width
    k_steps = plan.k_steps
    m_loc, n_loc = u_loc.shape[0], b_loc.shape[1]
    t_a = k_steps // max(cfg.p_col, 1) or 1
    t_b = b_loc.shape[0] // bk
    mb_loc = v_loc.shape[0] // r_pad
    bm = m_loc // mb_loc

    c = jnp.zeros((m_loc, n_loc), cfg.accum_dtype)
    u_parts, v_parts, b_parts = [], [], []
    for kk in plan.live_panels:
        owner_col = kk // t_a
        owner_row = kk // t_b
        u_panel = jax.lax.dynamic_slice_in_dim(
            u_loc, (kk % t_a) * r_pad, r_pad, 1
        )
        v_panel = jax.lax.dynamic_slice_in_dim(
            v_loc, (kk % t_a) * bk, bk, 1
        )
        b_panel = jax.lax.dynamic_slice_in_dim(
            b_loc, (kk % t_b) * bk, bk, 0
        )
        b_bc = _bcast_panel(b_panel, owner_row, cfg.row_axis)
        if rank_panel_factored_comm(r_pad, bm, bk):
            u_parts.append(_bcast_panel(u_panel, owner_col, cfg.col_axis))
            v_parts.append(_bcast_panel(v_panel, owner_col, cfg.col_axis))
            b_parts.append(b_bc)
        else:
            a_panel = jnp.einsum(
                "ibr,irk->ibk",
                u_panel.reshape(mb_loc, bm, r_pad),
                v_panel.reshape(mb_loc, r_pad, bk),
                preferred_element_type=cfg.accum_dtype,
            ).reshape(m_loc, bk).astype(u_loc.dtype)
            a_bc = _bcast_panel(a_panel, owner_col, cfg.col_axis)
            c = _local_dot(a_bc, b_bc, c, cfg)

    if not u_parts:
        return c
    from repro.kernels.ops import _pick_tile

    live = len(b_parts)
    b_g = jnp.stack(b_parts)  # (L, bk, n_loc) — the "expert" weights
    v_tokens = jnp.concatenate(v_parts, axis=0)  # (L*mb_loc*r_pad, bk)
    tile_expert = jnp.asarray(
        np.repeat(np.arange(live, dtype=np.int32), mb_loc)
    )
    # same tile selection + pad/slice handling as ops.ranksparse_matmul,
    # so awkward n_loc stays lane-aligned on TPU
    bn = _pick_tile(n_loc, 256)
    n_pad_loc = -(-n_loc // bn) * bn
    y = grouped_gemm_pallas(
        v_tokens,
        jnp.pad(b_g, ((0, 0), (0, 0), (0, n_pad_loc - n_loc))),
        tile_expert,
        bt=r_pad,
        bk=bk,
        bn=bn,
        out_dtype=cfg.accum_dtype,
        interpret=interpret_mode(),
    )[:, :n_loc]
    y4 = y.reshape(live, mb_loc, r_pad, n_loc)
    u_g = jnp.stack(u_parts).reshape(live, mb_loc, bm, r_pad)
    c = c + jnp.einsum(
        "libr,lirn->ibn", u_g, y4, preferred_element_type=cfg.accum_dtype
    ).reshape(m_loc, n_loc)
    return c.astype(cfg.accum_dtype)


_EXEC_IMPLS: dict[str, Callable] = {
    "procedural": _exec_procedural,
    "taskbased": _exec_taskbased,
    "allgather": _exec_allgather,
}


# ---------------------------------------------------------------------------
# The executable cache: plan-digest-keyed jitted programs
# ---------------------------------------------------------------------------

#: (kind, plan digest, local_impl, lookahead, dtypes, shapes) -> jitted fn.
#: One entry per distinct static execution — repeated eager calls of the
#: same plan dispatch a cached compiled program instead of re-tracing the
#: interpreter loop op by op.
_EXEC_CACHE: dict = {}
_EXEC_STATS = {
    "hits": 0, "misses": 0, "retraces": 0, "build_s": 0.0, "trace_s": 0.0,
}


def executable_cache_stats() -> dict:
    """Hit/miss/retrace counters + current size of the executable cache.

    ``retraces`` counts actual jax trace executions of cached wrappers —
    with stable plan digests and dtypes it must equal ``misses`` (every
    program traced exactly once); a retrace without a miss means a cache
    key is unstable.  ``build_s`` sums the wall seconds of every new
    executable's first call (trace, lowering, compile or persistent-cache
    load, first enqueue); ``trace_s`` is the part of it spent in the
    traced Python body."""
    return {**_EXEC_STATS, "size": len(_EXEC_CACHE)}


def clear_executable_cache() -> None:
    """Drop every cached executable and zero the counters (tests)."""
    _EXEC_CACHE.clear()
    for k in _EXEC_STATS:
        _EXEC_STATS[k] = 0


def _is_traced(*arrays) -> bool:
    return any(isinstance(x, jax.core.Tracer) for x in arrays)


def _autotune_key_suffix() -> tuple:
    # A non-empty kernel-autotune cache changes what ``_local_dot`` traces,
    # so its content fingerprint joins executable cache keys; when the
    # cache is empty or disabled the suffix is empty and keys stay bitwise
    # pre-autotune.
    from repro.kernels.autotune import cache_fingerprint

    fp = cache_fingerprint()
    return (fp,) if fp else ()


def _run_cached(key: tuple, body: Callable, *args) -> jax.Array:
    """``body(*args)`` through the cached jitted program for ``key``.

    A hit dispatches the program inside a ``repro.dispatch`` span.  A miss
    jits ``body`` and makes the first call inside ``repro.compile``
    instead, adding its wall time to ``build_s`` and the time spent in
    ``body`` while tracing to ``trace_s``; hits are not timed."""
    key = key + _autotune_key_suffix()
    fn = _EXEC_CACHE.get(key)
    if fn is not None:
        _EXEC_STATS["hits"] += 1
        with TraceAnnotation("repro.dispatch"):
            return fn(*args)
    _EXEC_STATS["misses"] += 1

    def traced(*xs):
        _EXEC_STATS["retraces"] += 1
        t0 = time.perf_counter()
        try:
            return body(*xs)
        finally:
            _EXEC_STATS["trace_s"] += time.perf_counter() - t0

    fn = _EXEC_CACHE[key] = jax.jit(traced)
    t0 = time.perf_counter()
    with TraceAnnotation("repro.compile"):
        out = fn(*args)
    _EXEC_STATS["build_s"] += time.perf_counter() - t0
    return out


def warm_plan_executable(plan, dtype, *, out_dtype: Any | None = None):
    """Compile (and cache) the executable for ``plan`` ahead of use.

    Drives the jitted wrapper with zero operands of the plan's padded
    shapes — ``jax.jit``'s dispatch cache is populated by a real call, so
    AOT lowering alone would not make the first production call cheap.
    Rank-sparse plans need a factor payload and cannot be warmed here
    (returns ``False``); everything else returns ``True``.
    """
    if plan.local_impl == "ranksparse":
        return False
    (mp, kp), (_, np_) = plan.padded_shapes
    a = jnp.zeros((mp, kp), dtype)
    b = jnp.zeros((kp, np_), dtype)
    execute_plan(a, b, plan, out_dtype=out_dtype).block_until_ready()
    return True


# ---------------------------------------------------------------------------
# Plan execution (the single entry into shard_map)
# ---------------------------------------------------------------------------


@functools.partial(annotate_function, name="repro.execute")
def execute_plan(
    a: jax.Array,
    b: jax.Array,
    plan,
    *,
    out_dtype: Any | None = None,
    compiled: bool = True,
) -> jax.Array:
    """Run C = A @ B according to a precomputed ``core.plan.MatmulPlan``.

    ``a``/``b`` must already be padded to ``plan.padded_shapes`` and
    sharded P(row_axis, col_axis).  Every public matmul entry point —
    dense, block-sparse, nonuniform — funnels through here.

    Eager calls dispatch one cached jitted program per ``(plan digest,
    dtypes)`` (``compiled=False`` opts out — the differential-oracle
    harness compares the two routes).  Accumulators live entirely inside
    the compiled program (XLA-managed buffers, freed on exit); operand
    buffers are deliberately *not* donated, since callers routinely reuse
    them across timing iterations.  Inside an enclosing ``jax.jit`` the
    interpreter body inlines into the caller's trace unchanged.  The
    whole call is the ``repro.execute`` span.
    """
    _check_plan_operands(a, b, plan)
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    if not compiled or _is_traced(a, b):
        return _execute_plan_eager(a, b, plan, out_dtype=out_dtype)
    key = (
        "plan", plan.digest(), plan.local_impl, plan.resolve_lookahead(),
        str(a.dtype), str(b.dtype), str(out_dtype),
    )
    return _run_cached(
        key,
        lambda a, b: _execute_plan_eager(a, b, plan, out_dtype=out_dtype),
        a, b,
    )


def _check_plan_operands(a, b, plan) -> None:
    (mp, kp), (_, np_) = plan.padded_shapes
    if a.shape != (mp, kp) or b.shape != (kp, np_):
        raise ValueError(
            f"operands {a.shape} @ {b.shape} do not match the plan's padded "
            f"shapes ({mp},{kp}) @ ({kp},{np_})"
        )


def _execute_plan_eager(
    a: jax.Array,
    b: jax.Array,
    plan,
    *,
    out_dtype: Any | None = None,
) -> jax.Array:
    """The strategy-interpreter body (trace-level; see ``execute_plan``)."""
    cfg = plan.cfg
    out_dtype = out_dtype or a.dtype
    spec2 = P(cfg.row_axis, cfg.col_axis)
    if plan.a_mask is not None:
        # Zero masked blocks so padded/garbage data cannot contribute.
        a = _apply_block_mask(a, plan.a_mask)
        b = _apply_block_mask(b, plan.b_mask)

    if getattr(plan, "stationarity", "C") != "C":
        # A-/B-stationary schedules (repro.spgemm): the stationary operand
        # keeps its canonical (row, col) layout; the other is re-laid-out
        # with K over the opposite grid axis and consumed in place; the
        # per-device partials reduce-scatter (bandwidth-optimal, factor 1)
        # into C's canonical layout.  No K pipeline — masked operands are
        # already zeroed above, so structure still prunes arithmetic work
        # at the value level.
        if plan.stationarity == "A":
            in_specs = (spec2, P(cfg.col_axis, None))
            scatter_axis, scatter_dim = cfg.col_axis, 1
        else:
            in_specs = (P(None, cfg.row_axis), spec2)
            scatter_axis, scatter_dim = cfg.row_axis, 0

        def fn_stat(a_loc, b_loc):
            c0 = jnp.zeros((a_loc.shape[0], b_loc.shape[1]), cfg.accum_dtype)
            part = _local_dot(a_loc, b_loc, c0, cfg)
            c = jax.lax.psum_scatter(
                part, scatter_axis, scatter_dimension=scatter_dim, tiled=True
            )
            return c.astype(out_dtype)

        out = shard_map(
            fn_stat,
            mesh=cfg.mesh,
            in_specs=in_specs,
            out_specs=spec2,
            check_vma=False,
        )(a, b)
        return _filter_c(out, plan)

    if plan.local_impl == "bsmm":
        cols = jnp.asarray(plan.local_cols)
        cols_spec = P(cfg.row_axis, cfg.col_axis, None, None)

        def fn_bsmm(a_loc, b_loc, cols_loc):
            c = _exec_sparse_bsmm(a_loc, b_loc, cols_loc[0, 0], plan)
            return c.astype(out_dtype)

        out = shard_map(
            fn_bsmm,
            mesh=cfg.mesh,
            in_specs=(spec2, spec2, cols_spec),
            out_specs=spec2,
            check_vma=False,
        )(a, b, cols)
        return _filter_c(out, plan)

    if plan.local_impl in ("masked", "ranksparse"):
        # Rank plans given dense-stored operands run the masked DAG: the
        # ranks informed the cost model / scheduler, but without factors
        # there is nothing rank-sized to multiply (execute_rank_plan is
        # the factorized path).
        run = (
            _exec_sparse_pull
            if getattr(plan, "comm_mode", "broadcast") == "pull"
            else _exec_sparse_dag
        )

        def fn_masked(a_loc, b_loc):
            return run(a_loc, b_loc, plan).astype(out_dtype)

        out = shard_map(
            fn_masked,
            mesh=cfg.mesh,
            in_specs=(spec2, spec2),
            out_specs=spec2,
            check_vma=False,
        )(a, b)
        return _filter_c(out, plan)

    local = _EXEC_IMPLS[cfg.strategy]

    def fn_dense(a_loc, b_loc):
        return local(a_loc, b_loc, plan).astype(out_dtype)

    out = shard_map(
        fn_dense,
        mesh=cfg.mesh,
        in_specs=(spec2, spec2),
        out_specs=spec2,
        check_vma=False,
    )(a, b)
    return _filter_c(out, plan)


def rank_operands(a_ranks, plan) -> tuple[np.ndarray, np.ndarray]:
    """Lay a ``RankCSR`` out as the dense-stored factor operands the
    rank-sparse executor consumes.

    Returns ``(u_all, v_all)``: ``u_all`` is (m_pad, k_steps·r_pad) with
    block row ``i``, panel ``kk`` holding ``U[i,kk]`` at column offset
    ``kk·r_pad`` (zero beyond the true rank); ``v_all`` is
    (m_blocks·r_pad, k_pad) with ``V[i,kk]`` at row offset ``i·r_pad``,
    column offset ``kk·bk``.  Both shard P(row_axis, col_axis) exactly
    like A — every U/V panel lives on the device that owns the matching A
    panel, so ``_bcast_panel``'s owner arithmetic carries over unchanged.
    Memoized per padded geometry on the (frozen) ``RankCSR`` so repeated
    eager calls don't re-lay-out the factors.
    """
    cache_key = ("_rank_operands", plan.m_pad, plan.k_pad, plan.k_steps)
    cached = a_ranks.__dict__.get(cache_key)
    if cached is not None:
        return cached
    bm, bk = a_ranks.bm, a_ranks.bk
    r_pad = a_ranks.r_pad
    csr = a_ranks.csr
    m_blk_p = plan.m_pad // bm
    k_steps = plan.k_steps
    u_all = np.zeros((plan.m_pad, k_steps * r_pad), np.float32)
    v_all = np.zeros((m_blk_p * r_pad, plan.k_pad), np.float32)
    for i in range(csr.m_blocks):
        lo, hi = csr.row_ptr[i], csr.row_ptr[i + 1]
        for s in range(lo, hi):
            kk = int(csr.col_idx[s])
            u_all[i * bm : (i + 1) * bm, kk * r_pad : (kk + 1) * r_pad] = (
                a_ranks.u[s]
            )
            v_all[i * r_pad : (i + 1) * r_pad, kk * bk : (kk + 1) * bk] = (
                a_ranks.v[s]
            )
    a_ranks.__dict__[cache_key] = (u_all, v_all)
    return u_all, v_all


@functools.partial(annotate_function, name="repro.execute")
def execute_rank_plan(
    u: jax.Array,
    v: jax.Array,
    b: jax.Array,
    plan,
    *,
    out_dtype: Any | None = None,
    compiled: bool = True,
) -> jax.Array:
    """Run C = A @ B with A given as factorized rank-sparse operands.

    ``u``/``v`` come from :func:`rank_operands` (already padded); ``b``
    must be padded to the plan's (k_pad, n_pad).  All three are sharded
    P(row_axis, col_axis).  Requires ``plan.local_impl == "ranksparse"``
    (the planner guarantees the factor layout fits the grid).  With
    ``local_matmul="pallas"`` the gathered live panels run through the
    grouped-gemm kernel (kernels/grouped_gemm.py), stage 1 being the
    ragged per-rank V·B gemms.

    Eager calls dispatch a cached jitted program keyed by the plan digest
    + operand shapes/dtypes.  The factors are *runtime arguments*, never
    trace constants — the digest (like ``plan.rank_key``) sees only the
    rank structure, so baking values in would silently serve stale
    factors to a same-structure payload.  The whole call is the
    ``repro.execute`` span.
    """
    out_dtype = jnp.dtype(out_dtype or b.dtype)
    if compiled and not _is_traced(u, v, b):
        _check_rank_operands(u, v, b, plan)  # eager, caller-friendly errors
        key = (
            "rank", plan.digest(), plan.resolve_lookahead(),
            u.shape, v.shape, str(u.dtype), str(v.dtype), str(b.dtype),
            str(out_dtype),
        )
        return _run_cached(
            key,
            lambda u, v, b: _execute_rank_plan_eager(
                u, v, b, plan, out_dtype=out_dtype
            ),
            u, v, b,
        )
    return _execute_rank_plan_eager(u, v, b, plan, out_dtype=out_dtype)


def _check_rank_operands(u, v, b, plan) -> None:
    if plan.local_impl != "ranksparse":
        raise ValueError(
            f"plan.local_impl={plan.local_impl!r}: not a rank-sparse plan "
            "(factor layout needs M blocks aligned to the grid rows; "
            "densify with RankCSR.to_dense() and use execute_plan)"
        )
    k_r = u.shape[1]
    if k_r % plan.k_steps:
        raise ValueError(
            f"U width {k_r} must be k_steps={plan.k_steps} factor panels"
        )
    r_pad = k_r // plan.k_steps
    (mp, kp), (_, np_) = plan.padded_shapes
    m_blk_p = v.shape[0] // r_pad
    if (
        u.shape[0] != mp
        or v.shape != (m_blk_p * r_pad, kp)
        or b.shape != (kp, np_)
    ):
        raise ValueError(
            f"factor operands u{u.shape}/v{v.shape}/b{b.shape} do not "
            f"match the plan's padded shapes ({mp},{kp}) @ ({kp},{np_})"
        )


def _execute_rank_plan_eager(
    u: jax.Array,
    v: jax.Array,
    b: jax.Array,
    plan,
    *,
    out_dtype: Any | None = None,
) -> jax.Array:
    """The factorized-interpreter body (see ``execute_rank_plan``)."""
    cfg = plan.cfg
    _check_rank_operands(u, v, b, plan)  # shapes are static under a trace
    r_pad = u.shape[1] // plan.k_steps
    out_dtype = out_dtype or b.dtype
    spec2 = P(cfg.row_axis, cfg.col_axis)
    if plan.b_mask is not None:
        b = _apply_block_mask(b, plan.b_mask)
    if getattr(plan, "comm_mode", "broadcast") == "pull":
        # factor-fetching pull route (repro.spgemm): rank-sized gets for
        # both local_matmul flavors — the grouped kernel's gather stage is
        # broadcast-shaped, so pull always runs the indexed-read emulation
        local = _exec_ranksparse_pull
    else:
        local = (
            _exec_ranksparse_grouped
            if cfg.local_matmul == "pallas"
            else _exec_ranksparse
        )

    def fn_rank(u_loc, v_loc, b_loc):
        c = local(u_loc, v_loc, b_loc, plan, r_pad=r_pad)
        return c.astype(out_dtype)

    out = shard_map(
        fn_rank,
        mesh=cfg.mesh,
        in_specs=(spec2, spec2, spec2),
        out_specs=spec2,
        check_vma=False,
    )(u, v, b)
    return _filter_c(out, plan)


# ---------------------------------------------------------------------------
# Public entry points (thin wrappers planning + executing)
# ---------------------------------------------------------------------------


def summa_matmul(
    a: jax.Array,
    b: jax.Array,
    cfg: SummaConfig,
    *,
    out_dtype: Any | None = None,
) -> jax.Array:
    """Distributed C = A @ B with the configured SUMMA strategy.

    ``a``: (M, K) sharded P(row_axis, col_axis); ``b``: (K, N) sharded
    P(row_axis, col_axis); returns (M, N) sharded P(row_axis, col_axis).
    Shapes must divide evenly by the grid (use core.api.DistributedMatmul
    for auto-padding).
    """
    from repro.core.plan import plan_matmul

    (m, k), (k2, n) = a.shape, b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    p_row, p_col = cfg.p_row, cfg.p_col
    if m % p_row or n % p_col or k % math.lcm(p_row, p_col):
        raise ValueError(
            f"shapes ({m},{k})x({k2},{n}) must divide grid ({p_row},{p_col})"
        )
    plan = plan_matmul(m, k, n, cfg, itemsize=a.dtype.itemsize)
    if plan.padded_shapes != (a.shape, b.shape):
        raise ValueError(
            f"shapes ({m},{k})x({k2},{n}) need padding for grid/k_blocks; "
            "use core.api.DistributedMatmul for auto-padding"
        )
    return execute_plan(a, b, plan, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# 2.5D task-based SUMMA (paper §3: "immediately applicable to the 2.5D
# variant since it's based on 2D SUMMA")
# ---------------------------------------------------------------------------


def summa_25d_matmul(
    a: jax.Array,
    b: jax.Array,
    cfg: SummaConfig,
    *,
    rep_axis: str = "pod",
    out_dtype: Any | None = None,
    plan=None,
) -> jax.Array:
    """2.5D task-based SUMMA: operands replicated over ``rep_axis`` (c
    copies), each replica executes a disjoint 1/c of the SUMMA iterations
    (multiple-issue within its range), and the partial C's are summed
    across replicas — Solomonik-Demmel's memory-for-communication trade
    with the paper's task pipeline inside each replica.

    Per-replica broadcast traffic drops by c at the cost of c× operand
    memory + one C all-reduce over ``rep_axis``.

    ``plan`` accepts a precomputed (possibly tuned) ``MatmulPlan`` for
    these shapes; by default one is derived here.
    """
    from repro.core.plan import plan_matmul

    (m, k), (k2, n) = a.shape, b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    if rep_axis not in cfg.mesh.shape:
        raise ValueError(
            f"rep_axis {rep_axis!r} is not a mesh axis; "
            f"available: {tuple(cfg.mesh.shape)}"
        )
    c_rep = cfg.mesh.shape[rep_axis]
    if plan is None:
        plan = plan_matmul(m, k, n, cfg, itemsize=a.dtype.itemsize)
    if plan.padded_shapes != (a.shape, b.shape):
        raise ValueError(
            f"shapes ({m},{k})x({k2},{n}) need padding for grid/k_blocks"
        )
    k_steps = plan.k_steps
    if k_steps % c_rep:
        raise ValueError(
            f"replica count {c_rep} (mesh axis {rep_axis!r}) must divide "
            f"k_blocks={k_steps} so each replica owns an equal K sub-range"
        )
    per_rep = k_steps // c_rep
    out_dtype = jnp.dtype(out_dtype or a.dtype)

    def run(a, b):
        def fn(a_loc, b_loc):
            k_start = jax.lax.axis_index(rep_axis) * per_rep
            c_acc = _exec_taskbased(
                a_loc, b_loc, plan, k_steps=per_rep, k_start=k_start
            )
            c_acc = jax.lax.psum(c_acc, rep_axis)
            return c_acc.astype(out_dtype)

        # no rep_axis in the specs: replicated operands
        spec2 = P(cfg.row_axis, cfg.col_axis)
        return shard_map(
            fn,
            mesh=cfg.mesh,
            in_specs=(spec2, spec2),
            out_specs=spec2,
            check_vma=False,
        )(a, b)

    if _is_traced(a, b):
        return run(a, b)
    key = (
        "25d", plan.digest(), rep_axis, per_rep,
        str(a.dtype), str(b.dtype), str(out_dtype),
    )
    return _run_cached(key, run, a, b)


# ---------------------------------------------------------------------------
# Block-sparse SUMMA (the paper's target use case)
# ---------------------------------------------------------------------------


def summa_blocksparse_matmul(
    a: jax.Array,
    b: jax.Array,
    a_mask: np.ndarray,
    b_mask: np.ndarray,
    cfg: SummaConfig,
    *,
    out_dtype: Any | None = None,
) -> jax.Array:
    """Block-sparse distributed C = A @ B.

    ``a_mask``: (M_blk, K_blk) bool; ``b_mask``: (K_blk, N_blk) bool — the
    *static* block-structure (distance decay / screening in the paper's
    domain).  One SUMMA panel per K block.  Panels the plan marks globally
    dead are skipped at trace time (no broadcast, no compute); with
    ``local_matmul="pallas"`` the surviving panels run through the BSMM
    scalar-prefetch kernel on the plan's per-device CSR maps, so FLOPs
    follow the per-device fill-in.
    """
    from repro.core.plan import plan_matmul

    (m, k), (k2, n) = a.shape, b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    plan = plan_matmul(
        m, k, n, cfg, a_mask=a_mask, b_mask=b_mask,
        itemsize=a.dtype.itemsize,
    )
    if plan.padded_shapes != (a.shape, b.shape):
        raise ValueError(
            f"shape/grid/blocking mismatch: ({m},{k})x({k2},{n}) on grid "
            f"({cfg.p_row},{cfg.p_col}) with {plan.k_steps} K blocks needs "
            f"padding to {plan.padded_shapes}; use core.api.DistributedMatmul"
        )
    return execute_plan(a, b, plan, out_dtype=out_dtype)


def _filter_c(out: jax.Array, plan) -> jax.Array:
    """Apply the plan's output filter: dead C blocks are zeroed, so an
    execution can never populate blocks the output structure excludes
    (numerically significant when ``c_mask`` is narrower than the
    symbolic ``a (.) b`` product)."""
    c_mask = getattr(plan, "c_mask", None)
    if c_mask is not None:
        out = _apply_block_mask(out, c_mask)
    return out


def _apply_block_mask(x: jax.Array, mask: np.ndarray) -> jax.Array:
    """Zero out masked blocks of a (R, C) array given an (Rb, Cb) mask."""
    r, c = x.shape
    rb, cb = mask.shape
    if r % rb or c % cb:
        raise ValueError(f"array {x.shape} not divisible by mask {mask.shape}")
    fine = jnp.asarray(np.repeat(np.repeat(mask, r // rb, 0), c // cb, 1))
    return jnp.where(fine, x, jnp.zeros((), x.dtype))
