"""User-facing API for distributed (block-sparse) matrix multiplication.

``DistributedMatmul`` is a thin front-end over the ``core.plan`` planner:
every call — dense, block-sparse, one-sided mask, nonuniform — resolves
to one cached ``MatmulPlan`` (keyed by shapes + mask content + strategy)
that ``core.summa.execute_plan`` interprets.  The front-end only pads
operands to the plan's physical shapes and crops the result.
``NonuniformMatmul`` adds the bucketized expand/compact adaptation for
nonuniformly blocked matrices.  This is the object the LM stack and the
examples use.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import blocking as bk
from repro.core import summa as sm
from repro.core.plan import MatmulPlan, mask_key, plan_matmul, rank_key
from repro.core.sparsity import BlockRankMap, RankCSR

__all__ = ["DistributedMatmul", "pad_to_multiple", "NonuniformMatmul"]


def pad_to_multiple(x: jax.Array, multiples: tuple[int, ...]) -> jax.Array:
    """Zero-pad each dim of ``x`` up to the next multiple."""
    pads = []
    for dim, mult in zip(x.shape, multiples):
        target = -(-dim // mult) * mult
        pads.append((0, target - dim))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _pad_to_shape(x: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    pads = [(0, t - d) for d, t in zip(x.shape, shape)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


@dataclasses.dataclass
class DistributedMatmul:
    """C = A @ B on a 2-D mesh slice, task-based SUMMA under the hood.

    Example::

        mesh = jax.make_mesh((4, 4), ("data", "model"))
        mm = DistributedMatmul(mesh, strategy="taskbased", k_blocks=8)
        c = mm(a, b)                       # dense
        c = mm(a, b, a_mask=am, b_mask=bm) # block-sparse
        c = mm(a, b, b_mask=bm)            # one-sided block structure

    Each distinct (shapes, masks, strategy) builds its ``MatmulPlan``
    once; repeated (re)traces — scanned layers, prefill vs decode shapes
    — hit the cache instead of re-deriving the schedule.

    Every call opens profiler spans, nested by time on the caller's
    thread: ``repro.matmul`` (the whole call) around ``repro.plan`` (plan
    lookup; ``repro.plan.build`` inside it on a miss), ``repro.pad``,
    ``repro.execute`` (``core.summa``: ``repro.dispatch`` of a cached
    program, or ``repro.compile`` on its first call) and ``repro.unpad``.
    """

    mesh: Mesh
    row_axis: str = "data"
    col_axis: str = "model"
    strategy: str = "taskbased"
    k_blocks: int | None = None
    lookahead: int | None = None
    accum_dtype: Any = jnp.float32
    local_matmul: str = "xla"
    #: dispatch cached jitted executables (core.summa / core.contract);
    #: False forces the eager interpreters everywhere (oracle baseline)
    compiled: bool = True
    _plan_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    # spec/tiling-keyed matricization geometry + compiled contraction
    # programs for core.contract
    _contract_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    _cache_stats: dict = dataclasses.field(
        default_factory=lambda: {
            "plan_hits": 0, "plan_misses": 0, "plan_build_s": 0.0,
            "geom_hits": 0, "geom_misses": 0,
            "step_hits": 0, "step_misses": 0, "step_retraces": 0,
        },
        repr=False, compare=False,
    )

    def config(self, strategy: str | None = None) -> sm.SummaConfig:
        return sm.SummaConfig(
            mesh=self.mesh,
            row_axis=self.row_axis,
            col_axis=self.col_axis,
            strategy=strategy or self.strategy,  # type: ignore[arg-type]
            k_blocks=self.k_blocks,
            lookahead=self.lookahead,
            accum_dtype=self.accum_dtype,
            local_matmul=self.local_matmul,  # type: ignore[arg-type]
        )

    # -- sharding helpers ---------------------------------------------------

    def operand_shardings(self):
        spec = P(self.row_axis, self.col_axis)
        s = NamedSharding(self.mesh, spec)
        return s, s, s

    def shard(self, a: jax.Array, b: jax.Array):
        """Place (padded) operands with SUMMA shardings."""
        sa, sb, _ = self.operand_shardings()
        return jax.device_put(a, sa), jax.device_put(b, sb)

    # -- planning ------------------------------------------------------------

    @functools.partial(annotate_function, name="repro.plan")
    def plan(
        self,
        m: int,
        k: int,
        n: int,
        *,
        a_mask: np.ndarray | None = None,
        b_mask: np.ndarray | None = None,
        a_ranks: BlockRankMap | RankCSR | None = None,
        b_ranks: BlockRankMap | RankCSR | None = None,
        c_mask: np.ndarray | None = None,
        strategy: str | None = None,
        itemsize: int = 4,
        tune: bool = False,
        lookahead: int | None = None,
        comm_mode: str = "broadcast",
        stationarity: str = "C",
        a_norms: np.ndarray | None = None,
        b_norms: np.ndarray | None = None,
        filter_eps: float = 0.0,
        k_blocks: int | None = None,
    ) -> MatmulPlan:
        """The (cached) execution plan for a (M, K) x (K, N) product.

        ``a_ranks`` (a ``BlockRankMap`` or ``RankCSR``) plans A as
        block-rank-sparse: costs/schedule follow the per-block ranks.  The
        cache key digests the *rank structure*, not factor values — two
        ``RankCSR`` with the same ranks share a plan.  ``b_ranks`` is B's
        structure (rank-aware pruning; B stays dense-stored) and
        ``c_mask`` the output block filter — the sparse x sparse planning
        inputs of ``repro.spgemm``, like ``comm_mode`` ("broadcast" |
        "pull") and ``stationarity`` ("C" | "A" | "B" | "auto").
        ``tune=True`` runs the schedule autotuner (repro.sched.tuner)
        over the plan: the cached result carries the
        simulated-makespan-optimal strategy / k_blocks / lookahead /
        comm mode instead of the static config.  ``lookahead`` pins the
        per-plan multiple-issue window explicitly (the chain scheduler
        uses this to execute jointly tuned windows); it overrides a tuned
        window.  ``a_norms`` / ``b_norms`` (per-block Frobenius norms)
        with ``filter_eps > 0`` screen small products DBCSR-style; the
        cache key digests the norm grids only when a filter is active, so
        ``filter_eps=0`` calls key (and plan) identically to norm-free
        ones.  ``k_blocks`` overrides the config's K over-decomposition;
        together with ``strategy``/``lookahead`` it lets the persistent
        plan service (``serve.plan_service``) re-apply a stored tuned
        schedule without re-running the tuner.
        """
        from repro.core.sparsity import norms_key

        rank_payload = isinstance(a_ranks, RankCSR)
        key = (
            m, k, n, mask_key(a_mask), mask_key(b_mask), rank_key(a_ranks),
            rank_payload, strategy or self.strategy, itemsize, tune,
            lookahead, rank_key(b_ranks), mask_key(c_mask), comm_mode,
            stationarity,
        )
        if k_blocks is not None:
            key = key + ("k_blocks", int(k_blocks))
        if filter_eps > 0.0:
            key = key + (
                float(filter_eps), norms_key(a_norms), norms_key(b_norms),
            )
        plan = self._plan_cache.get(key)
        if plan is None:
            self._cache_stats["plan_misses"] += 1
            t0 = time.perf_counter()
            with TraceAnnotation("repro.plan.build"):
                rank_map = a_ranks.rank_map() if rank_payload else a_ranks
                b_rank_map = (
                    b_ranks.rank_map()
                    if isinstance(b_ranks, RankCSR)
                    else b_ranks
                )
                cfg = self.config(strategy)
                if k_blocks is not None:
                    cfg = dataclasses.replace(cfg, k_blocks=int(k_blocks))
                plan = plan_matmul(
                    m, k, n, cfg,
                    a_mask=a_mask, b_mask=b_mask, a_ranks=rank_map,
                    b_ranks=b_rank_map, c_mask=c_mask,
                    rank_payload=rank_payload, comm_mode=comm_mode,
                    stationarity=stationarity, itemsize=itemsize,
                    a_norms=a_norms, b_norms=b_norms, filter_eps=filter_eps,
                )
                if tune:
                    from repro.sched.tuner import tune_plan  # deferred: no cycle

                    plan = tune_plan(plan)
                if lookahead is not None:
                    plan = dataclasses.replace(plan, lookahead=int(lookahead))
            self._cache_stats["plan_build_s"] += time.perf_counter() - t0
            self._plan_cache[key] = plan
        else:
            self._cache_stats["plan_hits"] += 1
        return plan

    # -- observability -------------------------------------------------------

    def cache_stats(self) -> dict:
        """Hit/miss/retrace counters for every cache on the hot path.

        ``plan``: the ``MatmulPlan`` cache on this instance, with
        ``build_s`` the seconds spent building plans on misses.  ``contract``:
        the matricization-geometry cache (``geom_*``) and the compiled
        contraction-step programs (``step_*`` — ``step_retraces`` counts
        actual jax traces, which must equal ``step_misses`` when keys are
        stable).  ``executable``: the process-wide plan-digest-keyed
        executable cache in ``core.summa``.  ``kernel``: the process-wide
        count of ``tiled_matmul``'s tile choices, ``"MxKxN->bmxbkxbn"``.
        """
        from repro.kernels.ops import tile_choice_stats

        s = self._cache_stats
        return {
            "plan": {
                "size": len(self._plan_cache),
                "hits": s["plan_hits"], "misses": s["plan_misses"],
                "build_s": s["plan_build_s"],
            },
            "contract": {
                "size": len(self._contract_cache),
                "geom_hits": s["geom_hits"], "geom_misses": s["geom_misses"],
                "step_hits": s["step_hits"], "step_misses": s["step_misses"],
                "step_retraces": s["step_retraces"],
            },
            "executable": sm.executable_cache_stats(),
            "kernel": tile_choice_stats(),
        }

    def reset_cache_stats(self) -> None:
        """Zero the counters (cache *contents* are kept)."""
        for k in self._cache_stats:
            self._cache_stats[k] = 0

    # -- call paths ----------------------------------------------------------

    @functools.partial(annotate_function, name="repro.matmul")
    def __call__(
        self,
        a: jax.Array | None,
        b: jax.Array,
        *,
        a_mask: np.ndarray | None = None,
        b_mask: np.ndarray | None = None,
        a_ranks: BlockRankMap | RankCSR | None = None,
        b_ranks: BlockRankMap | RankCSR | None = None,
        c_mask: np.ndarray | None = None,
        strategy: str | None = None,
        tune: bool = False,
        lookahead: int | None = None,
        comm_mode: str = "broadcast",
        stationarity: str = "C",
        a_norms: np.ndarray | None = None,
        b_norms: np.ndarray | None = None,
        filter_eps: float = 0.0,
    ) -> jax.Array:
        """C = A @ B.  ``a_ranks`` plans A block-rank-sparse:

        * a ``RankCSR`` supplies the factor payload — ``a`` may be
          ``None`` (A *is* the factorization) and execution multiplies the
          factors (``execute_rank_plan``), FLOPs and broadcast bytes
          following per-panel ranks;
        * a bare ``BlockRankMap`` refines the cost model / schedule only —
          ``a`` must be the dense-stored operand and execution runs the
          masked DAG over the ``rank > 0`` mask.

        SpGEMM planning inputs (``repro.spgemm``): ``b_ranks`` gives B's
        structure rank-aware (B stays dense-stored), ``c_mask`` filters
        the output block grid (dead C blocks are pruned from the schedule
        and zeroed in the result), ``comm_mode="pull"`` plans one-sided
        panel fetches, ``stationarity="auto"`` lets the comm-volume
        chooser pick the stationary operand.  ``a_norms`` / ``b_norms``
        (per-block Frobenius norm grids, e.g. ``sparsity.block_norms``)
        with ``filter_eps > 0`` drop every (i, k, j) product whose
        ``||A_ik||.||B_kj||`` bound falls below the threshold; the
        result then differs from the exact product by at most the plan's
        recorded ``filter_bound`` in Frobenius norm.
        """
        if a_mask is not None and a_ranks is not None:
            # same rule the planner enforces for the BlockRankMap path —
            # a RankCSR must not silently override an explicit mask
            raise ValueError("pass either a_mask or a_ranks for A, not both")
        if isinstance(a_ranks, RankCSR):
            if a is not None:
                # a RankCSR *is* the A operand; a dense twin would be
                # silently ignored (the factors may be a lossy truncation
                # of it) — make the caller choose one representation
                raise ValueError(
                    "pass a=None when a_ranks is a RankCSR: the "
                    "factorization is the A operand (use "
                    "RankCSR.to_dense() if you meant the dense product)"
                )
            return self._call_ranksparse(
                a_ranks, b, b_mask=b_mask, b_ranks=b_ranks, c_mask=c_mask,
                strategy=strategy, tune=tune, lookahead=lookahead,
                comm_mode=comm_mode, stationarity=stationarity,
                a_norms=a_norms, b_norms=b_norms, filter_eps=filter_eps,
            )
        if a is None:
            raise ValueError("a=None requires a_ranks to be a RankCSR")
        m, k = a.shape
        k2, n = b.shape
        if k != k2:
            raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
        plan = self.plan(
            m, k, n, a_mask=a_mask, b_mask=b_mask, a_ranks=a_ranks,
            b_ranks=b_ranks, c_mask=c_mask, strategy=strategy,
            itemsize=a.dtype.itemsize, tune=tune, lookahead=lookahead,
            comm_mode=comm_mode, stationarity=stationarity,
            a_norms=a_norms, b_norms=b_norms, filter_eps=filter_eps,
        )
        return self._run(a, b, plan)

    def _run(self, a: jax.Array, b: jax.Array, plan: MatmulPlan) -> jax.Array:
        """Pad to the plan's shapes, execute, crop to (M, N)."""
        (mp, kp), (_, np_) = plan.padded_shapes
        with TraceAnnotation("repro.pad"):
            a_p = _pad_to_shape(a, (mp, kp))
            b_p = _pad_to_shape(b, (kp, np_))
        c_p = sm.execute_plan(a_p, b_p, plan, compiled=self.compiled)
        with TraceAnnotation("repro.unpad"):
            return c_p[: a.shape[0], : b.shape[1]]

    # -- tensor contractions -------------------------------------------------

    def contract(self, spec: str, x, y, **kwargs):
        """Einsum-style binary block-sparse tensor contraction.

        Thin delegate to :func:`core.contract.contract` with this
        instance supplying the mesh/strategy, the plan cache, and the
        spec/tiling-keyed matricization-geometry cache — repeated
        contractions of the same structure (scanned layers, chained
        steps) re-derive nothing.
        """
        from repro.core.contract import contract as _contract

        return _contract(spec, x, y, mm=self, **kwargs)

    def contract_chain(self, steps, **kwargs):
        """Jointly scheduled chain of contractions
        (:func:`core.contract.contract_chain`)."""
        from repro.core.contract import contract_chain as _chain

        return _chain(steps, mm=self, **kwargs)

    def _call_ranksparse(
        self,
        a_ranks: RankCSR,
        b: jax.Array,
        *,
        b_mask: np.ndarray | None = None,
        b_ranks: BlockRankMap | RankCSR | None = None,
        c_mask: np.ndarray | None = None,
        strategy: str | None = None,
        tune: bool = False,
        lookahead: int | None = None,
        comm_mode: str = "broadcast",
        stationarity: str = "C",
        a_norms: np.ndarray | None = None,
        b_norms: np.ndarray | None = None,
        filter_eps: float = 0.0,
    ) -> jax.Array:
        m, k = a_ranks.shape
        k2, n = b.shape
        if k != k2:
            raise ValueError(
                f"contraction mismatch {a_ranks.shape} @ {b.shape}"
            )
        if filter_eps > 0.0 and a_norms is None:
            # the factor payload carries its own norms (||A_ik||_F =
            # ||U_ik V_ik||_F computed exactly from the factors)
            from repro.core.sparsity import rank_csr_norms

            a_norms = rank_csr_norms(a_ranks)
        plan = self.plan(
            m, k, n, b_mask=b_mask, b_ranks=b_ranks, c_mask=c_mask,
            a_ranks=a_ranks, strategy=strategy,
            itemsize=b.dtype.itemsize, tune=tune, lookahead=lookahead,
            comm_mode=comm_mode, stationarity=stationarity,
            a_norms=a_norms, b_norms=b_norms, filter_eps=filter_eps,
        )
        if plan.local_impl != "ranksparse":
            # factor layout does not fit this grid: densify and run the
            # planned masked DAG (correct, mask-level pruning only)
            return self._run(jnp.asarray(a_ranks.to_dense()), b, plan)
        (_, kp), (_, np_) = plan.padded_shapes
        with TraceAnnotation("repro.pad"):
            b_p = _pad_to_shape(b, (kp, np_))
            u_all, v_all = sm.rank_operands(a_ranks, plan)
            u, v = jnp.asarray(u_all), jnp.asarray(v_all)
        c_p = sm.execute_rank_plan(u, v, b_p, plan, compiled=self.compiled)
        with TraceAnnotation("repro.unpad"):
            return c_p[:m, :n]


@dataclasses.dataclass
class NonuniformMatmul:
    """Matmul over *nonuniformly blocked* matrices (paper §4.1/§4.4).

    Logical nonuniform tilings are bucketed into uniform physical tiles
    (core.blocking.bucketize); operands are gathered into the padded
    physical layout (zeros in the pad), multiplied through the shared
    ``MatmulPlan`` engine, and the result is scattered back to the
    compact layout.  Zero padding is exact: pad rows/cols contribute
    nothing.

    This is the TPU-native realisation of the paper's arbitrary-block-size
    support; ``padding_waste`` quantifies the cost of the adaptation.
    """

    mm: DistributedMatmul
    row_tiling: bk.Tiling
    inner_tiling: bk.Tiling
    col_tiling: bk.Tiling
    tile: int | str = 256

    def __post_init__(self):
        if self.tile == "auto":
            # physical tile from the kernel autotune cache: pick the
            # measured-fastest square bucket (normalized per flop) that
            # the logical block sizes can fill; static 256 on a cold cache.
            from repro.kernels.autotune import preferred_tile

            max_block = max(
                max(self.row_tiling.sizes),
                max(self.inner_tiling.sizes),
                max(self.col_tiling.sizes),
            )
            self.tile = preferred_tile(max_block) or 256
        self.row_b = bk.bucketize(self.row_tiling, self.tile)
        self.inner_b = bk.bucketize(self.inner_tiling, self.tile)
        self.col_b = bk.bucketize(self.col_tiling, self.tile)

    @property
    def padding_waste(self) -> dict[str, float]:
        return {
            "rows": self.row_b.padding_waste,
            "inner": self.inner_b.padding_waste,
            "cols": self.col_b.padding_waste,
        }

    def plan(
        self,
        *,
        a_ranks: np.ndarray | None = None,
        itemsize: int = 4,
        lookahead: int | None = None,
        tune: bool = False,
    ) -> MatmulPlan:
        """The underlying uniform-tile plan for the bucketized product.

        ``a_ranks`` is a *logical* (row_blocks, inner_blocks) per-block
        rank map; see :meth:`physical_rank_map`.
        """
        return self.mm.plan(
            self.row_b.padded_extent,
            self.inner_b.padded_extent,
            self.col_b.padded_extent,
            a_ranks=(
                self.physical_rank_map(a_ranks)
                if a_ranks is not None else None
            ),
            itemsize=itemsize,
            lookahead=lookahead,
            tune=tune,
        )

    def physical_rank_map(self, logical_ranks: np.ndarray) -> BlockRankMap:
        """Expand a logical per-block rank map onto the physical tile grid.

        Every physical tile inherits its logical block's rank, clamped by
        the tile's valid extents (a submatrix cannot exceed its parent
        block's rank, nor its own dimensions).  Rank 0 means the logical
        block is screened out — its tiles are pruned like masked blocks.
        """
        ranks = np.asarray(logical_ranks, dtype=np.int32)
        want = (self.row_tiling.num_blocks, self.inner_tiling.num_blocks)
        if ranks.shape != want:
            raise ValueError(
                f"logical rank map {ranks.shape} must match the logical "
                f"block grid {want}"
            )
        bid_r = np.asarray(self.row_b.block_id)
        bid_i = np.asarray(self.inner_b.block_id)
        valid_r = np.asarray(self.row_b.valid)
        valid_i = np.asarray(self.inner_b.valid)
        phys = ranks[np.ix_(bid_r, bid_i)]
        cap = np.minimum(valid_r[:, None], valid_i[None, :])
        return BlockRankMap(
            ranks=np.minimum(phys, cap).astype(np.int32),
            bm=self.tile,
            bk=self.tile,
        )

    def _expand(self, x: jax.Array, bdim: bk.BucketedTiling, axis: int):
        idx = jnp.asarray(bdim.gather_indices())
        safe = jnp.maximum(idx, 0)
        out = jnp.take(x, safe, axis=axis)
        shape = [1, 1]
        shape[axis] = -1
        keep = (idx >= 0).reshape(shape)
        return jnp.where(keep, out, jnp.zeros((), x.dtype))

    def _compact(self, c: jax.Array):
        ridx = self.row_b.gather_indices()
        cidx = self.col_b.gather_indices()
        rsel = np.nonzero(ridx >= 0)[0]
        csel = np.nonzero(cidx >= 0)[0]
        # physical order of valid elements == logical order (blocks packed
        # in order, tiles in order within a block)
        return c[jnp.asarray(rsel)][:, jnp.asarray(csel)]

    def __call__(
        self,
        a: jax.Array,
        b: jax.Array,
        *,
        a_ranks: np.ndarray | None = None,
        lookahead: int | None = None,
        tune: bool = False,
    ) -> jax.Array:
        """``a_ranks`` (logical per-block rank map) plans A's physical
        tiles rank-sparse: rank-0 logical blocks are screened out of the
        product and the plan's costs/schedule follow the tile ranks."""
        if a.shape != (self.row_tiling.extent, self.inner_tiling.extent):
            raise ValueError(f"A shape {a.shape} mismatches tilings")
        if b.shape != (self.inner_tiling.extent, self.col_tiling.extent):
            raise ValueError(f"B shape {b.shape} mismatches tilings")
        with TraceAnnotation("repro.matmul"):
            with TraceAnnotation("repro.pad"):
                a_p = self._expand(
                    self._expand(a, self.row_b, 0), self.inner_b, 1
                )
                b_p = self._expand(
                    self._expand(b, self.inner_b, 0), self.col_b, 1
                )
            plan = self.plan(
                a_ranks=a_ranks, itemsize=a.dtype.itemsize,
                lookahead=lookahead, tune=tune,
            )
            c_p = self.mm._run(a_p, b_p, plan)
            with TraceAnnotation("repro.unpad"):
                return self._compact(c_p)
