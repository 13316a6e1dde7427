"""Benchmark harness — one section per paper table/figure.

Also writes ``BENCH_summa.json`` (``--json`` to relocate): a
machine-readable record of the planned-sparse sweep — GF/s, modeled
per-device collective bytes from the ``MatmulPlan`` cost model, fill-in,
strategy, and the dense-vs-planned-sparse speedup at fills 0.1/0.3/1.0 —
so the perf trajectory is tracked across PRs.

Prints ``name,us_per_call,derived`` CSV rows:

* table1_*   — paper Table 1: min:max memory/work ratios of nonuniformly
               blocked matrices at the paper's exact sizes, plus the §4.4
               effective per-process imbalance (the 1:1.35 claim).
* fig4/5_*   — weak scaling (N grows with P), uniform vs nonuniform:
               GFLOP rate + wall time (paper Figs 4, 5).
* fig6/7_*   — strong scaling at fixed N (paper Figs 6, 7 commodity run).
* fig8_*     — efficiency relative to the single-device rate (paper Fig 8).
* summa_*    — strategy comparison (procedural vs task-based vs allgather):
               collective bytes/device from compiled HLO — the structural
               cost the roofline consumes.

Wall-clock caveat: this container exposes one physical core; emulated
multi-device wall times measure total work, not parallel speedup — the
HLO-derived per-device metrics are the scaling signal (EXPERIMENTS.md).

Usage: PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse

import numpy as np


def _row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _block(out):
    """Wait for device completion of a bench result (raw array or
    ``BlockSparseTensor``)."""
    data = getattr(out, "data", out)
    if hasattr(data, "block_until_ready"):
        data.block_until_ready()
    else:
        np.asarray(data)
    return out


def timed_split(fn, *args, iters: int = 3):
    """Split first-call from steady-state timing.

    Returns ``(out, compile_s, wall_s)``: ``compile_s`` is the first call
    (trace + compile + run — what a cold cache costs), ``wall_s`` the
    median of ``iters`` (>= 3) post-warmup calls — the dispatch-bound
    steady state the executable cache is accountable for.  Earlier
    BENCH_*.json trajectories conflated the two.
    """
    import time as _t

    t0 = _t.perf_counter()
    out = _block(fn(*args))
    compile_s = _t.perf_counter() - t0
    walls = []
    for _ in range(max(int(iters), 3)):
        t0 = _t.perf_counter()
        out = _block(fn(*args))
        walls.append(_t.perf_counter() - t0)
    return out, compile_s, float(np.median(walls))


def bench_table1():
    from repro.core.blocking import load_stats, nonuniform_tiling

    # paper's exact matrix sizes, average block 256
    for n in (32_768, 65_536, 98_304, 256_000):
        t0 = __import__("time").perf_counter()
        rt = nonuniform_tiling(n, n // 256, seed=n)
        it = nonuniform_tiling(n, n // 256, seed=n + 1)
        ct = nonuniform_tiling(n, n // 256, seed=n + 2)
        s = load_stats(rt, ct, it)
        us = (__import__("time").perf_counter() - t0) * 1e6
        _row(
            f"table1_N{n}", us,
            f"mem=1:{s.memory_min_max:.2f};work=1:{s.work_min_max:.2f}",
        )
    # §4.4 effective per-process imbalance, N=32768, 256 procs (16x16)
    rt = nonuniform_tiling(32_768, 128, seed=32_768)
    ct = nonuniform_tiling(32_768, 128, seed=32_769)
    eff = load_stats(rt, ct, grid=(16, 16))
    _row(
        "table1_effective_P256", 0.0,
        f"mem=1:{eff.memory_min_max:.2f} (paper: 1:1.35)",
    )


def bench_weak_scaling(quick: bool):
    from benchmarks.summa_scaling import run_config

    # weak scaling: per-device work constant (N ~ sqrt(P))
    cells = [((1, 1), 1024), ((2, 2), 2048), ((4, 4), 4096)]
    if quick:
        cells = cells[:2]
    for blocked in (False, True):
        tag = "nonuniform" if blocked else "uniform"
        for grid, n in cells:
            r = run_config(grid, n, nonuniform=blocked, repeats=2)
            _row(
                f"fig4_weak_{tag}_P{grid[0] * grid[1]}_N{n}",
                r["wall_s"] * 1e6,
                f"gflops={r['gflops']:.1f};coll_B/dev={r['coll_bytes_per_device']:.3g};"
                f"platform={r['platform']}",
            )
            _row(
                f"fig5_weak_wall_{tag}_P{grid[0] * grid[1]}_N{n}",
                r["wall_s"] * 1e6,
                f"wall_s={r['wall_s']:.3f}",
            )


def bench_strong_scaling(quick: bool):
    from benchmarks.summa_scaling import run_config

    n = 2048
    grids = [(1, 1), (2, 2), (4, 4)]
    if quick:
        grids = grids[:2]
    base_rate = None
    for blocked in (False, True):
        tag = "nonuniform" if blocked else "uniform"
        for grid in grids:
            p = grid[0] * grid[1]
            r = run_config(grid, n, nonuniform=blocked, repeats=2)
            _row(
                f"fig6_strong_{tag}_P{p}_N{n}",
                r["wall_s"] * 1e6,
                f"gflops={r['gflops']:.1f};flops/dev={r['flops_per_device_hlo']:.3g};"
                f"platform={r['platform']}",
            )
            _row(
                f"fig7_strong_wall_{tag}_P{p}_N{n}",
                r["wall_s"] * 1e6,
                f"wall_s={r['wall_s']:.3f}",
            )
            if not blocked:
                # fig8: per-device useful work vs P=1 (structural efficiency)
                if base_rate is None:
                    base_rate = r["flops_per_device_hlo"]
                eff = base_rate / (r["flops_per_device_hlo"] * p) * 100
                _row(
                    f"fig8_efficiency_P{p}_N{n}",
                    r["wall_s"] * 1e6,
                    f"structural_efficiency_pct={eff:.1f}",
                )


def bench_strategies():
    """Collective cost of procedural vs task-based vs allgather SUMMA —
    the §Perf baseline table for the paper's own technique."""
    from benchmarks.summa_scaling import run_config

    for strategy in ("procedural", "taskbased", "allgather"):
        r = run_config((4, 4), 2048, strategy=strategy, repeats=2)
        _row(
            f"summa_strategy_{strategy}_P16_N2048",
            r["wall_s"] * 1e6,
            f"coll_B/dev={r['coll_bytes_per_device']:.4g};"
            f"ag={r['coll_breakdown']['all-gather']:.3g};"
            f"ar={r['coll_breakdown']['all-reduce']:.3g};"
            f"platform={r['platform']}",
        )


def bench_blocksparse():
    """Block-sparse SUMMA: communication scales with live K panels, and
    useful work scales with block fill (paper's goal).  Dead panels model
    screened-out interaction shells (distance decay)."""
    import time as _t

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.hlo import analyze_hlo
    from repro.core import mask_matmul_flops, random_block_mask
    from repro.core.summa import SummaConfig, summa_blocksparse_matmul
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, 1)
    n, kb = 1024, 16
    bs = n // kb
    a = jnp.ones((n, n), jnp.float32)
    b = jnp.ones((n, n), jnp.float32)
    cfg = SummaConfig(mesh=mesh, strategy="taskbased", k_blocks=kb)
    for fill, dead_frac in ((0.25, 0.5), (0.5, 0.25), (1.0, 0.0)):
        am = random_block_mask(kb, kb, fill, seed=1)
        bm = random_block_mask(kb, kb, fill, seed=2)
        dead = np.arange(int(kb * dead_frac)) * 2 + 1  # screened shells
        am[:, dead] = False
        bm[dead, :] = False
        f = jax.jit(lambda a, b: summa_blocksparse_matmul(a, b, am, bm, cfg))
        txt = f.lower(a, b).compile().as_text()
        wc = analyze_hlo(txt)
        out = f(a, b)
        out.block_until_ready()
        t0 = _t.perf_counter()
        for _ in range(3):
            out = f(a, b)
        out.block_until_ready()
        us = (_t.perf_counter() - t0) / 3 * 1e6
        useful, dense = mask_matmul_flops(am, bm, bs, bs, bs)
        alive = sum(
            1 for k in range(kb) if am[:, k].any() and bm[k, :].any()
        )
        _row(
            f"blocksparse_fill{fill}_dead{dead_frac}_N{n}",
            us,
            f"alive_panels={alive}/{kb};hlo_flops={wc.flops:.3g};"
            f"useful={useful:.3g};dense={dense:.3g}",
        )


def bench_planned_sparse(json_path: str) -> None:
    """Dense vs *planned* sparse at fills 0.1/0.3/1.0 -> BENCH_summa.json.

    One ``MatmulPlan`` per fill supplies the modeled per-device collective
    bytes, the fill-in, and the per-device pruning stats; the measured
    wall clock gives GF/s and the dense-vs-sparse speedup.  The JSON is
    the cross-PR perf trajectory record.
    """
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import DistributedMatmul
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, 1)
    # Wide panels (K/4) keep per-panel GEMMs MXU/BLAS-efficient on this
    # single-core container; finer grids fragment the local dots and the
    # wall clock measures overhead instead of pruning.
    n, kb = 1024, 4
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    # Same K granularity for dense and sparse so the comparison isolates
    # the planner's pruning, not the panel count.
    mm = DistributedMatmul(mesh, strategy="taskbased", k_blocks=kb)

    def screened_mask(fill, seed):
        """Screening-style mask: dead rows/columns allowed (unlike
        ``random_block_mask``, which guarantees full coverage), so global
        panel pruning actually fires at low fill."""
        r = np.random.default_rng(seed)
        return r.random((kb, kb)) < fill

    def timed(fn):
        _, compile_s, wall = timed_split(fn, a, b)
        return compile_s, wall

    dense_compile, dense_wall = timed(jax.jit(lambda a, b: mm(a, b)))
    dense_plan = mm.plan(n, n, n)
    entries = [
        {
            "name": "dense_N1024",
            "wall_s": dense_wall,
            "compile_s": dense_compile,
            "gflops_per_s": 2.0 * n**3 / dense_wall / 1e9,
            "speedup_vs_dense": 1.0,
            "plan": dense_plan.summary(),
        }
    ]
    _row("plan_dense_N1024", dense_wall * 1e6, "speedup=1.00")
    for fill in (0.1, 0.3, 1.0):
        am = screened_mask(fill, seed=1)
        bm = screened_mask(fill, seed=2)
        plan = mm.plan(n, n, n, a_mask=am, b_mask=bm)
        compile_s, wall = timed(
            jax.jit(lambda a, b, am=am, bm=bm: mm(a, b, a_mask=am, b_mask=bm))
        )
        useful = plan.cost.flops_sparse
        entries.append(
            {
                "name": f"planned_sparse_fill{fill}_N{n}",
                "wall_s": wall,
                "compile_s": compile_s,
                "gflops_per_s": useful / wall / 1e9,
                "speedup_vs_dense": dense_wall / wall,
                "plan": plan.summary(),
            }
        )
        _row(
            f"plan_sparse_fill{fill}_N{n}",
            wall * 1e6,
            f"speedup={dense_wall / wall:.2f};fill={plan.cost.fill_in:.3f};"
            f"comm_B={plan.cost.comm_bytes['taskbased']:.3g}",
        )
    with open(json_path, "w") as f:
        json.dump(
            {
                "bench": "summa",
                "entries": entries,
                "cache_stats": mm.cache_stats(),
            },
            f, indent=2,
        )
    print(f"# wrote {json_path}", flush=True)


def bench_sched(json_path: str) -> None:
    """Schedule-simulator record -> BENCH_sched.json.

    Three sections: (1) predicted vs measured makespan for dense products
    on the local host mesh — the FLOP rate is calibrated once on the
    smallest case, every other prediction must land within 30 % of wall
    time; (2) the paper's imbalance-absorption result on a simulated
    nonuniform 16x16 grid (multi-issue I = Eq. 1 vs I = 1); (3) the
    autotuner vs the static cost-model pick on virtual grids — tuned
    simulated makespan is never worse.
    """
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import DistributedMatmul
    from repro.core.blocking import nonuniform_tiling
    from repro.core.plan import plan_matmul
    from repro.launch.mesh import make_host_mesh
    from repro.sched import (
        MachineModel,
        abstract_summa_config,
        eq1_lookahead,
        from_tilings,
        simulate,
        simulate_plan,
        tune_plan,
    )

    entries = []
    mesh = make_host_mesh(1, 1)
    mm = DistributedMatmul(mesh, strategy="taskbased", k_blocks=4)
    rng = np.random.default_rng(0)

    compile_by_n: dict[int, float] = {}

    def timed(n):
        a = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
        f = jax.jit(lambda a, b: mm(a, b))
        _, compile_s, wall = timed_split(f, a, b)
        compile_by_n[n] = compile_s
        return wall

    # (1) calibrate the machine FLOP rate on one compute-bound dense case,
    # then predict the rest: the 30% acceptance band of EXPERIMENTS.md.
    # (Sub-1k sizes are launch-overhead-bound on this host and sit outside
    # the model — the protocol calibrates and predicts in the GEMM regime.)
    n0 = 1024
    wall0 = timed(n0)
    machine = MachineModel(
        flops_per_s=2.0 * n0**3 / wall0, name="local-calibrated"
    )
    for n in (n0, 1536, 2048):
        wall = wall0 if n == n0 else timed(n)
        plan = mm.plan(n, n, n)
        sim = simulate_plan(plan, machine)
        rel = abs(sim.makespan_s - wall) / wall
        entries.append(
            {
                "name": f"local_dense_N{n}",
                "grid": [1, 1],
                "predicted_makespan_s": sim.makespan_s,
                "measured_wall_s": wall,
                "compile_s": compile_by_n[n],
                "rel_err": rel,
                "within_30pct": bool(rel <= 0.30),
                "chosen_lookahead": plan.resolve_lookahead(),
                "imbalance_ratio": sim.imbalance_ratio,
                "calibration": n == n0,
            }
        )
        _row(
            f"sched_local_dense_N{n}", wall * 1e6,
            f"pred_ms={sim.makespan_s*1e3:.2f};meas_ms={wall*1e3:.2f};"
            f"rel_err={rel:.2f}",
        )

    # (2) nonuniform imbalance absorption on a virtual 16x16 grid
    # (EXPERIMENTS.md §Simulated scaling workload: N=4096, 64 nonuniform
    # blocks per dimension drawn by the paper's §4.1 procedure)
    tilings = [nonuniform_tiling(4096, 64, seed=s) for s in (1, 2, 3)]
    s1 = simulate(from_tilings(16, 16, *tilings, lookahead=1))
    se = simulate(from_tilings(16, 16, *tilings))
    speedup = s1.makespan_s / se.makespan_s
    entries.append(
        {
            "name": "sim_nonuniform_P256_N4096",
            "grid": [16, 16],
            "chosen_lookahead": eq1_lookahead(16, 16, 64),
            "makespan_I1_s": s1.makespan_s,
            "makespan_eq1_s": se.makespan_s,
            "multi_issue_speedup": speedup,
            "imbalance_ratio": se.imbalance_ratio,
        }
    )
    _row(
        "sched_sim_nonuniform_P256", se.makespan_s * 1e6,
        f"speedup_vs_I1={speedup:.2f};imbalance={se.imbalance_ratio:.2f}",
    )

    # (3) tuner vs the static cost-model choice on virtual grids
    for pr, pc, n in ((4, 4, 4096), (16, 16, 8192)):
        cfg = abstract_summa_config(pr, pc, strategy="taskbased")
        tuned = tune_plan(plan_matmul(n, n, n, cfg))
        t = tuned.tuned
        entries.append(
            {
                "name": f"tuned_P{pr*pc}_N{n}",
                "grid": [pr, pc],
                "strategy_static": t["static_strategy"],
                "strategy_tuned": t["strategy"],
                "chosen_lookahead": t["lookahead"],
                "k_blocks": t["k_blocks"],
                "makespan_static_s": t["static_makespan_s"],
                "makespan_tuned_s": t["makespan_s"],
                "tuner_not_worse": bool(
                    t["makespan_s"] <= t["static_makespan_s"] * (1 + 1e-9)
                ),
                "imbalance_ratio": t["imbalance_ratio"],
            }
        )
        _row(
            f"sched_tuned_P{pr*pc}_N{n}", t["makespan_s"] * 1e6,
            f"static={t['static_strategy']};tuned={t['strategy']};"
            f"I={t['lookahead']};speedup={t['speedup_vs_static']:.2f}",
        )
    with open(json_path, "w") as f:
        json.dump(
            {
                "bench": "sched",
                "entries": entries,
                "cache_stats": mm.cache_stats(),
            },
            f, indent=2,
        )
    print(f"# wrote {json_path}", flush=True)


def bench_ranksparse(json_path: str) -> None:
    """Rank-sparse vs mask-only vs dense -> BENCH_ranksparse.json.

    The sequel's claim on this container: on a decay-structured workload
    (near-diagonal blocks ~full rank, ranks decaying with block distance,
    far blocks screened out) the *factorized* execution beats mask-only
    block sparsity once the average block rank is small — each gemm task
    costs O(r·(bm+bk)·n) instead of O(bm·bk·n).  One entry per max-rank
    level records measured walls, both speedups, the mean rank, and the
    plan digest (modeled rank FLOPs vs mask FLOPs vs dense); the
    acceptance bar is rank-sparse beating mask-only at mean rank <= bm/4.
    """
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        DistributedMatmul,
        decay_rank_map,
        synthesize_rank_csr,
    )
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, 1)
    n, blocks = 1024, 8
    bsz = n // blocks  # 128x128 blocks; dense-fallback threshold r* = 64
    mm = DistributedMatmul(mesh, strategy="taskbased")
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)

    def timed(fn):
        _, compile_s, wall = timed_split(fn, b, iters=5)
        return compile_s, wall

    a_dense = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    dense_compile, dense_wall = timed(jax.jit(lambda b: mm(a_dense, b)))
    entries = [
        {
            "name": "dense_N1024",
            "wall_s": dense_wall,
            "compile_s": dense_compile,
            "mean_rank": float(bsz),
            "speedup_vs_dense": 1.0,
            "plan": mm.plan(n, n, n).summary(),
        }
    ]
    _row("ranksparse_dense_N1024", dense_wall * 1e6, "speedup=1.00")
    # One decay structure (mask shared across rank levels) so the
    # rank-vs-mask comparison isolates the factorization, not the mask.
    mask_wall = None
    for max_rank in (96, 48, 32, 16, 8):
        rank_map = decay_rank_map(
            blocks, blocks, bsz, bsz,
            max_rank=max_rank, decay=0.9, threshold=5e-2,
        )
        rcsr = synthesize_rank_csr(rank_map, seed=1)
        if mask_wall is None:
            a_twin = jnp.asarray(rcsr.to_dense())
            mask_compile, mask_wall = timed(
                jax.jit(
                    lambda b, a=a_twin, m=rank_map.mask: mm(a, b, a_mask=m)
                )
            )
            mask_plan = mm.plan(n, n, n, a_mask=rank_map.mask)
            entries.append(
                {
                    "name": "maskonly_decay_N1024",
                    "wall_s": mask_wall,
                    "compile_s": mask_compile,
                    "speedup_vs_dense": dense_wall / mask_wall,
                    "plan": mask_plan.summary(),
                }
            )
            _row(
                "ranksparse_maskonly_N1024", mask_wall * 1e6,
                f"speedup={dense_wall / mask_wall:.2f};"
                f"fill={mask_plan.cost.fill_in:.3f}",
            )
        rank_compile, rank_wall = timed(
            jax.jit(lambda b, r=rcsr: mm(None, b, a_ranks=r))
        )
        plan = mm.plan(n, n, n, a_ranks=rcsr)
        mean_rank = rank_map.mean_rank
        entries.append(
            {
                "name": f"ranksparse_rmax{max_rank}_N1024",
                "wall_s": rank_wall,
                "compile_s": rank_compile,
                "mean_rank": mean_rank,
                "speedup_vs_dense": dense_wall / rank_wall,
                "speedup_vs_maskonly": mask_wall / rank_wall,
                "beats_maskonly": bool(rank_wall < mask_wall),
                "acceptance_regime": bool(mean_rank <= bsz / 4),
                "plan": plan.summary(),
            }
        )
        _row(
            f"ranksparse_rmax{max_rank}_N1024", rank_wall * 1e6,
            f"mean_rank={mean_rank:.1f};"
            f"speedup_vs_dense={dense_wall / rank_wall:.2f};"
            f"speedup_vs_maskonly={mask_wall / rank_wall:.2f};"
            f"flops_rank={plan.cost.flops_sparse:.3g};"
            f"flops_mask={plan.cost.flops_mask:.3g}",
        )
    with open(json_path, "w") as f:
        json.dump(
            {
                "bench": "ranksparse",
                "entries": entries,
                "cache_stats": mm.cache_stats(),
            },
            f, indent=2,
        )
    print(f"# wrote {json_path}", flush=True)


def bench_contract(json_path: str) -> None:
    """Tensor-contraction sweep + chained-contraction scheduling ->
    BENCH_contract.json.

    Two sections:

    (1) executed contractions on the host mesh — one entry per spec
    family (masked 3-D ``abc,cd->abd``, multi-contracted ``abc,bcd->ad``,
    rank-sparse ``ab,bc->ac`` on a factor payload, nonuniform mode
    extents), each recording wall time, the residual vs the float64
    ``np.einsum`` reference, and the underlying plan digest — the proof
    that the einsum front-end rides the same planned engine;

    (2) the nonuniform chain: D = (A.B).C with §4.1 nonuniform blocks on
    a virtual 8x8 grid, simulated sequentially (barrier between MMs) vs
    as the union graph (``chain_graphs``) vs jointly tuned
    (``tune_chain``).  The CI acceptance gate asserts
    ``beats_sequential`` — the union graph's makespan is strictly below
    the barrier sum (the paper's "no explicit internodal synchronization
    lets MMs overlap", measured).  The simulation is deterministic, so
    the gate is noise-free.
    """
    import json

    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        BlockSparseTensor,
        DistributedMatmul,
        contract,
        contract_chain,
        decay_block_mask,
        decay_rank_map,
        nonuniform_tiling,
        synthesize_rank_csr,
    )
    from repro.launch.mesh import make_host_mesh
    from repro.sched import chain_graphs, from_tilings, simulate, tune_chain

    entries = []
    mesh = make_host_mesh(1, 1)
    mm = DistributedMatmul(mesh, strategy="taskbased")
    rng = np.random.default_rng(0)

    def timed(fn, *args):
        return timed_split(fn, *args)

    def dense(shape, block_shape, mask=None):
        data = rng.normal(size=shape).astype(np.float32)
        return BlockSparseTensor.from_dense(
            jnp.asarray(data), block_shape=block_shape, mask=mask
        )

    def case_free2():
        x = dense(
            (16, 32, 512), (8, 16, 32),
            mask=rng.random((2, 2, 16)) < 0.4,
        )
        y = dense((512, 384), (32, 32), mask=decay_block_mask(16, 12, 0.5))
        return "abc,cd->abd", x, y, 64

    def case_multi():
        x = dense((512, 16, 32), (32, 8, 16), mask=rng.random((16, 2, 2)) < 0.5)
        y = dense((16, 32, 384), (8, 16, 32))
        return "abc,bcd->ad", x, y, 64

    def case_rank():
        rank_map = decay_rank_map(8, 8, 64, 64, max_rank=8, decay=0.7)
        x = BlockSparseTensor.from_rank_csr(
            synthesize_rank_csr(rank_map, seed=1)
        )
        y = dense((512, 384), (64, 32))
        return "ab,bc->ac", x, y, 64
    def case_nonuniform():
        rt = nonuniform_tiling(500, 8, seed=1)
        it = nonuniform_tiling(480, 6, seed=2)
        ct = nonuniform_tiling(420, 7, seed=3)
        x = BlockSparseTensor(
            data=jnp.asarray(rng.normal(size=(500, 480)).astype(np.float32)),
            tilings=(rt, it), mask=rng.random((8, 6)) < 0.5,
        )
        y = BlockSparseTensor(
            data=jnp.asarray(rng.normal(size=(480, 420)).astype(np.float32)),
            tilings=(it, ct),
        )
        return "ab,bc->ac", x, y, 64

    for name, case in (
        ("free2", case_free2), ("multi_contracted", case_multi),
        ("rank_sparse", case_rank), ("nonuniform", case_nonuniform),
    ):
        spec, x, y, tile = case()
        out, compile_s, wall = timed(
            lambda: contract(spec, x, y, mm=mm, tile=tile)
        )
        ref = np.einsum(
            spec, x.to_dense().astype(np.float64),
            y.to_dense().astype(np.float64),
        )
        resid = float(np.abs(np.asarray(out.data) - ref).max())
        from repro.core.contract import _geometry_cached, _plan_step

        plan = _plan_step(mm, _geometry_cached(mm, spec, x, y, tile), x)
        entries.append(
            {
                "name": f"contract_{name}",
                "spec": spec,
                "wall_s": wall,
                "compile_s": compile_s,
                "max_abs_err": resid,
                "out_fill": out.fill(),
                "plan": plan.summary(),
            }
        )
        _row(
            f"contract_{name}", wall * 1e6,
            f"spec={spec};compile_s={compile_s:.2f};err={resid:.2e};"
            f"fill={plan.cost.fill_in:.3f}",
        )

    # (2) the nonuniform chain on a virtual 8x8 grid
    nb, extent, (pr, pc) = 16, 2048, (8, 8)
    tilings = [nonuniform_tiling(extent, nb, seed=s) for s in (1, 2, 3, 4)]
    rt, it, ct, dt = tilings
    builders = [
        lambda la=None: from_tilings(pr, pc, rt, it, ct, lookahead=la),
        lambda la=None: from_tilings(pr, pc, rt, ct, dt, lookahead=la),
    ]
    seq = float(sum(simulate(b(None)).makespan_s for b in builders))
    joint = simulate(chain_graphs([b(None) for b in builders]))
    las, tuned_sim, record = tune_chain(builders)
    entries.append(
        {
            "name": f"chain_nonuniform_P{pr*pc}_N{extent}",
            "grid": [pr, pc],
            "blocks": nb,
            "sequential_makespan_s": seq,
            "joint_makespan_s": joint.makespan_s,
            "tuned_makespan_s": tuned_sim.makespan_s,
            "tuned_lookaheads": [int(la) for la in las],
            "speedup_vs_sequential": seq / tuned_sim.makespan_s,
            "beats_sequential": bool(tuned_sim.makespan_s < seq),
        }
    )
    _row(
        f"contract_chain_P{pr*pc}_N{extent}", tuned_sim.makespan_s * 1e6,
        f"seq_us={seq*1e6:.1f};joint_us={joint.makespan_s*1e6:.1f};"
        f"speedup={seq/tuned_sim.makespan_s:.3f};I={las}",
    )

    # executed chain on the host mesh (correctness + wall record); the
    # whole chain is one compiled program, so steady-state wall_s is pure
    # dispatch + compute with zero host round-trips between steps
    am = decay_block_mask(8, 8, decay=0.5, threshold=5e-2)
    x = dense((512, 512), (64, 64), mask=am)
    y1 = dense((512, 512), (64, 64), mask=am)
    y2 = dense((512, 384), (64, 48))
    report_box = {}

    def run_chain():
        res, report = contract_chain(
            [("ab,bc->ac", x, y1), ("ab,bc->ac", y2)], mm=mm, tune=True
        )
        report_box["report"] = report
        return res

    res, compile_s, wall = timed(run_chain)
    report = report_box["report"]
    ref = (
        x.to_dense().astype(np.float64) @ y1.to_dense().astype(np.float64)
    ) @ np.asarray(y2.data, np.float64)
    entries.append(
        {
            "name": "chain_executed_N512",
            "wall_s": wall,
            "compile_s": compile_s,
            "max_abs_err": float(np.abs(np.asarray(res.data) - ref).max()),
            "joint_makespan_s": report["joint_makespan_s"],
            "sequential_makespan_s": report["sequential_makespan_s"],
            "lookaheads": report["lookaheads"],
            "out_fill": res.fill(),
        }
    )
    _row(
        "contract_chain_executed_N512", wall * 1e6,
        f"err={entries[-1]['max_abs_err']:.2e};"
        f"I={report['lookaheads']};fill={res.fill():.3f}",
    )
    with open(json_path, "w") as f:
        json.dump(
            {
                "bench": "contract",
                "entries": entries,
                "cache_stats": mm.cache_stats(),
            },
            f, indent=2,
        )
    print(f"# wrote {json_path}", flush=True)


def bench_spgemm(json_path: str) -> None:
    """Sparse x sparse (SpGEMM) planning sweep -> BENCH_spgemm.json.

    A fill x fill grid of block masks on a 16x16-block product
    (m = k = n = 1024, one block per virtual device of a 16x16 grid):

    * output-structure-aware pruning — gemm tasks of the A-structure-only
      plan vs the plan that also sees B's mask and the symbolic output
      mask (``repro.spgemm.output_mask``); the aware plan must never emit
      more tasks, and strictly fewer on the banded entries;
    * pull vs broadcast — total comm bytes and simulated makespan of the
      one-sided fetch DAG vs the panel-broadcast DAG on the virtual
      16x16 grid; pull must move strictly fewer bytes on the banded
      entries and strictly *more* on the dense entry (the crossover);
    * measured correctness — both comm modes execute on the local host
      mesh and must land within 1e-3 relative residual of the float64
      numpy oracle.

    The acceptance booleans ride in the JSON (CI asserts them).
    """
    import json

    import jax.numpy as jnp
    import numpy as np

    from repro.core import DistributedMatmul
    from repro.core.plan import plan_matmul
    from repro.core.sparsity import banded_block_mask, random_block_mask
    from repro.launch.mesh import make_host_mesh
    from repro.sched import abstract_summa_config, from_plan, simulate
    from repro.spgemm import output_mask

    blk = 16  # block grid == virtual device grid (one C block per device)
    n = 1024
    cfg = abstract_summa_config(blk, blk, strategy="taskbased")
    mesh = make_host_mesh(1, 1)
    mm = DistributedMatmul(mesh, strategy="taskbased", k_blocks=blk)
    rng = np.random.default_rng(0)

    cases = [
        ("banded_bw0", banded_block_mask(blk, blk, 0),
         banded_block_mask(blk, blk, 0)),
        ("banded_bw1", banded_block_mask(blk, blk, 1),
         banded_block_mask(blk, blk, 1)),
    ]
    for f in (0.05, 0.1, 0.2, 0.4):
        cases.append((
            f"random_f{int(f * 100):02d}",
            random_block_mask(blk, blk, f, seed=1),
            random_block_mask(blk, blk, f, seed=2),
        ))
    cases.append(
        ("dense", np.ones((blk, blk), bool), np.ones((blk, blk), bool))
    )

    def gemms(graph):
        return sum(
            1 for t in graph.tasks if t.kind == "gemm" and t.flops > 0
        )

    def comm_bytes(graph):
        return float(
            sum(t.bytes for t in graph.tasks if t.resource == "comm")
        )

    a64 = rng.standard_normal((n, n))
    b64 = rng.standard_normal((n, n))
    a32 = jnp.asarray(a64, jnp.float32)
    b32 = jnp.asarray(b64, jnp.float32)
    bs = n // blk

    entries = []
    for name, amask, bmask in cases:
        cmask = output_mask(amask, bmask)
        p_aonly = plan_matmul(n, n, n, cfg, a_mask=amask)
        p_aware = plan_matmul(
            n, n, n, cfg, a_mask=amask, b_mask=bmask, c_mask=cmask
        )
        p_pull = plan_matmul(
            n, n, n, cfg, a_mask=amask, b_mask=bmask, c_mask=cmask,
            comm_mode="pull",
        )
        g_aonly = from_plan(p_aonly)
        g_aware = from_plan(p_aware)
        g_pull = from_plan(p_pull)
        sim_bcast = simulate(g_aware)
        sim_pull = simulate(g_pull)

        # measured: both comm modes on the host mesh vs the f64 oracle
        fine_a = np.kron(amask, np.ones((bs, bs), bool))
        fine_b = np.kron(bmask, np.ones((bs, bs), bool))
        ref = np.where(fine_a, a64, 0.0) @ np.where(fine_b, b64, 0.0)
        scale = max(1.0, float(np.abs(ref).max()))
        res = {}
        for mode in ("broadcast", "pull"):
            out = _block(mm(
                a32, b32, a_mask=amask, b_mask=bmask, c_mask=cmask,
                comm_mode=mode,
            ))
            res[mode] = float(
                np.abs(np.asarray(out, np.float64) - ref).max()
            ) / scale

        sparse = name != "dense"
        banded = name.startswith("banded")
        entry = {
            "name": name,
            "fill_a": float(amask.mean()),
            "fill_b": float(bmask.mean()),
            "fill_c": float(cmask.mean()),
            "grid": [blk, blk],
            "shape": [n, n, n],
            "gemms_a_only": gemms(g_aonly),
            "gemms_aware": gemms(g_aware),
            "bytes_modeled_bcast": p_aware.cost.comm_bytes.get("taskbased"),
            "bytes_modeled_pull": p_pull.cost.comm_bytes.get("pull"),
            "bytes_graph_bcast": comm_bytes(g_aware),
            "bytes_graph_pull": comm_bytes(g_pull),
            "makespan_bcast_s": sim_bcast.makespan_s,
            "makespan_pull_s": sim_pull.makespan_s,
            "pull_speedup_sim": (
                sim_bcast.makespan_s / sim_pull.makespan_s
                if sim_pull.makespan_s > 0 else 1.0
            ),
            "residual_broadcast": res["broadcast"],
            "residual_pull": res["pull"],
            "aware_not_worse": bool(gemms(g_aware) <= gemms(g_aonly)),
            "aware_strictly_prunes": bool(
                gemms(g_aware) < gemms(g_aonly)
            ),
            "pull_fewer_bytes": bool(
                comm_bytes(g_pull) < comm_bytes(g_aware)
            ),
            "residual_ok": bool(max(res.values()) < 1e-3),
        }
        entries.append(entry)
        _row(
            f"spgemm_{name}", sim_bcast.makespan_s * 1e6,
            f"gemms={entry['gemms_aware']}/{entry['gemms_a_only']};"
            f"pull_bytes={entry['bytes_graph_pull']:.0f};"
            f"bcast_bytes={entry['bytes_graph_bcast']:.0f};"
            f"res={max(res.values()):.1e}",
        )

        # acceptance: output-aware planning never loses, and wins
        # strictly on the banded entries; pull's one-sided fetches beat
        # broadcast exactly where fill is low (and lose at dense — the
        # crossover the simulator prices via owner-clock contention)
        assert entry["aware_not_worse"], name
        if sparse:
            assert entry["residual_ok"], (name, res)
        if banded:
            assert entry["aware_strictly_prunes"], name
            assert entry["pull_fewer_bytes"], name
        if not sparse:
            assert not entry["pull_fewer_bytes"], name
            assert entry["residual_ok"], (name, res)

    with open(json_path, "w") as f:
        json.dump(
            {
                "bench": "spgemm",
                "entries": entries,
                "cache_stats": mm.cache_stats(),
            },
            f, indent=2,
        )
    print(f"# wrote {json_path}", flush=True)


def bench_filter(json_path: str) -> None:
    """Norm-filter threshold sweep + autotune persistence -> BENCH_filter.json.

    DBCSR-style on-the-fly filtering on a decaying-norm workload (block
    norms fall exponentially with band distance |i - k|, the iterative
    C <- A.B regime of arXiv:1910.13555):

    * threshold sweep — for each ``filter_eps`` the planned gemm-task
      count must fall **monotonically**, the simulated makespan must
      never exceed the unfiltered schedule's (filtered-never-slower; the
      simulation is deterministic so the gate is noise-free), and the
      measured Frobenius error vs the unfiltered float64 product must
      stay <= the plan's documented additive bound ``filter_bound``;
    * ``filter_eps=0`` — the plan digest must be **bitwise identical** to
      a plan that never saw norms (the no-op contract the executable
      cache relies on);
    * filtered contract latency — steady-state wall of a filtered
      ``contract()`` call, FLOP-normalized against the dense matmul wall
      measured in the same process (the CI latency gate's filtered leg);
    * kernel autotune — a save/load roundtrip of a freshly tuned bucket
      (fingerprint-stable), with the recorded winner never slower than
      the generic ``xla`` route on its own bucket.

    The acceptance booleans ride in the JSON (CI asserts them).
    """
    import json
    import os
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from repro.core import DistributedMatmul
    from repro.core.contract import BlockSparseTensor
    from repro.core.plan import plan_matmul
    from repro.core.sparsity import block_norms
    from repro.kernels.autotune import KernelAutotuner, set_autotune_cache
    from repro.launch.mesh import make_host_mesh
    from repro.sched import abstract_summa_config, from_plan, simulate

    set_autotune_cache(None)  # keep measured digests on the cold path
    blk, n = 16, 1024
    bs = n // blk
    cfg = abstract_summa_config(blk, blk, strategy="taskbased")
    mesh = make_host_mesh(1, 1)
    mm = DistributedMatmul(mesh, strategy="taskbased", k_blocks=blk)
    rng = np.random.default_rng(0)
    decay = np.exp(
        -0.8 * np.abs(np.arange(blk)[:, None] - np.arange(blk)[None, :])
    )

    def mat(_seed):
        x = rng.standard_normal((n, n))
        return (
            x.reshape(blk, bs, blk, bs) * decay[:, None, :, None]
        ).reshape(n, n)

    a64, b64 = mat(0), mat(1)
    a32 = jnp.asarray(a64, jnp.float32)
    b32 = jnp.asarray(b64, jnp.float32)
    an = block_norms(a64, blk, blk)
    bn = block_norms(b64, blk, blk)
    pmax = float(np.max(an[:, :, None] * bn[None, :, :]))
    ref = a64 @ b64

    def gemms(graph):
        return sum(
            1 for t in graph.tasks if t.kind == "gemm" and t.flops > 0
        )

    base_plan = plan_matmul(n, n, n, cfg)
    base_sim = simulate(from_plan(base_plan))
    # eps=0 digest bitwise: norms without a threshold are a strict no-op
    eps0_plan = plan_matmul(
        n, n, n, cfg, a_norms=an, b_norms=bn, filter_eps=0.0
    )
    digest_preserved = eps0_plan.digest() == base_plan.digest()

    entries = []
    prev_gemms = None
    monotone = True
    for frac in (0.0, 1e-4, 1e-3, 1e-2, 5e-2):
        eps = frac * pmax
        if eps > 0.0:
            p = plan_matmul(
                n, n, n, cfg, a_norms=an, b_norms=bn, filter_eps=eps
            )
        else:
            p = base_plan
        sim = simulate(from_plan(p))
        ng = gemms(from_plan(p))
        out, compile_s, wall_s = timed_split(
            lambda e=eps: mm(
                a32, b32, a_norms=an, b_norms=bn, filter_eps=e
            )
        )
        err = float(
            np.linalg.norm(np.asarray(out, np.float64) - ref)
        )
        bound = float(getattr(p, "filter_bound", 0.0))
        # float32 execution noise rides on top of the analytic bound;
        # normalize the slack to the result's own scale
        slack = 1e-5 * float(np.linalg.norm(ref))
        entry = {
            "name": f"filter_f{frac:g}",
            "filter_eps": eps,
            "gemm_tasks": ng,
            "gemm_tasks_unfiltered": gemms(from_plan(base_plan)),
            "filter_bound": bound,
            "error_frobenius": err,
            "error_within_bound": bool(err <= bound + slack),
            "makespan_s": sim.makespan_s,
            "makespan_unfiltered_s": base_sim.makespan_s,
            "never_slower_sim": bool(
                sim.makespan_s <= base_sim.makespan_s * (1 + 1e-9)
            ),
            "wall_s": wall_s,
            "compile_s": compile_s,
        }
        if prev_gemms is not None and ng > prev_gemms:
            monotone = False
        prev_gemms = ng
        entries.append(entry)
        _row(
            entry["name"], wall_s * 1e6,
            f"gemms={ng};bound={bound:.3g};err={err:.3g};"
            f"sim={sim.makespan_s:.3e}",
        )
        assert entry["error_within_bound"], (entry["name"], err, bound)
        assert entry["never_slower_sim"], (
            entry["name"], sim.makespan_s, base_sim.makespan_s,
        )
    assert monotone, [e["gemm_tasks"] for e in entries]
    assert digest_preserved, "filter_eps=0 changed the plan digest"

    # filtered contract leg of the latency gate: steady-state wall of a
    # filtered contract() vs the dense matmul wall, FLOP-normalized
    xa = BlockSparseTensor.from_dense(a32, block_shape=(bs, bs))
    xb = BlockSparseTensor.from_dense(b32, block_shape=(bs, bs))
    eps_mid = 1e-3 * pmax
    _, dense_compile, dense_wall = timed_split(lambda: mm(a32, b32))
    cout, c_compile, c_wall = timed_split(
        lambda: mm.contract("ik,kj->ij", xa, xb, filter_eps=eps_mid)
    )
    fp = mm.plan(n, n, n, a_norms=an, b_norms=bn, filter_eps=eps_mid)
    fsummary = fp.summary()
    contract_entry = {
        "name": "contract_filtered",
        "filter_eps": eps_mid,
        "wall_s": c_wall,
        "compile_s": c_compile,
        "dense_wall_s": dense_wall,
        "flops_sparse": fsummary["flops_sparse"],
        "flops_dense": fsummary["flops_dense"],
    }
    entries.append(contract_entry)
    _row(
        "filter_contract", c_wall * 1e6,
        f"dense_wall={dense_wall * 1e6:.1f}us;"
        f"flops_ratio={fsummary['flops_sparse'] / fsummary['flops_dense']:.3f}",
    )

    # kernel autotune: tuned winner never loses to the generic route on
    # its own bucket, and the JSON persistence roundtrip is stable
    tuner = KernelAutotuner()
    entry_at = tuner.tune(bs, bs, bs, repeats=2, routes=("xla", "pallas"))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "autotune.json")
        tuner.save(path)
        restored = KernelAutotuner()
        n_loaded = restored.load(path)
    autotune = {
        "winner": entry_at["winner"],
        "times_s": entry_at["times_s"],
        "winner_not_slower_than_generic": bool(
            entry_at["times_s"][entry_at["winner"]]
            <= entry_at["times_s"]["xla"]
        ),
        "roundtrip_entries": n_loaded,
        "roundtrip_fingerprint_stable": bool(
            restored.fingerprint() == tuner.fingerprint()
        ),
    }
    assert autotune["winner_not_slower_than_generic"], autotune
    assert autotune["roundtrip_fingerprint_stable"], autotune
    _row(
        "filter_autotune", 0.0,
        f"winner={autotune['winner']};entries={n_loaded}",
    )

    with open(json_path, "w") as f:
        json.dump(
            {
                "bench": "filter",
                "entries": entries,
                "autotune": autotune,
                "digest_preserved_eps0": digest_preserved,
                "monotone_gemm_reduction": monotone,
                "cache_stats": mm.cache_stats(),
            },
            f, indent=2,
        )
    print(f"# wrote {json_path}", flush=True)


def bench_serve(json_path: str) -> None:
    """Continuous vs static vs paged serving -> BENCH_serve.json.

    One ragged-arrival trace (adjacent requests alternate short/long
    decode depths — the shape static batching is worst at), served three
    ways through the *same* scheduler loop:

    * ``static``     — admit only when every slot is free (classic batch
                       serving; the baseline).
    * ``continuous`` — admit into any free slot every step.
    * ``paged``      — continuous + paged KV backend (``serve.pages``).

    Records tokens/s and p50/p99 per-step latency, asserts all three
    produce identical greedy outputs per request, and that continuous
    needs strictly fewer steps than static.  Also round-trips the
    persistent plan service (``serve.plan_service``): cold warm-up tunes,
    a restored service re-applies winners with zero tuner runs — the CI
    gate re-checks that across *processes*.
    """
    import json

    import jax

    from repro.configs import get_config
    from repro.dist.context import ParallelCtx
    from repro.models.model import init_model
    from repro.serve import engine
    from repro.serve.plan_service import PlanService
    from repro.serve.scheduler import Scheduler, ragged_trace

    cfg = get_config("llama3.2-1b", smoke=True)
    ctx = ParallelCtx(mesh=None)
    params = init_model(jax.random.PRNGKey(0), cfg, ctx)
    n_slots, max_len = 4, 48

    def trace():
        return ragged_trace(
            16, prompt_lens=(8, 16), gen_lens=(4, 24),
            vocab=cfg.vocab_size, seed=7,
        )

    entries, outputs = {}, {}
    for name, mode, backend in (
        ("static", "static", "dense"),
        ("continuous", "continuous", "dense"),
        ("paged", "continuous", "paged"),
    ):
        sched = Scheduler(
            params, cfg, ctx, n_slots=n_slots, max_len=max_len,
            mode=mode, backend=backend, page_size=8,
        )
        res = sched.run(trace())
        outputs[name] = res.pop("outputs")
        entries[name] = res
        _row(
            f"serve_{name}", res["p50_step_ms"] * 1e3,
            f"tok/s={res['tokens_per_s']:.1f};steps={res['steps']};"
            f"p99_ms={res['p99_step_ms']:.2f}",
        )
    assert outputs["continuous"] == outputs["static"] == outputs["paged"], (
        "serving modes disagree on greedy outputs"
    )
    assert entries["continuous"]["steps"] < entries["static"]["steps"], (
        entries["continuous"]["steps"], entries["static"]["steps"],
    )
    speedup = (
        entries["continuous"]["tokens_per_s"]
        / entries["static"]["tokens_per_s"]
    )
    _row("serve_speedup", 0.0, f"continuous/static={speedup:.2f}x")

    # plan-service persistence: cold tune -> save -> restore -> zero tunes
    import os
    import tempfile

    import numpy as _np
    from jax.sharding import Mesh

    mesh = Mesh(_np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    pctx = ParallelCtx(mesh=mesh, matmul_strategy="auto")
    cold = PlanService()
    engine.warm_matmul_plans(
        cfg, pctx, n_slots, 16, warm_executables=False, service=cold
    )
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "plans.json")
        cold.save(path)
        warm = PlanService()
        n_loaded = warm.load(path)
        engine.warm_matmul_plans(
            cfg, pctx, n_slots, 16, warm_executables=False, service=warm
        )
    plan_svc = {
        "cold_tunes": cold.stats["tunes"],
        "warm_tunes": warm.stats["tunes"],
        "warm_hits": warm.stats["hits"],
        "entries": n_loaded,
        "traffic": cold.traffic,
        "fingerprint_stable": bool(
            warm.fingerprint() == cold.fingerprint()
        ),
    }
    assert plan_svc["cold_tunes"] > 0, plan_svc
    assert plan_svc["warm_tunes"] == 0, plan_svc
    assert plan_svc["fingerprint_stable"], plan_svc
    _row(
        "serve_plan_service", 0.0,
        f"cold_tunes={plan_svc['cold_tunes']};"
        f"warm_tunes={plan_svc['warm_tunes']}",
    )

    with open(json_path, "w") as f:
        json.dump(
            {
                "bench": "serve",
                "trace": {
                    "requests": 16, "prompt_lens": [8, 16],
                    "gen_lens": [4, 24], "n_slots": n_slots,
                    "max_len": max_len,
                },
                "entries": entries,
                "speedup_continuous_vs_static": speedup,
                "outputs_identical_across_modes": True,
                "plan_service": plan_svc,
            },
            f, indent=2,
        )
    print(f"# wrote {json_path}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default="BENCH_summa.json")
    ap.add_argument("--sched-json", default="BENCH_sched.json")
    ap.add_argument("--ranksparse-json", default="BENCH_ranksparse.json")
    ap.add_argument("--contract-json", default="BENCH_contract.json")
    ap.add_argument("--spgemm-json", default="BENCH_spgemm.json")
    ap.add_argument("--filter-json", default="BENCH_filter.json")
    ap.add_argument("--serve-json", default="BENCH_serve.json")
    ap.add_argument(
        "--only",
        help="comma-separated list of JSON-writing sections to run "
        "(ranksparse, sched, summa, contract, spgemm, filter, serve), "
        "e.g. --only summa,contract (CI artifact jobs)",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    runners = {
        "summa": lambda: bench_planned_sparse(args.json),
        "sched": lambda: bench_sched(args.sched_json),
        "ranksparse": lambda: bench_ranksparse(args.ranksparse_json),
        "contract": lambda: bench_contract(args.contract_json),
        "spgemm": lambda: bench_spgemm(args.spgemm_json),
        "filter": lambda: bench_filter(args.filter_json),
        "serve": lambda: bench_serve(args.serve_json),
    }
    if args.only is not None:
        names = [s.strip() for s in args.only.split(",") if s.strip()]
        valid = ", ".join(sorted(runners))
        if not names:
            ap.error(f"--only: empty bench list (valid benches: {valid})")
        unknown = [s for s in names if s not in runners]
        if unknown:
            ap.error(
                f"--only: unknown bench name(s) {', '.join(unknown)} "
                f"(valid benches: {valid})"
            )
        print("name,us_per_call,derived")
        for s in names:
            runners[s]()
        return
    print("name,us_per_call,derived")
    bench_table1()
    bench_planned_sparse(args.json)
    bench_sched(args.sched_json)
    bench_ranksparse(args.ranksparse_json)
    bench_contract(args.contract_json)
    bench_spgemm(args.spgemm_json)
    bench_filter(args.filter_json)
    bench_blocksparse()
    bench_strategies()
    bench_weak_scaling(args.quick)
    bench_strong_scaling(args.quick)


if __name__ == "__main__":
    main()
