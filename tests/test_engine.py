"""Compiled execution engine: plan-digest keys, executable caching,
and the zero-retrace guarantee.

The engine's contract (core/summa.py executable cache + the compiled
step programs in core/contract.py) is that a *repeat* call with
identical geometry performs zero retraces and zero cache misses — the
whole hot path is one cached dispatch.  These tests pin that contract
via the observable counters (``DistributedMatmul.cache_stats()`` /
``executable_cache_stats()``) instead of timing, so they are stable on
any machine.
"""
import dataclasses
import json

import numpy as np
import pytest

from conftest import contract_case
from repro.core import (
    DistributedMatmul,
    clear_executable_cache,
    executable_cache_stats,
    warm_plan_executable,
)
from repro.launch.mesh import make_host_mesh


def _delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after if k in before}


def _mm():
    return DistributedMatmul(make_host_mesh(1, 1), strategy="taskbased")


# ---------------------------------------------------------------------------
# plan digests: the executable cache key must be stable and sensitive
# ---------------------------------------------------------------------------


def test_plan_digest_stable_across_calls():
    mm = _mm()
    rng = np.random.default_rng(0)
    plan = mm.plan(64, 96, 80, b_mask=rng.random((8, 4)) < 0.6)
    assert plan.digest() == plan.digest()
    # an identically-built plan hashes identically
    mm2 = _mm()
    plan2 = mm2.plan(64, 96, 80, b_mask=plan.b_mask)
    assert plan2.digest() == plan.digest()


def test_plan_digest_sensitive_to_execution_fields():
    mm = _mm()
    rng = np.random.default_rng(1)
    mask = rng.random((8, 4)) < 0.6
    plan = mm.plan(64, 96, 80, b_mask=mask)
    # lookahead changes the issue schedule => must change the digest
    bumped = dataclasses.replace(
        plan, lookahead=plan.resolve_lookahead() + 1
    )
    assert bumped.digest() != plan.digest()
    # a different mask changes the task DAG => must change the digest
    other = mm.plan(64, 96, 80, b_mask=~mask)
    assert other.digest() != plan.digest()
    # a different geometry => different digest
    wider = mm.plan(64, 96, 160)
    assert wider.digest() != plan.digest()


# ---------------------------------------------------------------------------
# executable cache: warm => hit; retraces never exceed misses
# ---------------------------------------------------------------------------


def test_warm_plan_executable_populates_cache():
    import jax.numpy as jnp

    from repro.core import summa as sm

    clear_executable_cache()
    mm = _mm()
    rng = np.random.default_rng(2)
    mask = rng.random((4, 4)) < 0.7
    plan = mm.plan(64, 64, 64, a_mask=mask)
    assert warm_plan_executable(plan, jnp.float32)
    warmed = executable_cache_stats()
    assert warmed["misses"] >= 1 and warmed["size"] >= 1
    a = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    out = sm.execute_plan(a, b, plan)
    after = executable_cache_stats()
    d = _delta(warmed, after)
    assert d["hits"] == 1 and d["misses"] == 0 and d["retraces"] == 0
    a_np = np.asarray(a) * np.kron(mask, np.ones((16, 16), np.float32))
    np.testing.assert_allclose(
        np.asarray(out), a_np @ np.asarray(b), atol=5e-4, rtol=1e-4,
    )


def test_executable_retraces_never_exceed_misses():
    """A retrace without a miss means a cache key failed to capture
    something the trace depends on — the core invariant of the cache."""
    stats = executable_cache_stats()
    assert stats["retraces"] <= stats["misses"]


# ---------------------------------------------------------------------------
# contract(): repeat call with identical geometry => 100% hit, 0 retrace
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["free2", "batch", "rank_sparse",
                                    "nonuniform"])
def test_contract_repeat_is_all_hits(family):
    mesh = make_host_mesh(1, 1)
    mm = DistributedMatmul(mesh, strategy="taskbased")
    case = contract_case(family, seed=13)
    out1 = mm.contract(case["spec"], case["x"], case["y"], tile=case["tile"])
    before = mm.cache_stats()
    out2 = mm.contract(case["spec"], case["x"], case["y"], tile=case["tile"])
    after = mm.cache_stats()
    d = _delta(before["contract"], after["contract"])
    assert d["step_misses"] == 0, d
    assert d["step_retraces"] == 0, d
    assert d["step_hits"] >= 1, d
    assert d["geom_misses"] == 0, d
    exec_d = _delta(before["executable"], after["executable"])
    assert exec_d.get("retraces", 0) == 0, exec_d
    np.testing.assert_array_equal(
        np.asarray(out1.data), np.asarray(out2.data)
    )


def test_contract_repeat_fresh_data_same_geometry_is_all_hits():
    """New operand *values* with the same block structure must reuse the
    compiled program (data is a runtime argument, not a baked constant)
    and still produce correct results."""
    import jax.numpy as jnp

    from repro.core import BlockSparseTensor

    mesh = make_host_mesh(1, 1)
    mm = DistributedMatmul(mesh, strategy="taskbased")
    rng = np.random.default_rng(17)
    mask = rng.random((4, 8)) < 0.6

    def operands(seed):
        r = np.random.default_rng(seed)
        x = BlockSparseTensor.from_dense(
            jnp.asarray(r.normal(size=(64, 96)).astype(np.float32)),
            block_shape=(16, 12), mask=mask,
        )
        y = BlockSparseTensor.from_dense(
            jnp.asarray(r.normal(size=(96, 80)).astype(np.float32)),
            block_shape=(12, 20),
        )
        return x, y

    x1, y1 = operands(1)
    mm.contract("ab,bc->ac", x1, y1, tile=64)
    before = mm.cache_stats()
    x2, y2 = operands(2)
    out = mm.contract("ab,bc->ac", x2, y2, tile=64)
    d = _delta(before["contract"], mm.cache_stats()["contract"])
    assert d["step_misses"] == 0 and d["step_retraces"] == 0, d
    ref = np.einsum(
        "ab,bc->ac",
        np.asarray(x2.to_dense(), np.float64),
        np.asarray(y2.to_dense(), np.float64),
    )
    np.testing.assert_allclose(
        np.asarray(out.data), ref, atol=5e-4, rtol=1e-4
    )


def test_contract_chain_repeat_is_all_hits():
    import jax.numpy as jnp

    from repro.core import BlockSparseTensor, contract_chain

    mesh = make_host_mesh(1, 1)
    mm = DistributedMatmul(mesh, strategy="taskbased")
    rng = np.random.default_rng(23)

    def dense(shape, block):
        return BlockSparseTensor.from_dense(
            jnp.asarray(rng.normal(size=shape).astype(np.float32)),
            block_shape=block,
        )

    x = dense((64, 96), (16, 12))
    y1 = dense((96, 80), (12, 20))
    y2 = dense((80, 48), (20, 12))
    steps = [("ab,bc->ac", x, y1), ("ab,bc->ac", y2)]
    out1, _ = contract_chain(steps, mm=mm, tile=64)
    before = mm.cache_stats()
    out2, _ = contract_chain(steps, mm=mm, tile=64)
    d = _delta(before["contract"], mm.cache_stats()["contract"])
    assert d["step_misses"] == 0, d
    assert d["step_retraces"] == 0, d
    assert d["step_hits"] >= 1, d
    np.testing.assert_array_equal(
        np.asarray(out1.data), np.asarray(out2.data)
    )
    ref = (
        np.asarray(x.to_dense(), np.float64)
        @ np.asarray(y1.to_dense(), np.float64)
        @ np.asarray(y2.to_dense(), np.float64)
    )
    np.testing.assert_allclose(
        np.asarray(out2.data), ref, atol=5e-3, rtol=1e-3
    )


# ---------------------------------------------------------------------------
# cache_stats(): shape of the observability surface
# ---------------------------------------------------------------------------


TILE_COUNTER_CODE = """
import json
import jax.numpy as jnp
import numpy as np
from repro.core import DistributedMatmul
from repro.launch.mesh import make_host_mesh
mm = DistributedMatmul(
    make_host_mesh({rows}, {cols}), strategy="taskbased",
    local_matmul="pallas", accum_dtype=jnp.float32,
)
a = jnp.asarray(np.random.default_rng(0).normal(size=(2048, 2048)), jnp.bfloat16)
for _ in range(2):
    mm(a, a).block_until_ready()
    print("KERNEL " + json.dumps(mm.cache_stats()["kernel"]))
"""


@pytest.mark.parametrize(
    "grid,want",
    [
        ((1, 1), {"2048x2048x2048->1024x1024x2048": 1}),
        # two 1024-wide K panels a chip, each traced once
        ((2, 2), {"1024x1024x1024->1024x1024x1024": 2}),
    ],
)
def test_cache_stats_report_tile_choices(subproc, grid, want):
    """``cache_stats()["kernel"]`` counts the local kernel's tile choice
    per trace: the first call adds it, the cached second call does not."""
    out = subproc(TILE_COUNTER_CODE.format(rows=grid[0], cols=grid[1]), devices=4)
    lines = [ln for ln in out.splitlines() if ln.startswith("KERNEL ")]
    assert [json.loads(ln[len("KERNEL "):]) for ln in lines] == [want, want]


def test_cache_stats_shape_and_reset():
    mm = _mm()
    stats = mm.cache_stats()
    assert set(stats) == {"plan", "contract", "executable", "kernel"}
    assert set(stats["plan"]) == {"size", "hits", "misses", "build_s"}
    assert {"geom_hits", "geom_misses", "step_hits", "step_misses",
            "step_retraces"} <= set(stats["contract"])
    assert {"hits", "misses", "retraces", "size", "build_s",
            "trace_s"} <= set(stats["executable"])
    rng = np.random.default_rng(3)
    mm.plan(64, 64, 64, b_mask=rng.random((4, 4)) < 0.5)
    assert mm.cache_stats()["plan"]["misses"] == 1
    assert mm.cache_stats()["plan"]["build_s"] > 0
    mm.reset_cache_stats()
    s = mm.cache_stats()
    assert s["plan"]["hits"] == 0 and s["plan"]["misses"] == 0
    assert s["plan"]["build_s"] == 0
