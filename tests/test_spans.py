"""The engine's profiler spans and set-up counters.

Every ``DistributedMatmul`` call opens ``jax.profiler.TraceAnnotation``
spans, nested by time on the caller's thread::

    repro.matmul
      repro.plan            (repro.plan.build inside it on a plan miss)
      repro.pad
      repro.execute
        repro.dispatch      (repro.compile on a new executable's first call)
      repro.unpad

The chip benchmark's per-layer readers (``chipbench/metrics``) find them
by these exact names, so each test records a real profile into a
temporary directory and reads it back with ``chipbench.xplane``.  The
counters ``build_s``/``trace_s`` (``executable_cache_stats``) and the plan
cache's ``build_s`` grow on a miss only.
"""
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import xplane  # noqa: E402
from repro.core import DistributedMatmul, NonuniformMatmul  # noqa: E402
from repro.core.blocking import nonuniform_tiling  # noqa: E402
from repro.core.sparsity import (  # noqa: E402
    random_rank_map,
    synthesize_rank_csr,
)
from repro.core.summa import executable_cache_stats  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402

#: the spans of a call that builds its plan and its executable ...
FIRST_CALL = (
    "repro.matmul", (
        ("repro.plan", (("repro.plan.build", ()),)),
        ("repro.pad", ()),
        ("repro.execute", (("repro.compile", ()),)),
        ("repro.unpad", ()),
    ),
)
#: ... and of the same call again, both cached
CACHED_CALL = (
    "repro.matmul", (
        ("repro.plan", ()),
        ("repro.pad", ()),
        ("repro.execute", (("repro.dispatch", ()),)),
        ("repro.unpad", ()),
    ),
)


def span_tree(calls):
    """Run each thunk of ``calls`` (blocking on its result) under the
    profiler; return the engine's host spans as a forest of ``(name,
    children)`` by containment in time."""
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            for call in calls:
                jax.block_until_ready(call())
        finally:
            jax.profiler.stop_trace()
        spans = [e for e in xplane.load(d).host if e.name.startswith("repro.")]
    spans.sort(key=lambda e: (e.start, -e.end))
    roots, stack = [], []
    for e in spans:
        while stack and not (stack[-1][0].start <= e.start and e.end <= stack[-1][0].end):
            stack.pop()
        node = (e, [])
        (stack[-1][1] if stack else roots).append(node)
        stack.append(node)

    def names(node):
        return (node[0].name, tuple(names(c) for c in node[1]))

    return tuple(names(r) for r in roots)


def _operands(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    return a, b


def test_spans_1x1_first_call_compiles_second_dispatches():
    mesh = make_host_mesh(1, 1)
    mm = DistributedMatmul(mesh, k_blocks=2)
    # shapes no other test uses, so the executable is new to this process
    a, b = _operands(88, 48, 40)
    tree = span_tree([lambda: mm(a, b), lambda: mm(a, b)])
    assert tree == (FIRST_CALL, CACHED_CALL)


SPANS_2X2_CODE = r'''
import json

import jax.numpy as jnp
import numpy as np

from repro.core import DistributedMatmul
from repro.launch.mesh import make_mesh
from test_spans import span_tree

mesh = make_mesh((2, 2), ("data", "model"))
mm = DistributedMatmul(mesh, strategy="taskbased", k_blocks=4)
rng = np.random.default_rng(0)
a = jnp.asarray(rng.normal(size=(96, 72)), jnp.float32)
b = jnp.asarray(rng.normal(size=(72, 56)), jnp.float32)
print("SPANS " + json.dumps(span_tree([lambda: mm(a, b), lambda: mm(a, b)])))
'''


def _tuples(x):
    return tuple(_tuples(v) for v in x) if isinstance(x, list) else x


def test_spans_2x2_first_call_compiles_second_dispatches(subproc):
    out = subproc(SPANS_2X2_CODE, devices=4)
    line = next(ln for ln in out.splitlines() if ln.startswith("SPANS "))
    assert _tuples(json.loads(line[len("SPANS "):])) == (FIRST_CALL, CACHED_CALL)


def test_spans_nonuniform_call_is_one_matmul():
    """The gather and scatter of ``NonuniformMatmul`` are ``repro.pad``
    and ``repro.unpad`` inside its own ``repro.matmul``, around the padded
    product's; no ``repro.matmul`` nests in another."""
    mesh = make_host_mesh(1, 1)
    rt = nonuniform_tiling(60, 4, seed=1)
    it = nonuniform_tiling(52, 3, seed=2)
    ct = nonuniform_tiling(44, 3, seed=3)
    nmm = NonuniformMatmul(DistributedMatmul(mesh), rt, it, ct, tile=16)
    a, b = _operands(60, 52, 44, seed=4)
    ((name, children),) = span_tree([lambda: nmm(a, b)])
    assert name == "repro.matmul"
    assert [c[0] for c in children] == [
        "repro.pad", "repro.plan", "repro.pad", "repro.execute",
        "repro.unpad", "repro.unpad",
    ]
    assert children[3][1] == (("repro.compile", ()),)


def test_spans_ranksparse_call():
    mesh = make_host_mesh(1, 1)
    mm = DistributedMatmul(mesh, k_blocks=4)
    rmap = random_rank_map(4, 4, 16, 16, 0.5, max_rank=4, seed=7)
    a_ranks = synthesize_rank_csr(rmap, seed=8)
    _, b = _operands(64, 64, 24, seed=9)
    tree = span_tree([
        lambda: mm(None, b, a_ranks=a_ranks),
        lambda: mm(None, b, a_ranks=a_ranks),
    ])
    assert tree == (FIRST_CALL, CACHED_CALL)


def test_build_and_trace_seconds_grow_on_a_miss_only():
    mesh = make_host_mesh(1, 1)
    mm = DistributedMatmul(mesh, k_blocks=2)
    a, b = _operands(104, 40, 24, seed=5)
    before = executable_cache_stats()
    jax.block_until_ready(mm(a, b))
    missed = executable_cache_stats()
    plan_build_s = mm.cache_stats()["plan"]["build_s"]
    assert missed["misses"] == before["misses"] + 1
    build = missed["build_s"] - before["build_s"]
    trace = missed["trace_s"] - before["trace_s"]
    assert 0 < trace < build
    assert plan_build_s > 0

    jax.block_until_ready(mm(a, b))
    hit = executable_cache_stats()
    assert hit["hits"] == missed["hits"] + 1
    assert hit["build_s"] == missed["build_s"]
    assert hit["trace_s"] == missed["trace_s"]
    assert mm.cache_stats()["plan"]["build_s"] == plan_build_s
    assert mm.cache_stats()["plan"]["hits"] == 1
