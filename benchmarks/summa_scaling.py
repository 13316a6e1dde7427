"""Shared runner for the paper's scaling experiments (Figs 4-8).

Each measurement runs in a fresh subprocess on the CPU backend
(``JAX_PLATFORMS=cpu``) with an emulated device count, so the parent
keeps seeing its own devices and never shares a chip with a child; every
record carries the child's ``platform``.  Two metric classes:

* wall-time / GFLOP-rate — what the paper plots.  CAVEAT (recorded in
  EXPERIMENTS.md): this container has ONE physical core, so emulated
  multi-device wall time measures the algorithm's total work + overhead,
  not true parallel speedup.
* structural metrics from the compiled HLO — per-device FLOPs and
  collective bytes (hardware-independent; these are what must scale for
  the algorithm to scale, and what the roofline consumes).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

_CHILD = r"""
import json, time, sys
import numpy as np, jax, jax.numpy as jnp
cfg_in = json.loads(sys.argv[1])
P = cfg_in["grid"]
N = cfg_in["n"]
strategy = cfg_in["strategy"]
nonuniform = cfg_in["nonuniform"]
repeats = cfg_in["repeats"]

from repro.launch.mesh import make_mesh
mesh = make_mesh((P[0], P[1]), ("data", "model"))
from repro.core import (DistributedMatmul, NonuniformMatmul, nonuniform_tiling,
                        uniform_tiling)
from repro.analysis.hlo import analyze_hlo

rng = np.random.default_rng(0)
a = jnp.asarray(rng.normal(size=(N, N)), jnp.float32)
b = jnp.asarray(rng.normal(size=(N, N)), jnp.float32)
mm = DistributedMatmul(mesh, strategy=strategy, k_blocks=cfg_in["k_blocks"])

if nonuniform:
    nb = max(N // cfg_in["block"], 1)  # paper: avg logical block 256
    tilings = [nonuniform_tiling(N, nb, seed=s) for s in (1, 2, 3)]
    # physical tile 64: bounds bucketization padding waste to ~12% per dim
    run = NonuniformMatmul(mm, *tilings, tile=64)
else:
    run = mm

fn = jax.jit(lambda a, b: run(a, b))
lowered = fn.lower(a, b)
compiled = lowered.compile()
wc = analyze_hlo(compiled.as_text())

out = fn(a, b)
out.block_until_ready()   # warmup (compile already done)
t0 = time.perf_counter()
for _ in range(repeats):
    out = fn(a, b)
out.block_until_ready()
wall = (time.perf_counter() - t0) / repeats

flops_total = 2.0 * N * N * N
print(json.dumps({
    "platform": jax.devices()[0].platform,
    "wall_s": wall,
    "gflops": flops_total / wall / 1e9,
    "flops_per_device_hlo": wc.flops,
    "coll_bytes_per_device": wc.coll_bytes,
    "coll_breakdown": wc.coll_bytes_by_op,
}))
"""


def run_config(
    grid: tuple[int, int],
    n: int,
    *,
    strategy: str = "taskbased",
    nonuniform: bool = False,
    block: int = 256,
    k_blocks: int | None = None,
    repeats: int = 3,
) -> dict:
    devices = grid[0] * grid[1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # emulated devices exist only on the CPU
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    payload = json.dumps(
        {
            "grid": list(grid),
            "n": n,
            "strategy": strategy,
            "nonuniform": nonuniform,
            "block": block,
            "k_blocks": k_blocks or max(grid),
            "repeats": repeats,
        }
    )
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, payload],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])
