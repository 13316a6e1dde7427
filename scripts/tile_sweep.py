"""Time the dense Pallas kernel alone over block triples, on a TPU.

    python scripts/tile_sweep.py [--sizes 32768 24576] [--reps 3]
        [--out results/tile_sweep.json]

For each size N, C = A @ B with bf16 N x N operands and an fp32 C (what
``core.summa._local_dot`` asks for), through ``tiled_matmul_pallas`` at
every triple with ``bm`` in {256, 512, 1024} and ``bk``, ``bn`` in
{512, 1024, 2048} whose VMEM a launch may hold, beside 256-cubed blocks
and XLA's own ``jnp.dot``.  Each row is the best of ``--reps`` calls,
each ending in ``block_until_ready``, after a compiling call.  The table
``kernels.ops.LARGE_TILES`` was taken from this sweep.  Exits 1 without
a TPU.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels.tiled_matmul import MAX_VMEM, tiled_matmul_pallas, vmem_bytes

PEAK_FLOPS = 197e12  # TPU v5e, bf16


def candidates() -> list[tuple[int, int, int]]:
    grid = itertools.product((256, 512, 1024), (512, 1024, 2048), (512, 1024, 2048))
    return [(256, 256, 256)] + [t for t in grid if vmem_bytes(*t, 2, 4) <= MAX_VMEM]


def best_of(fn, reps: int) -> float:
    jax.block_until_ready(fn())  # compiles
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def sweep(n: int, reps: int) -> list[dict]:
    ka, kb = jax.random.split(jax.random.key(n))
    a = jax.random.normal(ka, (n, n), jnp.bfloat16)
    b = jax.random.normal(kb, (n, n), jnp.bfloat16)
    rows = []
    flops = 2.0 * n**3
    xla = jax.jit(lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float32))
    runs = [("xla", None, lambda: xla(a, b))] + [
        ("pallas", t, lambda t=t: tiled_matmul_pallas(
            a, b, bm=t[0], bk=t[1], bn=t[2], out_dtype=jnp.float32))
        for t in candidates()
    ]
    for route, tiles, fn in runs:
        s = best_of(fn, reps)
        row = {"n": n, "route": route, "tiles": tiles, "ms": s * 1e3,
               "tflops": flops / s / 1e12, "peak_pct": 100 * flops / s / PEAK_FLOPS}
        if tiles:
            row["vmem_mib"] = vmem_bytes(*tiles, 2, 4) / 2**20
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", type=int, nargs="+", default=[32768, 24576])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="results/tile_sweep.json")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("tile_sweep: no TPU", file=sys.stderr)
        return 1
    d = jax.devices()[0]
    rows = [r for n in args.sizes for r in sweep(n, args.reps)]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": {"platform": d.platform, "kind": d.device_kind},
                   "rows": rows}, f, indent=1)
    for n in args.sizes:
        top = sorted((r for r in rows if r["n"] == n), key=lambda r: r["ms"])
        print(f"N={n} fastest: " + ", ".join(
            f"{r['route']} {r['tiles']} {r['ms']:.1f} ms" for r in top[:5]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
