"""Quickstart: task-based SUMMA in five minutes.

    PYTHONPATH=src python examples/quickstart.py

Demonstrates the paper's algorithm family on an emulated 2x4 mesh:
procedural baseline, multiple-issue task-based SUMMA (Eq. 1 lookahead),
and the all-gather extreme — all bit-compatible with the dense oracle.
"""
import os
import sys

sys.path.insert(0, "src")

# CPU-only: the 8-device mesh is emulated, which only the CPU backend does
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax.numpy as jnp
import numpy as np

from repro.core import DistributedMatmul, multi_issue_limit, reference_matmul
from repro.launch.mesh import make_mesh


def main():
    mesh = make_mesh((2, 4), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)}")

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(512, 1024)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(1024, 768)), jnp.float32)
    want = np.asarray(reference_matmul(a, b))

    # paper Eq. (1): how many SUMMA iterations are in flight
    k_steps = 8
    print(
        f"multiple-issue limit I(P_row=2, P_col=4, K={k_steps}) = "
        f"{multi_issue_limit(2, 4, k_steps)}"
    )

    for strategy in ("procedural", "taskbased", "allgather"):
        mm = DistributedMatmul(mesh, strategy=strategy, k_blocks=k_steps)
        got = np.asarray(mm(a, b))
        err = np.abs(got - want).max()
        print(f"{strategy:11s}: max |err| = {err:.2e}")

    # over-decomposition: more K panels -> finer pipeline slots
    for kb in (4, 8, 16):
        mm = DistributedMatmul(mesh, strategy="taskbased", k_blocks=kb)
        got = np.asarray(mm(a, b))
        print(f"k_blocks={kb:3d}: max |err| = {np.abs(got - want).max():.2e}")


if __name__ == "__main__":
    main()
