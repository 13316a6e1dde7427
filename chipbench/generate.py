"""The one generator of the benchmark's inputs: the operands and their
blocking.

It reads a configuration (sizes, dtype, mesh) and a traffic mix (fills,
value distribution, an optional tiling), both plain data files.  The
operands are made on the device from ``--seed`` in one jitted call,
straight into the engine's SUMMA shards, by an elementwise hash of each
element's coordinates: no random-bit buffer, no host copy, so making them
sets no memory peak of its own.

A mix may name a logical tiling (``"blocks"``, ``block_sizes``).  It is
fixed by the mix, never by ``--seed``: a deployment's blocking comes from
its basis, and the padded extent it gives sets the kernel's tiles.  Which
engine call a cell times is its configuration's entry
(``chipbench/entries/``); each entry checks that the mix is one it can
run.

Both operands are dense.  A block-sparse mix needs an occupancy and a
block structure that a public source gives, and the generator of that
structure here, with A's mask passed to an entry, the reference and the
work count (``chipbench/work.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["check_traffic", "block_sizes", "seed_keys", "make_operands", "mesh_sharding"]

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


def check_traffic(traffic: dict) -> None:
    """Refuse a mix this generator and the harness's loop cannot make:
    one closed-loop client, normal values, A and B dense."""
    wanted = {"loop": "closed", "clients": 1, "values": "normal", "a_fill": 1.0, "b_fill": 1.0}
    for key, value in wanted.items():
        if traffic.get(key) != value:
            raise ValueError(f"traffic {traffic.get('name')!r}: {key} must be {value!r}")


def block_sizes(traffic: dict, n: int) -> tuple[int, ...]:
    """The mix's logical block sizes of a dimension of extent ``n``.

    ``"blocks": "paper_4_1"`` is the paper's section 4.1 procedure (arXiv:
    1504.05046): ``n // mean_block`` blocks of one row each, the other rows
    added one at a time at random, here by a multinomial draw over weights
    uniform in [0.9, 1.1] from ``tiling_seed``.  A copy of
    ``repro.core.blocking.nonuniform_tiling``, kept here so that an edit to
    the program cannot move the cell's structure."""
    if traffic.get("blocks") != "paper_4_1":
        raise ValueError(f"traffic {traffic.get('name')!r}: blocks must be 'paper_4_1', not {traffic.get('blocks')!r}")
    count = n // int(traffic["mean_block"])
    rng = np.random.default_rng(int(traffic["tiling_seed"]))
    weights = rng.uniform(0.9, 1.1, size=count)
    weights /= weights.sum()
    return tuple(int(c) for c in rng.multinomial(n - count, weights) + 1)


def seed_keys(seed: int) -> np.ndarray:
    """Four 32-bit keys from a seed of any size (``--seed`` may exceed
    32 bits); they enter the generator as an argument, so a new seed never
    changes the compiled program."""
    return np.random.SeedSequence(int(seed)).generate_state(4, np.uint32)


def mesh_sharding(mesh) -> NamedSharding:
    """The engine's operand layout: rows over ``data``, columns over
    ``model``."""
    return NamedSharding(mesh, P("data", "model"))


def _mix(x):
    # murmur3's 32-bit finaliser
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    return x ^ (x >> 16)


def _to_normal(h):
    """A standard normal value from each 32-bit hash ``h``: the inverse
    normal CDF, ``sqrt(2) erfinv(v)``, of ``v`` uniform on the odd
    multiples of 2**-24 in (-1, 1).  ``v`` is made in integers, so it is
    exact in float32 and never reaches +-1, where ``erfinv`` is infinite.

    Not Box-Muller: compiled for a v5e, the code for a ``cos`` over an
    (n, n) array takes the TPU compiler some 45 s at n = 16384 or 32768,
    ``erf_inv`` about 7 s."""
    odd = (h >> 8).astype(jnp.int32) * 2 - (2**24 - 1)
    v = odd.astype(jnp.float32) * np.float32(2.0**-24)
    return np.float32(np.sqrt(2.0)) * jax.lax.erf_inv(v)


def _normal(shape, k0, k1, dtype):
    """Standard normal values from a hash of (row, col)."""
    i = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    j = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    return _to_normal(_mix(_mix(i ^ k0) + j * _GOLDEN + k1)).astype(dtype)


@functools.lru_cache(maxsize=None)
def _builder(n: int, dtype: str, mesh):
    sharding = mesh_sharding(mesh)

    def build(keys):
        a = _normal((n, n), keys[0], keys[1], dtype)
        b = _normal((n, n), keys[2], keys[3], dtype)
        return a, b

    return jax.jit(build, out_shardings=(sharding, sharding))


def make_operands(config: dict, seed: int, mesh):
    """A (n, n) and B (n, n) in ``config['dtype']``, sharded over ``mesh``
    as the engine takes them, made on the device from ``seed``."""
    build = _builder(int(config["n"]), config["dtype"], mesh)
    return build(jnp.asarray(seed_keys(seed)))
