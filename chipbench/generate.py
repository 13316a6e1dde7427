"""The one generator of the benchmark's inputs: the operands.

It reads a configuration (sizes, dtype, mesh) and a traffic mix (fills,
value distribution), both plain data files, and builds everything from
``--seed``.  The operands are made on the device in one jitted call,
straight into the engine's SUMMA shards, by an elementwise hash of each
element's coordinates: no random-bit buffer, no host copy, so making them
sets no memory peak of its own.

Both operands are dense.  A block-sparse mix needs an occupancy and a
block structure that a public source gives, and the generator of that
structure here, with A's mask passed through the harness, the reference
and the work count (``chipbench/work.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["check_traffic", "seed_keys", "make_operands", "mesh_sharding"]

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
