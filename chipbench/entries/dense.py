"""Entry ``dense``: ``DistributedMatmul`` (``core/api.py``) of two dense
(n, n) operands, sharded over the configuration's mesh as the engine takes
them.  The entry of every configuration that names none.

An entry module provides ``check_traffic(traffic)``, ``engine(config,
traffic, mesh)`` (the engine object, called on the compact operands) and
``build(config, traffic, seed, mesh)``, which returns the ``Product`` the
harness times.
"""
from __future__ import annotations

import jax.numpy as jnp

from chipbench import generate, reference


def check_traffic(traffic: dict) -> None:
    """The generator's loop checks; a dense product has no tiling."""
    generate.check_traffic(traffic)
    if "blocks" in traffic:
        raise ValueError(f"traffic {traffic.get('name')!r}: blocks names a tiling, which entry dense has not")


def engine(config: dict, traffic: dict, mesh):
    from repro.core import DistributedMatmul

    return DistributedMatmul(
        mesh, strategy=config["strategy"], local_matmul=config["local_matmul"],
        accum_dtype=jnp.dtype(config["accum_dtype"]),
    )


class Product:
    """One cell's operands, made on the device from the seed, and its
    engine: ``call()`` is the timed call and returns C, ``compare(c)`` the
    numbers that decide ``correct``, ``control()`` the control's C in the
    program's place.  ``drop()`` lets the engine go before the reference
    runs."""

    def __init__(self, config: dict, engine, a, b, mesh):
        self.config, self.engine, self.a, self.b, self.mesh = config, engine, a, b, mesh
        self.block = int(config["block"])

    def call(self):
        return self.engine(self.a, self.b)

    def cache_stats(self) -> dict:
        """The engine's cache counters: ``plan`` and ``executable`` misses
        inside the window mean a build there."""
        return self.engine.cache_stats()

    def counters(self) -> dict:
        """Program counters that per-layer readers get as ``RunContext.counters``."""
        return {}

    def drop(self) -> None:
        self.engine = None

    def compare(self, c) -> dict:
        return reference.compare(self.a, self.b, c, self.block, self.mesh)

    def control(self):
        return reference.int8_control(self.a, self.b, self.block, self.config["out_dtype"], self.mesh)


def build(config: dict, traffic: dict, seed: int, mesh) -> Product:
    check_traffic(traffic)
    a, b = generate.make_operands(config, seed, mesh)
    return Product(config, engine(config, traffic, mesh), a, b, mesh)
