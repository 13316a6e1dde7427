"""Entry ``nonuniform``: ``NonuniformMatmul`` (``core/api.py``) of two
compact (n, n) operands blocked by the mix's tiling, one tiling for rows,
inner and columns (``generate.block_sizes``).

Each timed call gathers both operands into the padded layout of
``config["block"]``-wide physical tiles, runs the padded SUMMA product
and compacts C back.  C is compared in its compact layout, so a block
misplaced or a remainder tile dropped in the compaction shows in a whole
tile's norm.
"""
from __future__ import annotations

from chipbench import generate
from chipbench.entries import dense


def check_traffic(traffic: dict) -> None:
    """The generator's loop checks; a nonuniform product needs a tiling."""
    generate.check_traffic(traffic)
    if "blocks" not in traffic:
        raise ValueError(f"traffic {traffic.get('name')!r}: entry nonuniform needs blocks, a tiling")


def engine(config: dict, traffic: dict, mesh):
    from repro.core import NonuniformMatmul
    from repro.core.blocking import Tiling

    tiling = Tiling(generate.block_sizes(traffic, int(config["n"])))
    return NonuniformMatmul(dense.engine(config, traffic, mesh), tiling, tiling, tiling, tile=int(config["block"]))


class Product(dense.Product):
    def cache_stats(self) -> dict:
        return self.engine.mm.cache_stats()

    def counters(self) -> dict:
        """``padding_waste``: per dimension, the share of the padded
        extent the engine planned that is padding."""
        return {"padding_waste": self.engine.padding_waste}


def build(config: dict, traffic: dict, seed: int, mesh) -> Product:
    check_traffic(traffic)
    a, b = generate.make_operands(config, seed, mesh)
    return Product(config, engine(config, traffic, mesh), a, b, mesh)
