"""Every cell's programs compiled at full size for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a described one, so a
program that does not fit the chip's memory, or a kernel Mosaic refuses,
fails here before any chip time is spent.  Compiled: the engine's call
as the window drives it, the operand generator, and the reference's
comparison.  Nothing runs, so these tests say nothing about results or
speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import generate, reference, run

#: what a v5e offers a program (16 GiB less the runtime's reserve)
CHIP_BYTES = 15.75 * 2**30
CELLS = [w["name"] for w in run._json(os.path.join(run.ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without the chip: keep the cache off.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The engine asks the backend whether to interpret its Pallas
    kernels; here the backend is the CPU, the target the chip."""
    from repro.core import summa
    from repro.kernels import ops

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(summa, "interpret_mode", lambda: False)
    summa.clear_executable_cache()
    yield
    summa.clear_executable_cache()


def _total(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


@pytest.mark.parametrize("cell", CELLS)
def test_cell_programs_fit_a_v5e(cell, topo, compiled_kernels):
    from repro.core import DistributedMatmul

    _, w, config, _ = run.load_cell(cell)
    n, block = config["n"], config["block"]
    mesh = run.make_mesh(config, topo.devices[: w["chips"]])
    spec = jax.ShapeDtypeStruct(
        (n, n), jnp.dtype(config["dtype"]), sharding=generate.mesh_sharding(mesh)
    )
    mm = DistributedMatmul(
        mesh, strategy=config["strategy"], local_matmul=config["local_matmul"],
        accum_dtype=jnp.dtype(config["accum_dtype"]),
    )
    engine = jax.jit(lambda x, y: mm(x, y)).lower(spec, spec).compile()
    assert "tpu_custom_call" in engine.as_text()
    assert _total(engine) <= CHIP_BYTES, engine.memory_analysis()

    replicated = NamedSharding(mesh, P())
    keys = jax.ShapeDtypeStruct((4,), jnp.uint32, sharding=replicated)
    gen = generate._builder(n, config["dtype"], mesh).lower(keys).compile()
    assert _total(gen) <= CHIP_BYTES

    rows = reference.row_block(n, block, mesh)
    cmp = reference._compare_fn(rows, block, mesh).lower(spec, spec, spec).compile()
    # the comparison runs beside A, B and C, which the engine's arguments
    # and output already count
    m = engine.memory_analysis()
    beside = m.argument_size_in_bytes + m.output_size_in_bytes
    assert beside + cmp.memory_analysis().temp_size_in_bytes <= CHIP_BYTES
