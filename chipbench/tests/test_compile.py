"""Every cell's programs compiled at full size for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a described one, so a
program that does not fit the chip's memory, or a kernel Mosaic refuses,
fails here before any chip time is spent.  Compiled: the engine's call
of the cell's entry as one program, the operand generator, and the
reference's comparison; for the nonuniform entry also the eager steps
the window drives one by one (the padded product, the compaction), with
every buffer alive beside them.  Nothing runs, so these tests say nothing about results or
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
    _, w, config, traffic = run.load_cell(cell)
    n, block = config["n"], config["block"]
    mesh = run.make_mesh(config, topo.devices[: w["chips"]])
    spec = jax.ShapeDtypeStruct(
        (n, n), jnp.dtype(config["dtype"]), sharding=generate.mesh_sharding(mesh)
    )
    mm = run.entry_of(config).engine(config, traffic, mesh)
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


def test_nonuniform_steps_fit_a_v5e(topo, compiled_kernels):
    """The nonuniform call runs eagerly: per operand a row gather and its
    select, a column gather and its select, then the padded product and
    the compaction's two gathers, each a program of its own.  The host
    enqueues the whole call before the chip has run it, so every buffer
    the call makes can be alive at once (a v5e held 15.23 GiB at N =
    18432, where this sum is 16.1 GiB).  At N = 14848, padded 22016, that
    sum, with the padded product's temporaries, keeps 2.25 GiB of the
    chip free."""
    _, w, config, traffic = run.load_cell("commodity.nonuniform")
    n = config["n"]
    mesh = run.make_mesh(config, topo.devices[: w["chips"]])
    nm = run.entry_of(config).engine(config, traffic, mesh)
    padded = nm.row_b.padded_extent
    assert padded == nm.inner_b.padded_extent == nm.col_b.padded_extent == 22016
    dtype = jnp.dtype(config["dtype"])
    sharding = generate.mesh_sharding(mesh)
    spec_p = jax.ShapeDtypeStruct((padded, padded), dtype, sharding=sharding)
    product = jax.jit(lambda x, y: nm.mm(x, y)).lower(spec_p, spec_p).compile()
    assert "tpu_custom_call" in product.as_text()
    compact = jax.jit(nm._compact).lower(spec_p).compile()
    m_p, m_c = product.memory_analysis(), compact.memory_analysis()
    item = dtype.itemsize
    assert m_p.output_size_in_bytes == padded * padded * item
    assert m_c.output_size_in_bytes == n * n * item
    per_operand = 2 * padded * n * item + 2 * padded * padded * item
    whole_call = (
        2 * n * n * item + 2 * per_operand + m_p.output_size_in_bytes + m_p.temp_size_in_bytes
        + padded * n * item + m_c.output_size_in_bytes
    )
    assert whole_call <= CHIP_BYTES - 2.25 * 2**30, whole_call / 2**30
