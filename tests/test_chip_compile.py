"""Main-path Pallas kernels compiled for a described TPU v5e chip.

No chip is attached: ``topologies.get_topology_desc`` describes one and
the TPU compiler compiles for it, so a block the chip cannot tile or a
kernel Mosaic refuses fails here, at real widths, before any chip run.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test worker
imports this file.  Keep these tests in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core.sparsity import block_csr_from_mask, random_block_mask
from repro.kernels.bsmm import bsmm_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grouped_gemm import grouped_gemm_pallas
from repro.kernels.ops import LARGE_TILES, choose_tiles
from repro.kernels.tiled_matmul import tiled_matmul_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without the chip: keep the cache off.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered, name):
    """The compiled program runs the kernel as a TPU custom call named
    ``name``, the name the profiler trace shows for it."""
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}(\.\d+)? = \S+ custom-call\(", text), (
        f"no custom call named {name!r} in the compiled program"
    )


def test_tiled_matmul_compiles(one_chip):
    a = _spec(one_chip, (4096, 4096))
    _assert_kernel(
        tiled_matmul_pallas.lower(
            a, a, bm=256, bk=256, bn=256, out_dtype=jnp.float32,
            interpret=False,
        ),
        "tiled_matmul",
    )


@pytest.mark.parametrize("n", [32768, 24576])
def test_tiled_matmul_compiles_with_chosen_tiles(one_chip, n):
    """Each cell's local product (one chip: 32768^3; a chip of the 2x2
    mesh: 24576^3 panels) with the triple the tile rule picks, fp32 C:
    Mosaic takes the blocks and they fit the launch's scoped VMEM."""
    bm, bk, bn = choose_tiles(n, n, n, 2, 4)
    assert (bm, bk, bn) in LARGE_TILES
    a = _spec(one_chip, (n, n))
    _assert_kernel(
        tiled_matmul_pallas.lower(
            a, a, bm=bm, bk=bk, bn=bn, out_dtype=jnp.float32,
            interpret=False,
        ),
        "tiled_matmul",
    )


def test_bsmm_compiles(one_chip):
    # 16384^2 over the 64x64 grid of 256-wide blocks, fill 0.3
    csr = block_csr_from_mask(random_block_mask(64, 64, 0.3, seed=0))
    cols = _spec(one_chip, (64, csr.max_row_nnz), jnp.int32)
    a = _spec(one_chip, (16384, 16384))
    _assert_kernel(
        bsmm_pallas.lower(
            a, a, cols, bm=256, bk=256, bn=256, out_dtype=jnp.float32,
            interpret=False,
        ),
        "bsmm",
    )


def test_flash_attention_compiles(one_chip):
    # llama3.2-1b prefill: 32 query heads, 8 kv heads, head_dim 64
    q = _spec(one_chip, (1, 32, 2048, 64))
    kv = _spec(one_chip, (1, 8, 2048, 64))
    _assert_kernel(
        flash_attention_pallas.lower(
            q, kv, kv, causal=True, bq=256, bk=256, interpret=False
        ),
        "flash_attention",
    )


def test_grouped_gemm_compiles(one_chip):
    x = _spec(one_chip, (4096, 2048))
    w = _spec(one_chip, (8, 2048, 1024))
    te = _spec(one_chip, (4096 // 256,), jnp.int32)
    _assert_kernel(
        grouped_gemm_pallas.lower(
            x, w, te, bt=256, bk=256, bn=256, interpret=False
        ),
        "grouped_gemm",
    )


def test_unaligned_block_is_refused_before_lowering():
    a = jax.ShapeDtypeStruct((1024, 1024), jnp.bfloat16)
    cols = jnp.asarray(np.zeros((16, 1), np.int32))
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        jax.eval_shape(
            lambda x, y, c: bsmm_pallas(
                x, y, c, bm=64, bk=64, bn=64, interpret=False
            ),
            a, a, cols,
        )
