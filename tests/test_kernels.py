"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparsity import random_block_mask
from repro.kernels import ops, ref
from repro.kernels.tiled_matmul import vmem_bytes

RNG = np.random.default_rng(0)


def _arr(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,k,n", [(64, 64, 64), (128, 256, 64), (96, 160, 224), (100, 60, 36)]
)
def test_tiled_matmul(m, k, n, dtype):
    a, b = _arr((m, k), dtype), _arr((k, n), dtype)
    out = ops.tiled_matmul(a, b, bm=64, bk=64, bn=64)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=_tol(dtype), atol=_tol(dtype) * k ** 0.5,
    )


@pytest.mark.parametrize(
    "shape,itemsizes,want",
    [
        # large aligned panels: the cells' local products, bf16 in, fp32 out
        ((32768, 32768, 32768), (2, 4), (1024, 1024, 2048)),
        ((24576, 24576, 24576), (2, 4), (1024, 1024, 2048)),
        ((3072, 1024, 3072), (2, 4), (1024, 1024, 1024)),
        ((1536, 1024, 1024), (2, 4), (512, 1024, 1024)),
        ((4096, 1536, 2048), (2, 4), (512, 512, 1024)),
        ((1536, 512, 1536), (2, 2), (512, 512, 512)),
        # fp32 operands need more VMEM: the first triple is over budget
        ((4096, 4096, 4096), (4, 4), (1024, 1024, 1024)),
        # at or under 256, or divided by no large triple: today's tiles
        ((256, 256, 256), (2, 4), (256, 256, 256)),
        ((100, 60, 36), (4, 4), (100, 60, 36)),
        ((256, 32768, 32768), (2, 4), (256, 256, 256)),
        ((32768, 256, 32768), (2, 4), (256, 256, 256)),
        ((768, 768, 768), (2, 4), (256, 256, 256)),
        ((1000, 2048, 2048), (2, 4), (256, 256, 256)),
        ((300, 200, 4096), (2, 4), (256, 200, 256)),
    ],
)
def test_choose_tiles(shape, itemsizes, want):
    got = ops.choose_tiles(*shape, *itemsizes)
    assert got == want
    assert vmem_bytes(*got, *itemsizes) <= ops.TILE_VMEM_BUDGET


@pytest.mark.parametrize(
    "shape,tiles,choice",
    [
        ((1024, 2048, 2048), None, "1024x2048x2048->1024x1024x2048"),
        ((1024, 2048, 2048), (512, 1024, 1024), "1024x2048x2048->512x1024x1024"),
        ((1024, 2048, 2048), (256, 256, 256), "1024x2048x2048->256x256x256"),
        ((300, 200, 520), None, "300x200x520->256x200x256"),
    ],
)
def test_tiled_matmul_tile_choice(shape, tiles, choice):
    """The rule's triple, or the caller's, runs and is counted: 2 grid
    steps of the rule's blocks, 8 of (512, 1024, 1024); padding where 256
    does not divide."""
    m, k, n = shape
    a, b = _arr((m, k), jnp.bfloat16), _arr((k, n), jnp.bfloat16)
    kw = dict(zip(("bm", "bk", "bn"), tiles)) if tiles else {}
    before = ops.tile_choice_stats().get(choice, 0)
    out = ops.tiled_matmul(a, b, out_dtype=jnp.float32, **kw)
    assert ops.tile_choice_stats()[choice] == before + 1
    want = ref.matmul_ref(a, b, out_dtype=jnp.float32)
    assert out.shape == (m, n) and out.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-3
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fill", [0.1, 0.4, 1.0])
@pytest.mark.parametrize("mb,kb", [(4, 8), (2, 2), (8, 4)])
def test_bsmm(fill, mb, kb, dtype):
    m, k, n = mb * 32, kb * 32, 96
    a, b = _arr((m, k), dtype), _arr((k, n), dtype)
    mask = random_block_mask(mb, kb, fill, seed=int(fill * 10) + mb)
    out = ops.bsmm(a, b, mask, bn=32)
    want = ref.bsmm_ref(a, b, mask)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=_tol(dtype), atol=_tol(dtype) * k ** 0.5,
    )


def test_bsmm_empty_rows_give_zero():
    mask = np.zeros((4, 4), dtype=bool)
    mask[0, 0] = True  # only one live block
    a, b = _arr((128, 128), jnp.float32), _arr((128, 64), jnp.float32)
    out = np.asarray(ops.bsmm(a, b, mask, bn=32))
    assert np.all(out[32:] == 0.0)
    assert np.any(out[:32] != 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,d,f,e,bt", [(256, 64, 96, 4, 64), (512, 128, 64, 8, 128)])
def test_grouped_gemm(t, d, f, e, bt, dtype):
    x = _arr((t, d), dtype)
    w = _arr((e, d, f), dtype)
    te = jnp.asarray(RNG.integers(0, e, size=t // bt), jnp.int32)
    out = ops.grouped_gemm(x, w, te, bt=bt, bk=64, bn=32)
    want = ref.grouped_gemm_ref(x, w, te, bt)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=_tol(dtype), atol=_tol(dtype) * d ** 0.5,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "h,hkv,s,causal,window",
    [
        (4, 2, 256, True, None),
        (4, 1, 256, True, 64),
        (2, 2, 128, False, None),
        (8, 4, 512, True, 128),
    ],
)
def test_flash_attention(h, hkv, s, causal, window, dtype):
    b, dh = 2, 64
    q = _arr((b, h, s, dh), dtype)
    k = _arr((b, hkv, s, dh), dtype)
    v = _arr((b, hkv, s, dh), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window, bq=128, bk=128)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 2e-3,
        atol=2e-2 if dtype == jnp.bfloat16 else 2e-3,
    )


def test_flash_attention_fully_masked_rows():
    """window smaller than block: early rows attend to <= window keys."""
    b, h, s, dh = 1, 2, 256, 64
    q, k, v = (_arr((b, h, s, dh), jnp.float32) for _ in range(3))
    out = ops.flash_attention(q, k, v, causal=True, window=8, bq=128, bk=128)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)
