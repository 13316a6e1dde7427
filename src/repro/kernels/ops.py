"""Public wrappers for the Pallas kernels.

Handle shape padding, tile selection, dtype policy, and backend dispatch:
on TPU the kernels run compiled; on CPU they run in ``interpret=True``
mode (Python-level execution of the kernel body) so every test validates
the *same* kernel code that targets the MXU.  There is no fallback: a
shape the kernel cannot take raises, on either backend.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparsity import block_csr_from_mask
from repro.kernels.bsmm import bsmm_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grouped_gemm import grouped_gemm_pallas
from repro.kernels.tiled_matmul import (
    DEFAULT_BK,
    DEFAULT_BM,
    DEFAULT_BN,
    tiled_matmul_pallas,
    vmem_bytes,
)
from repro.kernels.tpu import interpret_mode

__all__ = [
    "tiled_matmul",
    "choose_tiles",
    "tile_choice_stats",
    "bsmm",
    "grouped_gemm",
    "ranksparse_matmul",
    "flash_attention",
]


def _pad2(x, mults):
    pads = [(0, -(-d // m) * m - d) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def _pick_tile(dim: int, pref: int) -> int:
    """Tile for one kernel dimension: the whole dimension when it fits in
    ``pref`` (a full-extent block is legal on TPU at any size), else
    ``pref`` with the dimension padded up to a multiple of it.  Callers
    keep ``pref`` a multiple of 128 for the chip."""
    return dim if dim <= pref else pref


#: ``tiled_matmul``'s block triples for large panels, fastest first: a
#: triple is taken where each block divides its operand dimension and the
#: launch's VMEM (``vmem_bytes``) fits ``TILE_VMEM_BUDGET``.  From the
#: chip sweep of ``scripts/tile_sweep.py`` (PERF.md, section 6): on a v5e
#: at 32768^3 and 24576^3 the first reaches 98% of the MXU's peak and the
#: others take 1%, 4%, 8% and 32% longer; the budget is the first's VMEM.
LARGE_TILES = (
    (1024, 1024, 2048),
    (1024, 1024, 1024),
    (512, 1024, 1024),
    (512, 512, 1024),
    (512, 512, 512),
)
TILE_VMEM_BUDGET = 36 * 2**20

#: ``"MxKxN->bmxbkxbn"`` -> times ``tiled_matmul`` chose those tiles (each
#: trace, under jit), process-wide; ``DistributedMatmul.cache_stats()``
#: reports it as ``"kernel"``.
_TILE_CHOICES: collections.Counter = collections.Counter()


def choose_tiles(
    m: int, k: int, n: int, in_itemsize: int, out_itemsize: int
) -> tuple[int, int, int]:
    """``tiled_matmul``'s ``(bm, bk, bn)`` for an ``(m, k) @ (k, n)``
    product: the first of ``LARGE_TILES`` that divides the shape exactly
    within the VMEM budget, else the kernel's default 256-wide blocks as
    ``_pick_tile`` gives them (the whole dimension at or under 256, else
    256 with padding)."""
    for bm, bk, bn in LARGE_TILES:
        fits = vmem_bytes(bm, bk, bn, in_itemsize, out_itemsize) <= TILE_VMEM_BUDGET
        if fits and not (m % bm or k % bk or n % bn):
            return bm, bk, bn
    return tuple(
        _pick_tile(d, t) for d, t in zip((m, k, n), (DEFAULT_BM, DEFAULT_BK, DEFAULT_BN))
    )


def tile_choice_stats() -> dict:
    """``tiled_matmul``'s tile choices so far, ``{"MxKxN->bmxbkxbn": count}``."""
    return dict(_TILE_CHOICES)


def tiled_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int | None = None,
    bk: int | None = None,
    bn: int | None = None,
    out_dtype=None,
) -> jax.Array:
    """C = A @ B via the tiled Pallas kernel (fp32 accumulation),
    auto-padded; the result is ``out_dtype``, else A's dtype.  Tiles left
    ``None`` come from :func:`choose_tiles`."""
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype
    if None in (bm, bk, bn):
        cm, ck, cn = choose_tiles(
            m, k, n, a.dtype.itemsize, jnp.dtype(out_dtype).itemsize
        )
        bm, bk, bn = bm or cm, bk or ck, bn or cn
    bm = _pick_tile(m, bm)
    bk = _pick_tile(k, bk)
    bn = _pick_tile(n, bn)
    _TILE_CHOICES[f"{m}x{k}x{n}->{bm}x{bk}x{bn}"] += 1
    a_p = _pad2(a, (bm, bk))
    b_p = _pad2(b, (bk, bn))
    c = tiled_matmul_pallas(
        a_p, b_p, bm=bm, bk=bk, bn=bn, out_dtype=out_dtype, interpret=interpret_mode()
    )
    return c[:m, :n]


def bsmm(
    a: jax.Array,
    b: jax.Array,
    mask: np.ndarray,
    *,
    bn: int = 256,
    out_dtype=None,
) -> jax.Array:
    """Block-sparse C = A @ B; ``mask`` is the (M_blk, K_blk) block mask.

    Block sizes are derived from the mask grid; A's shape must divide the
    mask evenly.  Zero block rows produce zero C rows.
    """
    m, k = a.shape
    _, n = b.shape
    mask = np.asarray(mask, bool)
    mb, kb = mask.shape
    if m % mb or k % kb:
        raise ValueError(f"operand {a.shape} not divisible by mask {mask.shape}")
    bm_sz, bk_sz = m // mb, k // kb
    csr = block_csr_from_mask(mask)
    cols = jnp.asarray(csr.padded_cols(max(csr.max_row_nnz, 1)))
    bn = _pick_tile(n, bn)
    b_p = _pad2(b, (bk_sz, bn))
    c = bsmm_pallas(
        a,
        b_p,
        cols,
        bm=bm_sz,
        bk=bk_sz,
        bn=bn,
        out_dtype=out_dtype,
        interpret=interpret_mode(),
    )
    return c[:, :n]


def grouped_gemm(
    x: jax.Array,
    w: jax.Array,
    tile_expert: jax.Array,
    *,
    bt: int = 256,
    bk: int = 256,
    bn: int = 256,
    out_dtype=None,
) -> jax.Array:
    """Tile-aligned grouped GEMM (MoE expert compute)."""
    t, d = x.shape
    e, _, f = w.shape
    if t % bt:
        raise ValueError(f"token count {t} must divide tile {bt}")
    bk = _pick_tile(d, bk)
    bn = _pick_tile(f, bn)
    x_p = _pad2(x, (bt, bk))
    w_p = jnp.pad(
        w,
        (
            (0, 0),
            (0, x_p.shape[1] - d),
            (0, -(-f // bn) * bn - f),
        ),
    )
    y = grouped_gemm_pallas(
        x_p,
        w_p,
        tile_expert,
        bt=bt,
        bk=bk,
        bn=bn,
        out_dtype=out_dtype,
        interpret=interpret_mode(),
    )
    return y[:, :f]


def ranksparse_matmul(
    a_ranks,
    b: jax.Array,
    *,
    bn: int = 256,
    out_dtype=None,
) -> jax.Array:
    """Local C = A @ B with A block-rank-sparse (a ``RankCSR``).

    The ragged per-rank stage (every stored block's ``V[s] @ B[k_s]``,
    blocks of different panels and ranks interleaved) is ONE grouped-gemm
    kernel launch: stacked V rows are the tokens, each ``r_pad``-row tile
    chases its block's K panel through scalar prefetch (``tile_expert`` =
    the CSR column index), exactly the MegaBlocks layout of
    ``grouped_gemm_pallas``.  Stage 2 applies the U factors per block and
    segment-sums into C's block rows.  FLOPs scale with ``nnz · r_pad``,
    not the dense shape.
    """
    k, n = b.shape
    bm_sz, bk_sz = a_ranks.bm, a_ranks.bk
    csr = a_ranks.csr
    if k != csr.n_blocks * bk_sz:
        raise ValueError(
            f"B rows {k} != rank structure K {csr.n_blocks * bk_sz}"
        )
    out_dtype = out_dtype or b.dtype
    m = csr.m_blocks * bm_sz
    if csr.nnz == 0:
        return jnp.zeros((m, n), out_dtype)
    r_pad = a_ranks.r_pad
    # stage 1: y[s] = V[s] @ B_panel[col_idx[s]] for every stored block
    v_tokens = jnp.asarray(a_ranks.v.reshape(csr.nnz * r_pad, bk_sz))
    b_panels = b.reshape(csr.n_blocks, bk_sz, n)
    bn = _pick_tile(n, bn)
    b_p = jnp.pad(b_panels, ((0, 0), (0, 0), (0, -(-n // bn) * bn - n)))
    y = grouped_gemm_pallas(
        v_tokens,
        b_p,
        jnp.asarray(csr.col_idx),
        bt=r_pad,
        bk=bk_sz,
        bn=bn,
        out_dtype=jnp.float32,
        interpret=interpret_mode(),
    )[:, :n]
    # stage 2: per-block U application + segment sum into C block rows
    y3 = y.reshape(csr.nnz, r_pad, n)
    partials = jnp.einsum(
        "sbr,srn->sbn", jnp.asarray(a_ranks.u), y3,
        preferred_element_type=jnp.float32,
    )
    row_ids = jnp.asarray(
        np.repeat(np.arange(csr.m_blocks), csr.row_lengths())
    )
    c_blocks = jax.ops.segment_sum(
        partials, row_ids, num_segments=csr.m_blocks
    )
    return c_blocks.reshape(m, n).astype(out_dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    bq: int = 256,
    bk: int = 256,
) -> jax.Array:
    """Tiled online-softmax attention (forward).

    Sequence lengths longer than a block must be multiples of it; the
    kernel raises otherwise (the jnp path, ``ref.flash_attention_ref``,
    takes any length).
    """
    bq = _pick_tile(q.shape[2], bq)
    bk = _pick_tile(k.shape[2], bk)
    return flash_attention_pallas(
        q,
        k,
        v,
        causal=causal,
        window=window,
        scale=scale,
        bq=bq,
        bk=bk,
        interpret=interpret_mode(),
    )
