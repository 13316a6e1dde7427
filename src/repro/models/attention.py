"""GQA multi-head attention with RoPE / M-RoPE, causal + sliding window.

Training and serving prefill both use the differentiable jnp path (XLA
fuses it; remat bounds the S² logits); ``use_kernel=True`` swaps in the
forward-only Pallas flash-attention kernel, which no driver sets today.
TP: heads are sharded over ``ctx.tp_axis`` via sharding constraints;
GSPMD inserts the corresponding collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.context import ParallelCtx
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import layers as L
from repro.models.config import ModelConfig


def init_attention(rng, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    return {
        "wq": L.init_dense(k1, d, cfg.num_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": L.init_dense(k2, d, cfg.num_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": L.init_dense(k3, d, cfg.num_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": L.init_dense(k4, cfg.num_heads * hd, d, bias=False, dtype=dtype),
        "norm": L.init_rmsnorm(d),
    }


def _project_qkv(p, x, positions, cfg: ModelConfig, ctx: ParallelCtx):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = L.dense(p["wq"], x).reshape(b, s, cfg.num_heads, hd)
    k = L.dense(p["wk"], x).reshape(b, s, cfg.num_kv_heads, hd)
    v = L.dense(p["wv"], x).reshape(b, s, cfg.num_kv_heads, hd)
    q = ctx.wsc(q, ctx.dp, None, ctx.tp_axis, None)
    k = ctx.wsc(k, ctx.dp, None, ctx.tp_axis if cfg.num_kv_heads >= ctx.tp_size else None, None)
    v = ctx.wsc(v, ctx.dp, None, ctx.tp_axis if cfg.num_kv_heads >= ctx.tp_size else None, None)
    if cfg.rope == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        q = L.apply_mrope(q, positions, cfg.rope_theta)
        k = L.apply_mrope(k, positions, cfg.rope_theta)
    return q, k, v


def attention(
    p: dict,
    x: jax.Array,  # (B, S, D)
    positions: jax.Array,  # (B, S) or (B, S, 3) for mrope
    cfg: ModelConfig,
    ctx: ParallelCtx,
    *,
    window: int | None = None,
    use_kernel: bool = False,
    return_kv: bool = False,
):
    """Self-attention sublayer (pre-norm, residual added by caller)."""
    h = L.rmsnorm(p["norm"], x, cfg.norm_eps)
    q, k, v = _project_qkv(p, h, positions, cfg, ctx)
    # (B, S, H, Dh) -> (B, H, S, Dh)
    qt, kt, vt = (z.transpose(0, 2, 1, 3) for z in (q, k, v))
    if use_kernel:
        o = kops.flash_attention(qt, kt, vt, causal=cfg.causal, window=window)
    elif ctx.attention_impl == "chunked":
        from repro.models.chunked_attention import chunked_attention

        o = chunked_attention(qt, kt, vt, causal=cfg.causal, window=window)
    else:
        o = kref.flash_attention_ref(qt, kt, vt, causal=cfg.causal, window=window)
    b, s = x.shape[0], x.shape[1]
    o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
    o = L.dense(p["wo"], o)
    o = ctx.wsc(o, *([ctx.dp, None, None]))
    if return_kv:
        return o, (kt, vt)  # post-RoPE (B, Hkv, S, Dh)
    return o
