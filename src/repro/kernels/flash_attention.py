"""FlashAttention (forward) Pallas TPU kernel.

Online-softmax tiled attention with causal and sliding-window masking and
GQA head grouping.  Blocks that the mask eliminates entirely are skipped
with ``pl.when`` (no MXU work, no VMEM traffic beyond the prefetch), which
makes causal attention ~2× and sliding-window attention O(S·W) — the same
"skip empty blocks" discipline as the block-sparse matmul kernel.

Reached through ``kernels.ops.flash_attention`` and the
``attention(..., use_kernel=True)`` switch; serving prefill and training
run the jnp path (``ref.py::flash_attention_ref``, also the oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import check_blocks

__all__ = ["flash_attention_pallas"]

_LANES = 128
_NEG_INF = -1e30


def _fa_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    bq: int,
    bk: int,
    k_steps: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk

    # Static-shape mask reasoning is impossible (qi/ki traced), so the
    # skip is a runtime predicate — cheap, and the backend elides the
    # whole block body.
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1  # some key <= some query
    if window is not None:
        live &= q_start - (k_start + bk - 1) < window

    @pl.when(live)
    def _attend():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        pos_q = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        pos_k = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= pos_q >= pos_k
        if window is not None:
            mask &= pos_q - pos_k < window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == k_steps - 1)
    def _flush():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bk", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, H, S, Dh)
    k: jax.Array,  # (B, Hkv, S, Dh)
    v: jax.Array,  # (B, Hkv, S, Dh)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, h, s, dh = q.shape
    _, hkv, sk, _ = k.shape
    if s % bq or sk % bk:
        raise ValueError(f"seq {s}/{sk} must divide blocks ({bq},{bk})")
    if h % hkv:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {hkv}")
    if not interpret:
        check_blocks(
            "flash_attention", ((bq, dh), (s, dh)), ((bk, dh), (sk, dh))
        )
    g = h // hkv
    scale_val = float(scale) if scale is not None else 1.0 / float(np.sqrt(dh))
    qf = q.reshape(b * h, s, dh)
    kf = k.reshape(b * hkv, sk, dh)
    vf = v.reshape(b * hkv, sk, dh)
    k_steps = sk // bk
    grid = (b * h, s // bq, k_steps)

    def kv_index(bh, qi, ki):
        return ((bh // h) * hkv + (bh % h) // g, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _fa_kernel,
            scale=scale_val,
            causal=causal,
            window=window,
            bq=bq,
            bk=bk,
            k_steps=k_steps,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, bk, dh), kv_index),
            pl.BlockSpec((None, bk, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((None, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, h, s, dh)
