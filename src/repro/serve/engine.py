"""Serving engine: batched prefill + single-token decode with caches.

Cache kinds per block:

* ``attn``  — KV cache (B, Hkv, S_cache, Dh); rolling ring buffer of size
  ``window`` for sliding/local-attention archs, so the ``long_500k`` cell
  holds only O(window) state.  Decode attention shards the cache's S
  dimension over the TP axis and combines partial softmaxes with the
  log-sum-exp trick (flash-decoding on the mesh).
* ``rglru`` / ``mlstm`` / ``slstm`` — O(1) recurrent state; prefill
  derives the closed-form final state (no sequential pass where the math
  allows it).

Layout mirrors the model: stacked caches per scan unit + unrolled tail.
``pos`` is a per-slot ``(B,)`` vector counting tokens written so far in
each batch row — rows decode at independent positions, which is what the
continuous-batching scheduler (``serve.scheduler``) relies on to admit
and evict requests per step without reshaping live state.  A scalar
``pos`` (legacy fixed-shape caches) is still accepted and broadcast.

Capacity contract (non-windowed archs): decoding a token at position
``>= S_cache`` never corrupts the cache — the ring write is dropped — but
the returned logits for that row attend only to the first ``S_cache``
tokens, so they are not the true model output.  Drivers must not decode
past capacity: the serving loops raise :class:`CacheCapacityError`
instead (windowed archs wrap by design and have no capacity limit).
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.dist.context import ParallelCtx
from repro.models import layers as L
from repro.models.attention import _project_qkv, attention
from repro.models.config import ModelConfig
from repro.models.ffn import ffn
from repro.models.model import embed_inputs
from repro.models.moe import moe_ffn
from repro.models.recurrent import (
    mlstm_block,
    mlstm_step,
    rglru_block,
    rglru_step,
    slstm_block,
    slstm_step,
)

__all__ = [
    "CacheCapacityError",
    "init_cache",
    "cache_shardings",
    "prefill",
    "decode_step",
    "cache_len",
    "warm_matmul_plans",
    "warm_kernel_cache",
]


class CacheCapacityError(RuntimeError):
    """Decoding would write past the KV cache capacity of a non-windowed
    arch.  Raised by the serving drivers (``launch.serve``,
    ``serve.scheduler``) *before* the overflowing decode step — the
    engine itself drops out-of-capacity writes (never corrupts state) but
    cannot produce correct logits for tokens beyond ``S_cache``."""


def warm_matmul_plans(cfg: ModelConfig, ctx: ParallelCtx, batch: int,
                      prompt_len: int, *, warm_executables: bool = True,
                      service=None):
    """Pre-derive the SUMMA ``MatmulPlan``s for every projection shape the
    serving traces will request — prefill flattens (B, S, D) activations
    to M = B*S rows, decode to M = B — so the jitted prefill/decode paths
    hit ``DistributedMatmul``'s plan cache instead of re-deriving the
    schedule (numpy panel liveness, CSR maps, cost model) inside tracing.
    With ``matmul_strategy="auto"`` each plan is additionally *tuned*
    (repro.sched.tuner): the simulator search over lookahead x k_blocks x
    strategy runs here, once per shape, instead of inside the trace.
    With ``warm_executables`` (default) each warmed plan is also driven
    through ``core.summa``'s plan-digest-keyed executable cache at the
    serving dtype, so the first production matmul per shape dispatches a
    pre-compiled program instead of paying the trace+compile there.

    Tuned winners go through the **persistent plan service**
    (``serve.plan_service``; pass ``service=`` to override the process
    singleton): shapes whose (shape, structure digest, mesh fingerprint)
    key is already recorded re-apply the stored (strategy, k_blocks,
    lookahead, stationarity, comm_mode) without re-running the simulator
    search — the schedule analogue of ``KernelAutotuner``'s warm restore
    (seed it across processes via ``REPRO_PLAN_CACHE``).  The traffic
    shape ``(batch, prompt_len)`` is recorded so the service can pre-warm
    future processes from the observed distribution.
    Returns the warmed plans; no-op (empty) on the plain-einsum path.
    """
    from repro.core import summa as sm
    from repro.serve.plan_service import plan_service

    if not ctx.has_mesh or ctx.matmul_strategy == "xla" or ctx.pure_dp:
        return []
    svc = plan_service() if service is None else service
    svc.record_traffic(batch, prompt_len)
    d = cfg.d_model
    ffs = [cfg.d_ff] if cfg.d_ff else []
    if cfg.moe is not None and cfg.moe.num_shared_experts:
        ffs.append(cfg.moe.d_ff * cfg.moe.num_shared_experts)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    tune = ctx.matmul_strategy == "auto"
    # "auto" also lets the comm-volume model pick the stationarity: tall
    # prefill activations keep C-stationary, skinny decode shapes can win
    # with the weight-stationary variants (repro.spgemm chooser).
    stationarity = "auto" if tune else "C"
    plans = []
    for m in (batch * prompt_len, batch):
        for f in ffs:
            for k_in, n_out in ((d, f), (f, d)):
                plans.append(
                    svc.plan_projection(
                        ctx, m, k_in, n_out, itemsize=itemsize, tune=tune,
                        stationarity=stationarity,
                    )
                )
    plans = [p for p in plans if p is not None]
    if warm_executables:
        for p in {id(p): p for p in plans}.values():
            sm.warm_plan_executable(p, jnp.dtype(cfg.dtype))
    return plans


def warm_kernel_cache(cfg: ModelConfig, ctx: ParallelCtx, batch: int,
                      prompt_len: int, *, path: str | None = None,
                      routes: tuple[str, ...] | None = None,
                      repeats: int = 3):
    """Tune the kernel-autotune buckets for every *local* gemm shape the
    serving projections produce, and persist the winners.

    The per-plan local panel product is ``(m_loc, kb_width) @ (kb_width,
    n_loc)`` — that shape (bucketed) is what ``summa._local_dot`` will
    look up at trace time, so tuning here moves the benchmarking out of
    the serving path exactly like :func:`warm_matmul_plans` moves the
    simulator search out of it.  ``path`` writes the JSON cache file
    (restore it in a later process via the ``REPRO_AUTOTUNE_CACHE`` env
    var or ``KernelAutotuner.load``); ``routes`` restricts the benchmark
    sweep (interpret-mode structured kernels are slow off-TPU).  Warm the
    kernel cache **before** :func:`warm_matmul_plans`: executable cache
    keys carry the autotune fingerprint, so executables warmed against a
    cold kernel cache are re-traced once it fills.  Returns the tuned
    bucket keys.
    """
    from repro.kernels.autotune import autotune_cache, bucket_key

    plans = warm_matmul_plans(cfg, ctx, batch, prompt_len,
                              warm_executables=False)
    cache = autotune_cache()
    tuned = []
    for p in plans:
        m_loc = p.m_pad // p.p_row
        n_loc = p.n_pad // p.p_col
        key = bucket_key(m_loc, p.kb_width, n_loc, dtype=cfg.dtype)
        if key in tuned:
            continue
        cache.tune(m_loc, p.kb_width, n_loc, dtype=cfg.dtype,
                   repeats=repeats, routes=routes)
        tuned.append(key)
    if path is not None:
        cache.save(path)
    return tuned


def cache_len(cfg: ModelConfig, max_len: int) -> int:
    if cfg.window is not None:
        return min(cfg.window, max_len)
    return max_len


# ---------------------------------------------------------------------------
# cache init (abstract-friendly: pure shapes)
# ---------------------------------------------------------------------------


def _quantize_kv(x: jax.Array):
    """(.., S, Dh) -> int8 values + per-(token, head) fp32 absmax scales."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def _block_cache(
    kind: str, cfg: ModelConfig, batch: int, max_len: int, kv_quant: bool = False
):
    dh = cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.dtype)
    if kind == "attn":
        s_c = cache_len(cfg, max_len)
        shape = (batch, cfg.num_kv_heads, s_c, dh)
        if kv_quant:
            sshape = (batch, cfg.num_kv_heads, s_c, 1)
            return {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(sshape, jnp.float32),
                "v_s": jnp.zeros(sshape, jnp.float32),
            }
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    d = cfg.d_model
    if kind == "rglru":
        return {
            "h": jnp.zeros((batch, d), jnp.float32),
            "conv": jnp.zeros((batch, 3, d), jnp.float32),
        }
    if kind == "mlstm":
        di = 2 * d
        nh = cfg.num_heads
        dh_i = di // nh
        return {
            "c": jnp.zeros((batch, nh, dh_i, dh_i), jnp.float32),
            "n": jnp.zeros((batch, nh, dh_i), jnp.float32),
            "m": jnp.full((batch, nh), -1e30, jnp.float32),
            "conv": jnp.zeros((batch, 3, di), jnp.float32),
        }
    if kind == "slstm":
        return {
            "c": jnp.zeros((batch, d), jnp.float32),
            "n": jnp.ones((batch, d), jnp.float32),
            "m": jnp.zeros((batch, d), jnp.float32),
            "h": jnp.zeros((batch, d), jnp.float32),
        }
    raise ValueError(kind)


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, *, kv_quant: bool = False
):
    def unit_cache(_):
        return {
            f"b{j}": _block_cache(kind, cfg, batch, max_len, kv_quant)
            for j, kind in enumerate(cfg.block_pattern)
        }

    units = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.units,) + x.shape).copy()
        if cfg.units
        else x[None][:0],
        unit_cache(None),
    )
    tail = [
        _block_cache(kind, cfg, batch, max_len, kv_quant) for kind in cfg.tail
    ]
    return {"units": units, "tail": tail, "pos": jnp.zeros((batch,), jnp.int32)}


#: attn-cache leaf names — KV values plus their int8 quantization scales;
#: everything else in a block cache is recurrent/conv state.
_KV_LEAF_KEYS = frozenset({"k", "v", "k_s", "v_s"})


def _leaf_key(entry) -> str | int | None:
    """Dict key / sequence index of one ``KeyPath`` entry."""
    return getattr(entry, "key", getattr(entry, "idx", None))


def cache_batch_axis(path) -> int:
    """Batch axis of a cache leaf from its tree path: stacked unit caches
    carry a leading scan dimension, tail caches and ``pos`` do not."""
    return 1 if _leaf_key(path[0]) == "units" else 0


def cache_shardings(cache, ctx: ParallelCtx, batch: int):
    """Shardings for a serving cache (the one cache-sharding function —
    ``launch.dryrun`` delegates here).

    * KV values **and their int8 scales** (``k``/``v``/``k_s``/``v_s``,
      ``(units?, B, Hkv, S, Dh|1)``): batch over DP, S over TP — the
      seq-sharded decode-attention layout.
    * recurrent / conv states (``h``/``c``/``n``/``m``/``conv``) and the
      per-slot ``pos`` vector: batch over DP only.  Classification is by
      leaf *name and tree path*, never by shape sniffing — stacked conv
      caches ``(U, B, 3, d)`` and mlstm ``(U, B, nh, dh, dh)`` states must
      never land an axis on TP.
    * batch not divisible by the DP degree: the batch axis is replicated
      (the same explicit fallback ``_decode_attention`` warns about).
    """
    bs = ctx.dp if batch % max(ctx.dp_size, 1) == 0 else None

    def spec(path, leaf):
        base = [None] * leaf.ndim
        if _leaf_key(path[-1]) in _KV_LEAF_KEYS:
            base[-4] = bs  # B
            base[-2] = ctx.tp_axis  # S
            return ctx.named(*base)
        if leaf.ndim > 0:  # recurrent state or pos: batch over DP
            base[cache_batch_axis(path)] = bs
        return ctx.named(*base)

    return jax.tree_util.tree_map_with_path(spec, cache)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def _prefill_block(kind, p, x, positions, cfg, ctx, batch, max_len):
    if kind == "attn":
        o, (k, v) = attention(
            p["attn"], x, positions, cfg, ctx, window=cfg.window,
            use_kernel=False, return_kv=True,
        )
        x = x + o
        if "moe" in p:
            y, _ = moe_ffn(p["moe"], x, cfg, ctx)
            x = x + y
        elif "ffn" in p:
            x = x + ffn(p["ffn"], x, cfg, ctx)
        s = k.shape[2]
        s_c = cache_len(cfg, max_len)
        if s >= s_c:
            # keep the last s_c keys, packed in ring order slot = t % s_c
            t0 = s - s_c
            idx = t0 + jnp.arange(s_c)  # tokens kept: [s-s_c, s)
            ring_slot = idx % s_c
            k_keep = jnp.take(k, idx, axis=2)
            v_keep = jnp.take(v, idx, axis=2)
            k_cache = jnp.zeros_like(k_keep)
            v_cache = jnp.zeros_like(v_keep)
            k_cache = k_cache.at[:, :, ring_slot, :].set(k_keep)
            v_cache = v_cache.at[:, :, ring_slot, :].set(v_keep)
        else:
            pad = s_c - s
            k_cache = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v_cache = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if ctx.kv_quant:
            kq, ks = _quantize_kv(k_cache)
            vq, vs = _quantize_kv(v_cache)
            return x, {"k": kq, "k_s": ks, "v": vq, "v_s": vs}
        return x, {"k": k_cache, "v": v_cache}
    if kind == "rglru":
        o, st = rglru_block(p["rec"], x, cfg, ctx, return_state=True)
        x = x + o
        x = x + ffn(p["ffn"], x, cfg, ctx)
        return x, st
    if kind == "mlstm":
        o, st = mlstm_block(p["rec"], x, cfg, ctx, return_state=True)
        return x + o, st
    if kind == "slstm":
        o, st = slstm_block(p["rec"], x, cfg, ctx, return_state=True)
        return x + o, st
    raise ValueError(kind)


def prefill(params, inputs: dict, cfg: ModelConfig, ctx: ParallelCtx, max_len: int):
    """Returns (last-token logits (B, V), cache)."""
    x = embed_inputs(params, inputs, cfg)
    b, s = x.shape[:2]
    positions = inputs.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def unit_fn(x, unit_params):
        caches = {}
        for j, kind in enumerate(cfg.block_pattern):
            x, c = _prefill_block(
                kind, unit_params[f"b{j}"], x, positions, cfg, ctx, b, max_len
            )
            caches[f"b{j}"] = c
        return x, caches

    if cfg.units > 0:
        x, unit_caches = jax.lax.scan(unit_fn, x, params["units"])
    else:
        unit_caches = {}
    tail_caches = []
    for j, kind in enumerate(cfg.tail):
        x, c = _prefill_block(
            kind, params["tail"][j], x, positions, cfg, ctx, b, max_len
        )
        tail_caches.append(c)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    last = x[:, -1, :]
    if "head" in params:
        logits = L.dense(params["head"], last).astype(jnp.float32)
    else:
        logits = L.unembed(params["embed"], last)
    cache = {
        "units": unit_caches,
        "tail": tail_caches,
        "pos": jnp.full((b,), s, jnp.int32),
    }
    return logits, cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _local_ring_update(buf, new_val, slot, offset):
    """Update per-row positions ``slot`` (global, ``(B,)``) in a seq-shard
    covering [offset, offset + S_loc): only the owning shard writes — no
    cross-shard traffic, no re-gather of the sharded cache.  Out-of-range
    rows (another shard owns the slot, or the slot is past capacity on a
    non-windowed arch) keep their current value — an overflowing write is
    *dropped*, never clamped onto the final slot."""
    b, _, s_loc, _ = buf.shape
    local = slot - offset  # (B,)
    in_range = (local >= 0) & (local < s_loc)
    lslot = jnp.clip(local, 0, s_loc - 1)
    rows = jnp.arange(b)
    cur = buf[rows, :, lslot, :]  # (B, Hkv, Dh)
    upd = jnp.where(
        in_range[:, None, None], new_val[:, :, 0, :].astype(buf.dtype), cur
    )
    return buf.at[rows, :, lslot, :].set(upd)


def _decode_attention(q, k_new, v_new, k_cache, v_cache, slot, n_valid,
                      ctx: ParallelCtx, k_scale=None, v_scale=None):
    """One fused decode-attention step: write the new token's K/V into the
    seq-sharded ring caches (shard-locally) and attend with LSE combine.

    q (B, H, Dh); k_new/v_new (B, Hkv, 1, Dh); caches (B, Hkv, S_c, Dh).
    ``slot`` / ``n_valid`` are per-row ``(B,)`` vectors (scalars are
    broadcast) — rows may sit at independent positions (continuous
    batching).  With ``k_scale``/``v_scale`` the caches are int8 and
    dequantized in-shard (fused into the matmuls on TPU: reads stay
    1 byte/elem).  Returns (attention output, updated caches...).
    """
    b, h, dh = q.shape
    hkv = k_cache.shape[1]
    g = h // hkv
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    slot = jnp.broadcast_to(jnp.asarray(slot, jnp.int32), (b,))
    n_valid = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (b,))
    quant = k_scale is not None
    if quant:
        kq_new, ks_new = _quantize_kv(k_new)
        vq_new, vs_new = _quantize_kv(v_new)

    def partial_attn(q_l, k_l, v_l, nv_l, offset, ks_l=None, vs_l=None):
        s_loc = k_l.shape[2]
        b_l = q_l.shape[0]  # may be the per-shard batch inside shard_map
        qg = (q_l.astype(jnp.float32) * scale).reshape(b_l, hkv, g, dh)
        kf = k_l.astype(jnp.float32)
        vf = v_l.astype(jnp.float32)
        if quant:
            kf = kf * ks_l
            vf = vf * vs_l
        logits = jnp.einsum("bhgd,bhsd->bhgs", qg, kf)
        live = (
            (offset + jnp.arange(s_loc))[None, None, None, :]
            < nv_l[:, None, None, None]
        )
        logits = jnp.where(live, logits, -1e30)
        m = jnp.max(logits, axis=-1)  # (b,hkv,g)
        p = jnp.exp(logits - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhgs,bhsd->bhgd", p, vf)
        return m, l, o

    if ctx.mesh is None or ctx.mesh.empty or ctx.tp_size == 1:
        if quant:
            k_cache = _local_ring_update(k_cache, kq_new, slot, 0)
            v_cache = _local_ring_update(v_cache, vq_new, slot, 0)
            k_scale = _local_ring_update(k_scale, ks_new, slot, 0)
            v_scale = _local_ring_update(v_scale, vs_new, slot, 0)
        else:
            k_cache = _local_ring_update(k_cache, k_new, slot, 0)
            v_cache = _local_ring_update(v_cache, v_new, slot, 0)
        m, l, o = partial_attn(q, k_cache, v_cache, n_valid, 0,
                               k_scale, v_scale)
        out = o / jnp.maximum(l[..., None], 1e-30)
        out = out.reshape(b, h, dh).astype(q.dtype)
        if quant:
            return out, k_cache, v_cache, k_scale, v_scale
        return out, k_cache, v_cache

    def body(q_l, kn_l, vn_l, slot_l, nv_l, k_l, v_l, *scales):
        s_loc = k_l.shape[2]
        offset = jax.lax.axis_index(ctx.tp_axis) * s_loc
        if quant:
            ks_l, vs_l, ksn_l, vsn_l = scales
            k_l = _local_ring_update(k_l, kn_l, slot_l, offset)
            v_l = _local_ring_update(v_l, vn_l, slot_l, offset)
            ks_l = _local_ring_update(ks_l, ksn_l, slot_l, offset)
            vs_l = _local_ring_update(vs_l, vsn_l, slot_l, offset)
        else:
            ks_l = vs_l = None
            k_l = _local_ring_update(k_l, kn_l, slot_l, offset)
            v_l = _local_ring_update(v_l, vn_l, slot_l, offset)
        m, l, o = partial_attn(q_l, k_l, v_l, nv_l, offset, ks_l, vs_l)
        m_g = jax.lax.pmax(m, ctx.tp_axis)
        corr = jnp.exp(m - m_g)
        denom = jax.lax.psum(l * corr, ctx.tp_axis)
        numer = jax.lax.psum(o * corr[..., None], ctx.tp_axis)
        out = numer / jnp.maximum(denom[..., None], 1e-30)
        out = out.reshape(q_l.shape[0], h, dh).astype(q.dtype)
        if quant:
            return out, k_l, v_l, ks_l, vs_l
        return out, k_l, v_l

    if b % max(ctx.dp_size, 1) == 0:
        bspec = ctx.dp
    else:
        # Explicit fallback: a ragged continuous batch that does not
        # divide the DP degree replicates the *whole cache* on every DP
        # rank for this step.  That is correct but costly — warn once per
        # trace so drivers size their slot pools to a DP multiple
        # (serve.scheduler does) or pad the batch.
        warnings.warn(
            f"decode batch {b} is not divisible by dp={ctx.dp_size}: "
            "KV cache DP sharding is dropped (replicated) for this step; "
            "pad the batch or use a slot count divisible by dp",
            RuntimeWarning,
            stacklevel=2,
        )
        bspec = None
    cache_spec = P(bspec, None, ctx.tp_axis, None)
    new_spec = P(bspec, None, None, None)  # new token K/V: replicated on S
    row_spec = P(bspec)  # per-row slot / n_valid vectors
    in_specs = [P(bspec, None, None), new_spec, new_spec, row_spec, row_spec,
                cache_spec, cache_spec]
    out_specs = [P(bspec, None, None), cache_spec, cache_spec]
    args = [q, kq_new if quant else k_new, vq_new if quant else v_new,
            slot, n_valid, k_cache, v_cache]
    if quant:
        in_specs += [cache_spec, cache_spec, new_spec, new_spec]
        out_specs += [cache_spec, cache_spec]
        args += [k_scale, v_scale, ks_new, vs_new]
    return shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False,
    )(*args)


def _decode_block(kind, p, x_t, positions, cache, pos, cfg, ctx):
    """x_t (B, D) one token at per-row positions ``pos`` (B,); returns
    (x_t, new_cache).  Non-windowed archs write slot = pos *unclamped*:
    past capacity the ring update drops the write (saturating semantics —
    the final KV slot is never silently overwritten forever; see the
    module capacity contract and :class:`CacheCapacityError`)."""
    if kind == "attn":
        h = L.rmsnorm(p["attn"]["norm"], x_t, cfg.norm_eps)
        q, k, v = _project_qkv(
            p["attn"], h[:, None, :], positions, cfg, ctx
        )  # (B, 1, H, dh)
        s_c = cache["k"].shape[2]
        slot = pos % s_c if cfg.window is not None else pos
        k_new = k.transpose(0, 2, 1, 3)  # (B, Hkv, 1, dh)
        v_new = v.transpose(0, 2, 1, 3)
        n_valid = jnp.minimum(pos + 1, s_c)
        q_t = q.reshape(q.shape[0], q.shape[2], q.shape[3])  # (B, H, dh)
        if ctx.kv_quant:
            o, ck, cv, cks, cvs = _decode_attention(
                q_t, k_new, v_new, cache["k"], cache["v"], slot, n_valid,
                ctx, cache["k_s"], cache["v_s"],
            )
            new_cache = {"k": ck, "v": cv, "k_s": cks, "v_s": cvs}
        else:
            o, ck, cv = _decode_attention(
                q_t, k_new, v_new, cache["k"], cache["v"], slot, n_valid, ctx
            )
            new_cache = {"k": ck, "v": cv}
        o = L.dense(p["attn"]["wo"], o.reshape(x_t.shape[0], -1))
        x_t = x_t + o
        if "moe" in p:
            y, _ = moe_ffn(p["moe"], x_t[:, None, :], cfg, ctx)
            x_t = x_t + y[:, 0]
        elif "ffn" in p:
            x_t = x_t + ffn(p["ffn"], x_t[:, None, :], cfg, ctx)[:, 0]
        return x_t, new_cache
    if kind == "rglru":
        o, st = rglru_step(p["rec"], x_t, cache, cfg)
        x_t = x_t + o
        x_t = x_t + ffn(p["ffn"], x_t[:, None, :], cfg, ctx)[:, 0]
        return x_t, st
    if kind == "mlstm":
        o, st = mlstm_step(p["rec"], x_t, cache, cfg)
        return x_t + o, st
    if kind == "slstm":
        o, st = slstm_step(p["rec"], x_t, cache, cfg)
        return x_t + o, st
    raise ValueError(kind)


def decode_step(params, cache, tokens, cfg: ModelConfig, ctx: ParallelCtx,
                *, active=None):
    """One decode step.  tokens (B,) int32 -> (logits (B, V), new cache).

    ``cache["pos"]`` is a per-row ``(B,)`` position vector (a legacy
    scalar is broadcast): rows decode at independent offsets, so a
    continuous-batching scheduler can hold requests at different depths
    in one batch.  ``active`` (optional ``(B,)`` bool/int) advances only
    the marked rows' positions — inactive (free) slots keep ``pos``
    untouched so an admitted request starts from a clean offset; their
    ride-along writes land in slots the next prefill overwrites anyway.
    """
    pos = cache["pos"]
    b = tokens.shape[0]
    if pos.ndim == 0:  # legacy fixed-shape caches: one position per batch
        pos = jnp.broadcast_to(pos, (b,))
    x = L.embed(params["embed"], tokens) if cfg.embed_inputs else tokens
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(
            pos[:, None, None], (b, 1, 3)
        ).astype(jnp.int32)
    else:
        positions = pos[:, None].astype(jnp.int32)

    def unit_fn(x_t, scanned):
        unit_params, unit_cache = scanned
        new_caches = {}
        for j, kind in enumerate(cfg.block_pattern):
            x_t, c = _decode_block(
                kind, unit_params[f"b{j}"], x_t, positions, unit_cache[f"b{j}"],
                pos, cfg, ctx,
            )
            new_caches[f"b{j}"] = c
        return x_t, new_caches

    if cfg.units > 0:
        x, new_unit_caches = jax.lax.scan(
            unit_fn, x, (params["units"], cache["units"])
        )
    else:
        new_unit_caches = cache["units"]
    new_tail = []
    for j, kind in enumerate(cfg.tail):
        x, c = _decode_block(
            kind, params["tail"][j], x, positions, cache["tail"][j], pos, cfg, ctx
        )
        new_tail.append(c)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if "head" in params:
        logits = L.dense(params["head"], x).astype(jnp.float32)
    else:
        logits = L.unembed(params["embed"], x)
    advance = 1 if active is None else jnp.asarray(active, jnp.int32)
    new_cache = {
        "units": new_unit_caches, "tail": new_tail, "pos": pos + advance,
    }
    return logits, new_cache
