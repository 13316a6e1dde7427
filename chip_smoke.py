#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, with every result checked.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a 2x2 mesh on a four-chip host

One chip:

* engine: ``DistributedMatmul`` (task-based SUMMA, Pallas local kernels)
  on a 1x1 mesh at N = 16384, bf16 operands, fp32 accumulation — dense
  (``tiled_matmul``), block-sparse A at fill 0.3 over the 64x64 block
  grid (``bsmm``), and ``NonuniformMatmul`` over the paper's §4.1 tiling
  (average block 256, physical tile 128);
* serve: ``repro.launch.serve`` for llama3.2-1b at its published width,
  static batch and continuous paged, plus the prefill logits of the
  ``summa`` context against the ``xla`` context.

``--four-chips`` runs only what needs the mesh: the engine on a 2x2 mesh
(taskbased, allgather, block-sparse bsmm) against a one-device reference,
and ``launch.serve --tp 4 --matmul-strategy summa`` with its prefill
logits against the 1x1 result.

This is a smoke, not a benchmark: it prints compile seconds and
residuals, never a rate.  Any failed check raises and the exit code is
non-zero.  Without a TPU it exits 1 before any phase.  The last line of
standard output is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

N = 16384  # engine operand extent
BLOCKS = 64  # block grid per dimension: 256-wide blocks at N
FILL = 0.3  # block-sparse A fill
SEED = 0  # operands, masks and tilings
NU_TILE = 128  # physical tile of the nonuniform case
# Relative Frobenius error bounds.  Engine: the result is rounded to bf16
# (unit roundoff 2**-8), against an fp32 HIGHEST-precision product of the
# same bf16 operands.
TOL_ENGINE = 1e-2
# Logits.  Where bf16 rounds differs between two prefill paths, and
# through 16 layers of random weights that alone moves the last-token
# logits by several percent.  So two paths are compared in fp32 at
# HIGHEST matmul precision, where they compute the same function and
# must agree to TOL_LOGITS_F32; in bf16, the path under test may be at
# most BF16_MARGIN times as far from the fp32 reference as the reference
# path's own bf16 run is.
TOL_LOGITS_F32 = 1e-3
BF16_MARGIN = 2.0
#: text every compiled Pallas route must contain
KERNEL_MARK = "tpu_custom_call"
ARCH, BATCH, PROMPT_LEN, GEN = "llama3.2-1b", 8, 512, 32


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(count: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU found (JAX's first device is "
            f"{devs[0].platform!r}); this smoke runs only on a TPU"
        )
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    return devs


@jax.jit
def ref_dot(a, b):
    return jnp.dot(
        a.astype(jnp.float32), b.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


@jax.jit
def rel_err(x, ref):
    ref = ref.astype(jnp.float32)
    return jnp.linalg.norm(x.astype(jnp.float32) - ref) / jnp.linalg.norm(ref)


def check(name: str, err, tol: float) -> None:
    err = float(err)
    log(f"{name}: relative error {err:.3e} (bound {tol:.2e})")
    if not err < tol:  # also catches NaN
        raise AssertionError(f"{name}: relative error {err} >= {tol}")


def compile_run(name: str, fn, *args):
    """Compile ``fn`` for ``args``, check it holds a Pallas kernel, run it."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    if KERNEL_MARK not in compiled.as_text():
        raise AssertionError(f"{name}: compiled program has no {KERNEL_MARK}")
    log(f"{name}: compile {secs:.1f} s, {KERNEL_MARK} present")
    return jax.block_until_ready(compiled(*args))


def operands(n: int):
    ka, kb = jax.random.split(jax.random.PRNGKey(SEED))
    return (
        jax.random.normal(ka, (n, n), jnp.bfloat16),
        jax.random.normal(kb, (n, n), jnp.bfloat16),
    )


def masked(a, mask: np.ndarray):
    bs = a.shape[0] // mask.shape[0]
    keep = jnp.repeat(jnp.repeat(jnp.asarray(mask), bs, 0), bs, 1)
    return jnp.where(keep, a, jnp.zeros((), a.dtype))


def engine_phase(mesh, *, n: int = N, blocks: int = BLOCKS):
    """Dense, block-sparse and nonuniform products on one device."""
    from repro.core import (
        DistributedMatmul,
        NonuniformMatmul,
        nonuniform_tiling,
        random_block_mask,
    )

    mm = DistributedMatmul(mesh, strategy="taskbased", local_matmul="pallas")
    a, b = operands(n)
    want = ref_dot(a, b)
    c = compile_run("engine dense", lambda x, y: mm(x, y), a, b)
    check("engine dense", rel_err(c, want), TOL_ENGINE)

    tilings = [nonuniform_tiling(n, blocks, seed=SEED + s) for s in (1, 2, 3)]
    nu = NonuniformMatmul(mm, *tilings, tile=NU_TILE)
    waste = {k: round(v, 3) for k, v in nu.padding_waste.items()}
    log(f"engine nonuniform: tile {NU_TILE}, padding waste {waste}")
    c = compile_run("engine nonuniform", lambda x, y: nu(x, y), a, b)
    check("engine nonuniform", rel_err(c, want), TOL_ENGINE)
    del c, want

    mask = random_block_mask(blocks, blocks, FILL, seed=SEED)
    plan = mm.plan(n, n, n, a_mask=mask, itemsize=a.dtype.itemsize)
    log(
        f"engine blocksparse: fill {mask.mean():.3f}, local_impl "
        f"{plan.local_impl}, kernel block {plan.local_block}"
    )
    if plan.local_impl != "bsmm":
        raise AssertionError(f"block-sparse plan runs {plan.local_impl}, not bsmm")
    c = compile_run("engine blocksparse", lambda x, y: mm(x, y, a_mask=mask), a, b)
    check("engine blocksparse", rel_err(c, ref_dot(masked(a, mask), b)), TOL_ENGINE)


def engine_mesh_phase(devs, *, n: int = N, blocks: int = BLOCKS):
    """taskbased, allgather and block-sparse bsmm on a 2x2 mesh, against
    the reference on device 0."""
    from repro.core import DistributedMatmul, random_block_mask
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(2, 2)
    mesh_devs = set(mesh.devices.flat)
    a, b = (jax.device_put(x, devs[0]) for x in operands(n))
    mask = random_block_mask(blocks, blocks, FILL, seed=SEED)
    dense = ref_dot(a, b)
    cases = [
        ("taskbased", {}, dense),
        ("allgather", {}, dense),
        ("taskbased", {"a_mask": mask}, ref_dot(masked(a, mask), b)),
    ]
    for strategy, kw, want in cases:
        name = f"mesh 2x2 {strategy}" + (" bsmm" if kw else "")
        mm = DistributedMatmul(mesh, strategy=strategy, local_matmul="pallas")
        if kw and mm.plan(n, n, n, itemsize=2, **kw).local_impl != "bsmm":
            raise AssertionError(f"{name}: plan does not run bsmm")
        fn = lambda x, y, mm=mm, kw=kw: mm(x, y, **kw)  # noqa: E731
        c = compile_run(name, fn, *mm.shard(a, b))
        shard_devs = {s.device for s in c.addressable_shards}
        if shard_devs != mesh_devs:
            raise AssertionError(f"{name}: output shards on {shard_devs}")
        log(f"{name}: output shards on {len(shard_devs)} devices")
        check(name, rel_err(jax.device_put(c, devs[0]), want), TOL_ENGINE)


def check_tokens(name: str, tokens, vocab: int) -> None:
    tokens = np.asarray(tokens)
    if not np.issubdtype(tokens.dtype, np.integer):
        raise AssertionError(f"{name}: tokens are {tokens.dtype}, not ints")
    if tokens.size == 0 or tokens.min() < 0 or tokens.max() >= vocab:
        raise AssertionError(f"{name}: tokens outside [0, {vocab})")
    log(f"{name}: {tokens.size} tokens, all in [0, {vocab})")


def prefill_logits(cfg, mesh, strategy: str, params, prompts, *, fp32: bool):
    """Last-token prefill logits of ``params`` under one matmul strategy:
    as served (bf16), or with params, activations and matmuls in fp32."""
    from repro.dist.context import ParallelCtx
    from repro.serve import engine

    if fp32:
        cfg = dataclasses.replace(cfg, dtype="float32")
        params = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params,
        )
    precision = "highest" if fp32 else "default"
    max_len = PROMPT_LEN + GEN
    ctx = ParallelCtx(mesh=mesh, matmul_strategy=strategy)
    # plans derived outside the trace, as the serving drivers do
    engine.warm_matmul_plans(cfg, ctx, *prompts.shape, warm_executables=False)
    t0 = time.perf_counter()
    with mesh, jax.default_matmul_precision(precision):
        logits, _ = jax.jit(
            lambda p, t: engine.prefill(p, {"tokens": t}, cfg, ctx, max_len)
        )(params, prompts)
    logits = jax.block_until_ready(logits)
    secs = time.perf_counter() - t0
    name = f"prefill[{strategy}, {cfg.dtype}, mesh {dict(mesh.shape)}]"
    log(f"{name}: compile+run {secs:.1f} s")
    if strategy != "xla":
        hits = ctx.matmul().cache_stats()["plan"]["hits"]
        if hits == 0:
            raise AssertionError(f"{name} never consulted the engine's plans")
        log(f"{name}: {hits} engine plan-cache hits while tracing")
    return logits


def check_logits(name: str, got: dict, ref: dict) -> None:
    """``got``/``ref``: ``{"bf16": logits, "fp32": logits}`` of two paths."""
    check(f"{name} fp32", rel_err(got["fp32"], ref["fp32"]), TOL_LOGITS_F32)
    ref_err = float(rel_err(ref["bf16"], ref["fp32"]))
    log(f"{name}: reference path bf16 vs its fp32, relative error {ref_err:.3e}")
    log(f"{name}: bf16 vs bf16, relative error "
        f"{float(rel_err(got['bf16'], ref['bf16'])):.3e} (not checked)")
    check(f"{name} bf16 vs fp32 reference",
          rel_err(got["bf16"], ref["fp32"]), BF16_MARGIN * ref_err)


def both_logits(cfg, mesh, strategy: str, params, prompts) -> dict:
    return {
        "bf16": prefill_logits(cfg, mesh, strategy, params, prompts, fp32=False),
        "fp32": prefill_logits(cfg, mesh, strategy, params, prompts, fp32=True),
    }


def serve_args(*extra: str, smoke: bool = False) -> list[str]:
    args = [
        "--arch", ARCH, "--batch", str(BATCH), "--prompt-len", str(PROMPT_LEN),
        "--gen", str(GEN), "--matmul-strategy", "summa", *extra,
    ]
    return args + ["--smoke"] if smoke else args


def model_inputs(cfg, mesh):
    """The params and prompts ``launch.serve`` builds from seed 0."""
    from repro.dist.context import ParallelCtx
    from repro.models.model import init_model

    params = init_model(jax.random.PRNGKey(0), cfg, ParallelCtx(mesh=mesh))
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (BATCH, PROMPT_LEN), 0, cfg.vocab_size
    )
    return params, prompts


def serve_phase(mesh, *, smoke: bool = False):
    """Static and continuous-paged serving, then summa vs xla prefill."""
    from repro.configs import get_config
    from repro.launch import serve
    from repro.serve.scheduler import ragged_trace

    cfg = get_config(ARCH, smoke=smoke)
    log("launch.serve output follows; its tok/s lines include compilation")
    t0 = time.perf_counter()
    gen = serve.main(serve_args(smoke=smoke))
    log(f"serve static: {time.perf_counter() - t0:.1f} s including compilation")
    if np.shape(gen) != (BATCH, GEN):
        raise AssertionError(f"serve static: generated shape {np.shape(gen)}")
    check_tokens("serve static", gen, cfg.vocab_size)

    t0 = time.perf_counter()
    res = serve.main(serve_args("--continuous", "--paged", smoke=smoke))
    secs = time.perf_counter() - t0
    log(f"serve continuous paged: {secs:.1f} s including compilation")
    # the ragged trace launch.serve builds for these widths
    trace = ragged_trace(
        4 * BATCH, prompt_lens=(PROMPT_LEN // 2, PROMPT_LEN),
        gen_lens=(GEN // 4, GEN), vocab=cfg.vocab_size, seed=0,
    )
    outputs = res["outputs"]
    done = [r.rid for r in trace if len(outputs.get(r.rid, ())) == r.max_new_tokens]
    if len(done) != len(trace) or res["requests"] != len(trace):
        raise AssertionError(
            f"serve continuous paged: {len(done)} of {len(trace)} requests done"
        )
    log(f"serve continuous paged: all {len(trace)} requests done, {res['steps']} steps")
    tokens = np.concatenate([outputs[r] for r in done])
    check_tokens("serve continuous paged", tokens, cfg.vocab_size)

    params, prompts = model_inputs(cfg, mesh)
    want = both_logits(cfg, mesh, "xla", params, prompts)
    got = both_logits(cfg, mesh, "summa", params, prompts)
    check_logits("prefill logits summa vs xla", got, want)


def serve_tp4_phase(*, smoke: bool = False):
    """``launch.serve --tp 4`` and its prefill logits against 1x1."""
    from repro.configs import get_config
    from repro.dist.partitioning import param_shardings
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh

    cfg = get_config(ARCH, smoke=smoke)
    log("launch.serve output follows; its tok/s lines include compilation")
    gen = serve.main(serve_args("--tp", "4", smoke=smoke))
    check_tokens("serve tp4 static", gen, cfg.vocab_size)

    one, four = make_host_mesh(1, 1), make_host_mesh(1, 4)
    params, prompts = model_inputs(cfg, one)
    want = both_logits(cfg, one, "summa", params, prompts)
    params4 = jax.tree.map(jax.device_put, params, param_shardings(params, four))
    got = both_logits(cfg, four, "summa", params4, prompts)
    got = jax.device_put(got, jax.devices()[0])
    check_logits("prefill logits tp4 vs 1x1", got, want)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the 2x2-mesh engine and tp=4 serving checks",
    )
    args = ap.parse_args(argv)
    devs = require_tpu(4 if args.four_chips else 1)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    # the checkout's own sources; outside a checkout these imports fail
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    log(
        f"chip smoke, not a benchmark: {len(devs)} x {devs[0].device_kind}, "
        f"jax {jax.__version__}, compile cache {enable_compile_cache()}"
    )
    phases = (
        [
            ("engine 2x2", lambda: engine_mesh_phase(devs)),
            ("serve tp4", serve_tp4_phase),
        ]
        if args.four_chips
        else [
            ("engine 1x1", lambda: engine_phase(make_host_mesh(1, 1))),
            ("serve 1x1", lambda: serve_phase(make_host_mesh(1, 1))),
        ]
    )
    for name, phase in phases:
        t0 = time.perf_counter()
        phase()
        log(f"phase {name} passed in {time.perf_counter() - t0:.1f} s")
    d = jax.devices()[0]
    count = len(jax.devices())
    device = {"platform": d.platform, "kind": d.device_kind, "count": count}
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
