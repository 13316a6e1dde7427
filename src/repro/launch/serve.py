"""Serving driver: batched prefill + autoregressive decode.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --batch 4 --prompt-len 64 --gen 32

Runs greedy decoding over synthetic prompts and reports prefill/decode
throughput.  With ``--tp > 1`` the KV cache is sequence-sharded and decode
attention uses the LSE-combined partial-softmax path.

``--continuous`` switches from the fixed-shape batch loop to the
continuous-batching scheduler (``serve.scheduler``) over a ragged
arrival trace; ``--paged`` additionally backs the KV cache with page
pools (``serve.pages``).  ``--plan-cache plans.json`` persists tuned
schedule winners + the traffic distribution across processes
(``serve.plan_service``) — a warm restart re-applies stored winners with
zero tuner runs.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.dist.context import ParallelCtx
from repro.dist.partitioning import param_shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import init_model
from repro.serve import engine
from repro.serve.plan_service import plan_service


def _run_continuous(params, cfg, ctx, args):
    from repro.serve.scheduler import Scheduler, ragged_trace

    max_len = args.prompt_len + args.gen
    sched = Scheduler(
        params, cfg, ctx, n_slots=args.batch, max_len=max_len,
        mode="continuous", backend="paged" if args.paged else "dense",
    )
    reqs = ragged_trace(
        4 * args.batch,
        prompt_lens=(max(args.prompt_len // 2, 1), args.prompt_len),
        gen_lens=(max(args.gen // 4, 1), args.gen),
        vocab=cfg.vocab_size, seed=args.seed,
    )
    res = sched.run(reqs)
    print(
        f"continuous[{res['backend']}]: {res['requests']} requests in "
        f"{res['steps']} steps   {res['tokens_per_s']:,.0f} tok/s   "
        f"p50 {res['p50_step_ms']:.1f} ms   p99 {res['p99_step_ms']:.1f} ms"
    )
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--matmul-strategy", default="xla",
        choices=["xla", "summa", "allgather", "auto"],
    )
    ap.add_argument("--max-len", type=int, default=None,
                    help="KV cache capacity (default: prompt-len + gen)")
    ap.add_argument("--continuous", action="store_true",
                    help="serve a ragged trace via the scheduler")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV backend (implies --continuous)")
    ap.add_argument("--plan-cache", default=None,
                    help="JSON path to load/save tuned plan winners")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family == "audio":
        raise SystemExit("encoder-only arch has no autoregressive serving")
    mesh = make_host_mesh(args.dp, args.tp)
    ctx = ParallelCtx(mesh=mesh, matmul_strategy=args.matmul_strategy)
    svc = plan_service()
    if args.plan_cache and os.path.exists(args.plan_cache):
        n = svc.load(args.plan_cache)
        print(f"plan cache: loaded {n} winners from {args.plan_cache}")
    # Derive all projection schedules once, outside the jitted traces.
    engine.warm_matmul_plans(cfg, ctx, args.batch, args.prompt_len)
    if args.plan_cache:
        svc.save(args.plan_cache)
        print(
            f"plan cache: saved {len(svc.table)} winners "
            f"(tunes={svc.stats['tunes']} hits={svc.stats['hits']})"
        )
    rng = jax.random.PRNGKey(args.seed)
    max_len = args.max_len or (args.prompt_len + args.gen)
    # The engine never corrupts state past capacity (writes are dropped),
    # but the logits would be wrong — the driver refuses up front.
    s_c = engine.cache_len(cfg, max_len)
    if cfg.window is None and args.prompt_len + args.gen > s_c:
        raise engine.CacheCapacityError(
            f"prompt {args.prompt_len} + gen {args.gen} = "
            f"{args.prompt_len + args.gen} tokens > cache capacity {s_c}; "
            "raise --max-len"
        )
    if args.continuous or args.paged:
        params = init_model(rng, cfg, ctx)
        with mesh:
            return _run_continuous(params, cfg, ctx, args)

    with mesh:
        params = init_model(rng, cfg, ctx)
        params = jax.tree.map(
            jax.device_put, params, param_shardings(params, mesh)
        )
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
            cfg.vocab_size,
        )
        inputs = {"tokens": prompts}
        if cfg.family == "vlm":
            s_vis = args.prompt_len // 4
            inputs = {
                "tokens": prompts[:, s_vis:],
                "embeds": jnp.zeros(
                    (args.batch, s_vis, cfg.d_model), jnp.bfloat16
                ),
                "positions": jnp.broadcast_to(
                    jnp.arange(args.prompt_len)[None, :, None],
                    (args.batch, args.prompt_len, 3),
                ).astype(jnp.int32),
            }

        prefill = jax.jit(
            lambda p, b: engine.prefill(p, b, cfg, ctx, max_len=max_len)
        )
        decode = jax.jit(lambda p, c, t: engine.decode_step(p, c, t, cfg, ctx))

        t0 = time.time()
        logits, cache = prefill(params, inputs)
        logits.block_until_ready()
        t_prefill = time.time() - t0

        tokens = jnp.argmax(logits, axis=-1)
        out_tokens = [tokens]
        t0 = time.time()
        for _ in range(args.gen - 1):
            logits, cache = decode(params, cache, tokens)
            tokens = jnp.argmax(logits, axis=-1)
            out_tokens.append(tokens)
        tokens.block_until_ready()
        t_decode = time.time() - t0

    gen = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    print(f"generated shape: {gen.shape}")
    print(f"sample: {gen[0][:16].tolist()}")
    print(
        f"prefill: {args.batch * args.prompt_len / t_prefill:,.0f} tok/s   "
        f"decode: {args.batch * (args.gen - 1) / max(t_decode, 1e-9):,.0f} tok/s"
    )
    return gen


if __name__ == "__main__":
    main()
