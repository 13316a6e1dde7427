import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as its own process (the two lines above must execute before
jax initialises devices):

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k [--multi-pod] [--out results/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all

Per cell this produces lowered.compile() (proving the sharding config is
coherent at 256/512 chips), prints memory_analysis / cost_analysis, and
derives the three roofline terms (analysis.hlo) recorded as JSON for
EXPERIMENTS.md §Dry-run / §Roofline.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo as hloa
from repro.configs import cell_skip_reason, get_config
from repro.dist.context import ParallelCtx
from repro.dist.partitioning import param_shardings
from repro.launch.mesh import make_production_mesh
from repro.models.config import SHAPES, ModelConfig, ShapeConfig
from repro.models.model import init_model
from repro.serve import engine
from repro.train.optimizer import OptimizerConfig, make_optimizer
from repro.train import train_step as ts

DEFAULT_MICROBATCHES = 16


def make_ctx(
    mesh,
    multi_pod: bool,
    matmul_strategy: str = "xla",
    attention_impl: str = "ref",
    mlstm_chunk: int | None = None,
    zero1: bool = False,
    kv_quant: bool = False,
    slstm_replicated: bool = False,
    pure_dp: bool = False,
) -> ParallelCtx:
    if pure_dp:
        dp = ("pod", "data", "model") if multi_pod else ("data", "model")
    else:
        dp = ("pod", "data") if multi_pod else ("data",)
    return ParallelCtx(
        mesh=mesh,
        dp_axes=dp,
        tp_axis="model",
        matmul_strategy=matmul_strategy,
        attention_impl=attention_impl,
        mlstm_chunk=mlstm_chunk,
        zero1=zero1,
        kv_quant=kv_quant,
        slstm_replicated=slstm_replicated,
        pure_dp=pure_dp,
    )


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs only — never allocated)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Abstract train/prefill batch for this arch family."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if cfg.family == "audio":
        return {
            "embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16),
            "labels": jax.ShapeDtypeStruct((b, s), i32),
        }
    if cfg.family == "vlm":
        s_vis = s // 4
        s_text = s - s_vis
        return {
            "tokens": jax.ShapeDtypeStruct((b, s_text), i32),
            "embeds": jax.ShapeDtypeStruct((b, s_vis, cfg.d_model), jnp.bfloat16),
            "positions": jax.ShapeDtypeStruct((b, s, 3), i32),
            "labels": jax.ShapeDtypeStruct((b, s_text), i32),
        }
    return {
        "tokens": jax.ShapeDtypeStruct((b, s), i32),
        "labels": jax.ShapeDtypeStruct((b, s), i32),
    }


def model_flops_per_step(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (forward-only), N = active."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


# ---------------------------------------------------------------------------
# cell builders: return (jitted_fn, example_args_abstract)
# ---------------------------------------------------------------------------


def build_train_cell(cfg, shape, ctx, microbatches):
    opt = make_optimizer(
        OptimizerConfig(
            name="adafactor" if cfg.name.startswith("kimi") else "adamw"
        )
    )
    rng = jax.random.PRNGKey(0)
    state = ts.abstract_train_state(rng, cfg, ctx, opt)
    st_sh = ts.state_shardings(state, ctx)
    batch = input_specs(cfg, shape)
    b_sh = ts.batch_shardings(batch, ctx)
    step = ts.build_train_step(cfg, ctx, opt, microbatches=microbatches)
    jitted = jax.jit(
        step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
        donate_argnums=(0,),
    )
    state = _with_shardings(state, st_sh)
    batch = _with_shardings(batch, b_sh)
    return jitted, (state, batch)


def build_prefill_cell(cfg, shape, ctx):
    rng = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda r: init_model(r, cfg, ctx), rng)
    p_sh = param_shardings(params, ctx.mesh)
    batch = input_specs(cfg, shape)
    batch.pop("labels", None)
    b_sh = ts.batch_shardings(batch, ctx)

    def fn(p, b):
        return engine.prefill(p, b, cfg, ctx, max_len=shape.seq_len)

    jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
    return jitted, (_with_shardings(params, p_sh), _with_shardings(batch, b_sh))


def build_decode_cell(cfg, shape, ctx):
    rng = jax.random.PRNGKey(0)
    b = shape.global_batch
    params = jax.eval_shape(lambda r: init_model(r, cfg, ctx), rng)
    p_sh = param_shardings(params, ctx.mesh)
    cache = jax.eval_shape(
        lambda: engine.init_cache(cfg, b, shape.seq_len, kv_quant=ctx.kv_quant)
    )
    c_sh = _cache_shardings(cache, ctx, b)
    tokens = jax.ShapeDtypeStruct((b,), jnp.int32)
    t_sh = NamedSharding(
        ctx.mesh, P(ctx.dp if b % ctx.dp_size == 0 else None)
    )

    def fn(p, c, t):
        return engine.decode_step(p, c, t, cfg, ctx)

    jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, t_sh), donate_argnums=(1,))
    return jitted, (
        _with_shardings(params, p_sh),
        _with_shardings(cache, c_sh),
        _with_shardings(tokens, t_sh),
    )


def _cache_shardings(cache, ctx: ParallelCtx, batch: int):
    # One cache-sharding function for the whole codebase: the engine owns
    # the leaf classification (KV + quant scales vs recurrent state).
    return engine.cache_shardings(cache, ctx, batch)


def _with_shardings(abstract_tree, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract_tree,
        shardings,
    )


# ---------------------------------------------------------------------------
# run one cell
# ---------------------------------------------------------------------------


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    microbatches: int = DEFAULT_MICROBATCHES,
    matmul_strategy: str = "xla",
    attention_impl: str = "ref",
    mlstm_chunk: int | None = None,
    zero1: bool = False,
    kv_quant: bool = False,
    slstm_replicated: bool = False,
    pure_dp: bool = False,
    save_hlo: str | None = None,
) -> dict:
    shape = SHAPES[shape_name]
    skip = cell_skip_reason(arch, shape_name)
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "matmul_strategy": matmul_strategy,
        "attention_impl": attention_impl,
        "mlstm_chunk": mlstm_chunk,
        "zero1": zero1,
        "kv_quant": kv_quant,
        "microbatches": microbatches if shape.kind == "train" else None,
    }
    if skip:
        result["status"] = skip
        return result
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = make_ctx(mesh, multi_pod, matmul_strategy, attention_impl,
                   mlstm_chunk, zero1, kv_quant, slstm_replicated, pure_dp)
    # per-microbatch batch must divide the DP degree, or sharding degrades
    # to replicated compute (found via the 2-pod roofline; EXPERIMENTS.md)
    if shape.kind == "train":
        microbatches = max(1, min(microbatches,
                                  shape.global_batch // ctx.dp_size))
        result["microbatches"] = microbatches
    # Derive + simulate (and for "auto": tune) the projection schedules
    # first, so the traces below hit the warmed plan cache.
    try:
        sched = sched_section(cfg, shape, ctx, microbatches)
    except Exception as e:  # simulation must never sink a dry-run cell
        sched = [{"status": f"sched-error: {type(e).__name__}: {e}"}]
    if sched is not None:
        result["sched"] = sched
    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            jitted, args = build_train_cell(cfg, shape, ctx, microbatches)
        elif shape.kind == "prefill":
            jitted, args = build_prefill_cell(cfg, shape, ctx)
        else:
            jitted, args = build_decode_cell(cfg, shape, ctx)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()
    wc = hloa.analyze_hlo(hlo_text)
    chips = int(np.prod(list(mesh.shape.values())))
    mf = model_flops_per_step(cfg, shape)
    rep = hloa.roofline(
        flops=wc.flops,
        hbm_bytes=wc.hbm_bytes,
        coll_bytes=wc.wire_bytes,  # ring wire-cost model (analysis.hlo)
        chips=chips,
        model_flops=mf,
    )
    result.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        chips=chips,
        flops_per_device=wc.flops,
        hbm_bytes_per_device=wc.hbm_bytes,
        collective_bytes_per_device=wc.coll_bytes,
        collective_wire_bytes_per_device=wc.wire_bytes,
        collective_breakdown=wc.coll_bytes_by_op,
        collective_counts=wc.coll_counts_by_op,
        xla_cost_analysis={
            "flops_unweighted": float(cost.get("flops", 0.0)) if cost else 0.0,
            "bytes_unweighted": float(cost.get("bytes accessed", 0.0)) if cost else 0.0,
        },
        roofline=rep.row(),
        memory_analysis=_mem_dict(mem),
    )
    if save_hlo:
        os.makedirs(os.path.dirname(save_hlo), exist_ok=True)
        with open(save_hlo, "w") as f:
            f.write(hlo_text)
    return result


def sched_section(cfg, shape, ctx, microbatches: int) -> list | None:
    """Simulated projection schedules for this cell (repro.sched).

    For every FFN projection shape the cell will trace, derive (and with
    ``matmul_strategy="auto"`` tune) the ``MatmulPlan``, then run its task
    DAG through the discrete-event simulator: predicted makespan,
    imbalance, and the executed lookahead land next to the roofline terms
    in the cell JSON.  Plans are cached, so the subsequent trace reuses
    them.
    """
    if not ctx.has_mesh or ctx.matmul_strategy == "xla" or ctx.pure_dp:
        return None
    if not cfg.d_ff:
        return None
    from repro.sched.simulator import simulate_plan

    if shape.kind == "train":
        m = (shape.global_batch // max(microbatches, 1)) * shape.seq_len
    elif shape.kind == "prefill":
        m = shape.global_batch * shape.seq_len
    else:
        m = shape.global_batch
    tune = ctx.matmul_strategy == "auto"
    # plan under the activation dtype's itemsize, or the traces below plan
    # under a different cache key and re-derive (serve.warm_matmul_plans
    # makes the same move)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    out = []
    d = cfg.d_model
    for k_in, n_out in ((d, cfg.d_ff), (cfg.d_ff, d)):
        plan = ctx.plan_projection(
            m, k_in, n_out, itemsize=itemsize, tune=tune
        )
        if plan is None:
            continue
        sim = simulate_plan(plan)
        out.append(
            {
                "proj": [m, k_in, n_out],
                "strategy": plan.cfg.strategy,
                "lookahead": plan.resolve_lookahead(),
                "k_steps": plan.k_steps,
                "sim_makespan_s": sim.makespan_s,
                "sim_imbalance": sim.imbalance_ratio,
                "sim_efficiency": sim.efficiency,
                "tuned": plan.tuned,
            }
        )
    return out


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=DEFAULT_MICROBATCHES)
    ap.add_argument("--matmul-strategy", default="xla",
                    choices=["xla", "summa", "allgather", "auto"])
    ap.add_argument("--attention", default="ref", choices=["ref", "chunked"])
    ap.add_argument("--mlstm-chunk", type=int, default=None)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--slstm-replicated", action="store_true")
    ap.add_argument("--pure-dp", action="store_true")
    ap.add_argument("--tag", default=None,
                    help="suffix for the result filename (perf variants)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    from repro.configs import ARCH_IDS

    cells = []
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    pods = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in pods:
                cells.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    for a, s, mp in cells:
        tag = f"{a}__{s}__{'2pod' if mp else '1pod'}"
        if args.matmul_strategy != "xla":
            tag += f"__{args.matmul_strategy}"
        if args.tag:
            tag += f"__{args.tag}"
        out_path = os.path.join(args.out, tag + ".json")
        if os.path.exists(out_path):
            print(f"[skip-existing] {tag}")
            continue
        print(f"[dryrun] {tag} ...", flush=True)
        try:
            res = run_cell(
                a, s, mp,
                microbatches=args.microbatches,
                matmul_strategy=args.matmul_strategy,
                attention_impl=args.attention,
                mlstm_chunk=args.mlstm_chunk,
                zero1=args.zero1,
                kv_quant=args.kv_quant,
                slstm_replicated=args.slstm_replicated,
                pure_dp=args.pure_dp,
                save_hlo=args.save_hlo,
            )
        except Exception as e:  # record failures — they are findings
            res = {
                "arch": a, "shape": s,
                "mesh": "2x16x16" if mp else "16x16",
                "status": f"error: {type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:],
            }
        with open(out_path, "w") as f:
            json.dump(res, f, indent=1, default=str)
        print(f"[done] {tag}: {res.get('status')}", flush=True)


if __name__ == "__main__":
    main()
