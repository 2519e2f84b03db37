import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, print memory/cost analysis, and emit roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.jsonl
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod ...

The XLA_FLAGS line above MUST run before any jax import: it materializes
512 host platform devices so ``jax.make_mesh`` can build the 2x16x16 mesh.
Only this entry point sets it — tests and benches see the real device.
"""
import argparse
import dataclasses
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_NAMES, get_config, supported_shapes
from ..models import Transformer, TrainState, make_train_step, make_serve_step, ShardHints
from ..models.config import INPUT_SHAPES
from ..optim import adam
from .compile_cache import enable_compile_cache
from .input_specs import input_specs
from .mesh import make_production_mesh
from .roofline import analyze_hlo, model_flops_for, roofline_from_stats
from .shardings import (ShardPolicy, build_batch_specs, build_cache_specs,
                        build_param_specs, named)

BIG_MODEL_PARAMS = 5e10        # >50B -> bf16 adam moments


def _adam_for(cfg):
    mdt = jnp.bfloat16 if cfg.param_count() > BIG_MODEL_PARAMS else jnp.float32
    return adam(1e-4, b1=0.9, b2=0.95, moment_dtype=mdt)


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
                fsdp: bool = True, moe_mode: str = "auto",
                residual: str = "dmodel"):
    """Returns (lowered, meta) for one (arch, shape, mesh) combo."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    pol = ShardPolicy(mesh, fsdp=fsdp, moe_mode=moe_mode)
    model = Transformer(cfg, shard=ShardHints(dp=pol.dp, tp=pol.tp,
                                              residual=residual))
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = build_param_specs(params_shape, pol, cfg.n_experts)
    batch = input_specs(cfg, shape)
    bspecs = build_batch_specs(batch, pol)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "chips": 512 if multi_pod else 256, "mode": shape.mode,
            "fsdp": fsdp, "moe_mode": moe_mode}

    with mesh:
        if shape.mode == "train":
            opt = _adam_for(cfg)
            opt_shape = jax.eval_shape(opt.init, params_shape)
            ospecs = jax.tree.map(
                lambda _: None, opt_shape)   # placeholder, rebuilt below
            # AdamState(mu, nu, count): mu/nu mirror params
            from ..optim.optimizers import AdamState
            ospecs = AdamState(mu=pspecs, nu=pspecs, count=P())
            state_shape = TrainState(params=params_shape, opt_state=opt_shape,
                                     step=jax.ShapeDtypeStruct((), jnp.int32))
            state_specs = TrainState(params=pspecs, opt_state=ospecs, step=P())
            step_fn = make_train_step(model, opt)
            jitted = jax.jit(step_fn,
                             in_shardings=(named(mesh, state_specs),
                                           named(mesh, bspecs)),
                             out_shardings=(named(mesh, state_specs), None))
            lowered = jitted.lower(state_shape, batch)
        elif shape.mode == "prefill":
            def fwd(params, batch):
                return model.forward(params, batch)[0]
            jitted = jax.jit(fwd, in_shardings=(named(mesh, pspecs),
                                                named(mesh, bspecs)))
            lowered = jitted.lower(params_shape, batch)
        else:   # decode
            caches_shape = jax.eval_shape(
                lambda: model.init_caches(shape.global_batch, shape.seq_len))
            cspecs = build_cache_specs(caches_shape, pol)
            serve = make_serve_step(model)
            jitted = jax.jit(serve,
                             in_shardings=(named(mesh, pspecs),
                                           named(mesh, cspecs),
                                           named(mesh, bspecs)),
                             out_shardings=(None, named(mesh, cspecs)))
            lowered = jitted.lower(params_shape, caches_shape, batch)
    return lowered, meta, cfg, shape, mesh


def run_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
              fsdp: bool = True, moe_mode: str = "auto",
              residual: str = "dmodel", verbose: bool = True) -> dict:
    t0 = time.time()
    if shape_name not in supported_shapes(arch):
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "SKIP",
               "reason": get_config(arch).notes or "unsupported shape"}
        if verbose:
            print(f"[dryrun] {arch} x {shape_name}: SKIP ({rec['reason']})")
        return rec
    try:
        lowered, meta, cfg, shape, mesh = lower_combo(
            arch, shape_name, multi_pod=multi_pod, fsdp=fsdp,
            moe_mode=moe_mode, residual=residual)
        t_lower = time.time() - t0
        with mesh:
            compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} memory_analysis: {mem}")
            print(f"[dryrun] {arch} x {shape_name} cost_analysis(flops): "
                  f"{cost.get('flops')} bytes: {cost.get('bytes accessed')}")
        stats = analyze_hlo(compiled.as_text())
        rep = roofline_from_stats(
            stats, arch=arch, shape=shape_name, mesh=meta["mesh"],
            chips=meta["chips"],
            model_flops=model_flops_for(cfg, shape, shape.mode))
        mem_info = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            if hasattr(mem, attr):
                mem_info[attr] = getattr(mem, attr)
        rec = {**meta, "status": "OK",
               "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
               "memory": mem_info,
               "xla_cost_flops": cost.get("flops"),
               "roofline": rep.as_dict(),
               "collectives": stats.collectives,
               "unknown_trip_loops": stats.unknown_trip_loops}
        if verbose:
            r = rep
            print(f"[dryrun] {arch} x {shape_name} [{meta['mesh']}]: OK "
                  f"lower={t_lower:.0f}s compile={t_compile:.0f}s | "
                  f"compute={r.compute_s*1e3:.2f}ms mem={r.memory_s*1e3:.2f}ms "
                  f"coll={r.collective_s*1e3:.2f}ms dom={r.dominant} "
                  f"useful={r.useful_flops_ratio:.2f} "
                  f"temp={mem_info.get('temp_size_in_bytes', 0)/2**30:.2f}GiB")
        return rec
    except Exception as e:
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "FAIL", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
        if verbose:
            print(f"[dryrun] {arch} x {shape_name}: FAIL {rec['error'][:200]}")
        return rec


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--moe-mode", default="auto", choices=["auto", "f2d", "ep_pad"])
    ap.add_argument("--residual", default="dmodel", choices=["dmodel", "seq"])
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    combos = []
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        for arch in ARCH_NAMES:
            for shape in INPUT_SHAPES:
                for mp in meshes:
                    combos.append((arch, shape, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape, mp) for mp in meshes]

    n_ok = n_fail = n_skip = 0
    for arch, shape, mp in combos:
        rec = run_combo(arch, shape, multi_pod=mp, fsdp=not args.no_fsdp,
                        moe_mode=args.moe_mode, residual=args.residual)
        n_ok += rec["status"] == "OK"
        n_fail += rec["status"] == "FAIL"
        n_skip += rec["status"] == "SKIP"
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    print(f"[dryrun] done: {n_ok} OK, {n_skip} SKIP, {n_fail} FAIL")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
