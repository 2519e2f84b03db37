import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Federated-round dry-run: prove Fed-TGAN's training round — per-client
local steps + similarity-weighted aggregation — lowers and compiles on the
production mesh.

Clients map onto the data axes (16 clients single-pod, 32 multi-pod =
pods x data slices; DESIGN.md §4): client-stacked params are sharded
P(dp, ...tensor spec...), local training is a vmapped lax.scan, and the
weighted merge is one einsum over the client axis which GSPMD lowers to
the all-reduce pattern over dp — the TPU rendering of the federator.

The ctgan-paper arch lowers the paper's own workload through the
:mod:`repro.fed` one-program layer (in-program §4.2 weighting + fused
merge); ``--shard-map`` switches it to the explicit-placement rendering.

  PYTHONPATH=src python -m repro.launch.fed_dryrun --arch llama3-8b
  PYTHONPATH=src python -m repro.launch.fed_dryrun --arch ctgan-paper --shard-map
  PYTHONPATH=src python -m repro.launch.fed_dryrun --arch ctgan-paper --faults
  PYTHONPATH=src python -m repro.launch.fed_dryrun --all --multi-pod
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs import ARCH_NAMES, get_config
from ..models import Transformer, TrainState, make_train_step, ShardHints
from ..models.config import INPUT_SHAPES
from ..optim import adam
from .compile_cache import enable_compile_cache
from .dryrun import _adam_for
from .input_specs import train_input_specs
from .mesh import make_production_mesh
from .roofline import analyze_hlo
from .shardings import ShardPolicy, build_param_specs, named

FED_ARCHS = ["ctgan-paper", "smollm-135m", "llama3-8b", "xlstm-1.3b"]
LOCAL_STEPS = 4


def lower_fed_round(arch: str, *, multi_pod: bool = False,
                    local_steps: int = LOCAL_STEPS,
                    agg_dtype: str = "f32"):
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = INPUT_SHAPES["train_4k"]
    pol = ShardPolicy(mesh, fsdp=False)
    n_clients = pol.axis_size(pol.dp)
    # Clients ride the data axes; within a client the model axis replicates
    # (stacked-client + TP trips an XLA SPMD grouping check — b/433785288
    # family; TP-within-arch is proven by the main dry-run, this one proves
    # the federated aggregation pattern).
    model = Transformer(cfg, shard=None)
    opt = _adam_for(cfg)

    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = jax.tree.map(lambda s: P(*((None,) * len(s.shape))), params_shape)

    def stack(tree_shapes, specs):
        sh = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (n_clients,) + s.shape, s.dtype), tree_shapes)
        sp = jax.tree.map(lambda s: P(*((pol.dp,) + tuple(s))), specs,
                          is_leaf=lambda x: isinstance(x, P))
        return sh, sp

    opt_shape = jax.eval_shape(opt.init, params_shape)
    from ..optim.optimizers import AdamState
    ospecs = AdamState(mu=pspecs, nu=pspecs, count=P())
    state_shape = TrainState(params=params_shape, opt_state=opt_shape,
                             step=jax.ShapeDtypeStruct((), jnp.int32))
    state_specs = TrainState(params=pspecs, opt_state=ospecs, step=P())
    st_sh, st_sp = stack(state_shape, state_specs)

    # per-client batches: (C, E, B_local, S)
    b_local = shape.global_batch // n_clients
    batch = train_input_specs(cfg, shape)
    batch = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        (n_clients, local_steps, b_local) + s.shape[1:], s.dtype), batch)
    bspecs = jax.tree.map(lambda s: P(*((pol.dp,) + (None,) * (len(s.shape) - 1))),
                          batch)
    w_spec = P(pol.dp)
    weights = jax.ShapeDtypeStruct((n_clients,), jnp.float32)

    step_fn = make_train_step(model, opt)

    def fed_round(states, batches, w):
        """One Fed-TGAN round: E local steps per client, weighted merge,
        redistribute (broadcast back into the stacked axis)."""
        def local(st, bts):
            def body(s, b):
                return step_fn(s, b)
            return jax.lax.scan(body, st, bts)

        states, metrics = jax.vmap(local)(states, batches)
        wn = w / jnp.maximum(jnp.sum(w), 1e-12)

        def merge(leaf):
            wb = wn.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(jnp.float32)
            contrib = leaf.astype(jnp.float32) * wb
            if agg_dtype == "bf16":
                # quantized aggregation (beyond-paper §Perf lever): the
                # scale happens in f32 locally, the cross-client reduction
                # moves bf16 — half the wire bytes of the f32 merge.
                contrib = contrib.astype(jnp.bfloat16)
            m = jnp.sum(contrib, axis=0)
            return jnp.broadcast_to(m.astype(leaf.dtype)[None], leaf.shape)

        merged = jax.tree.map(merge, states.params)
        return states._replace(params=merged), metrics

    with mesh:
        jitted = jax.jit(fed_round,
                         in_shardings=(named(mesh, st_sp), named(mesh, bspecs),
                                       named(mesh, w_spec)),
                         out_shardings=(named(mesh, st_sp), None))
        lowered = jitted.lower(st_sh, batch, weights)
    return lowered, mesh, n_clients


def lower_ctgan_fed_round(*, multi_pod: bool = False,
                          local_steps: int = LOCAL_STEPS,
                          shard_map: bool = False, faults: bool = False):
    """The PAPER'S OWN workload on the production mesh: one Fed-TGAN
    global round through the :mod:`repro.fed` execution layer — vmapped
    local rounds, IN-PROGRAM §4.2 weighting from the divergence matrix,
    and the fused whole-model merge, all in the one lowered program.
    Clients ride the data axes; encoders come from the §4.1 protocol on a
    synthetic Adult table (host-side, as in the real system).

    Two renderings of the same round:

      * default — ``FederatedProgram.global_round`` with the client axis
        stacked and sharded ``P(dp, ...)``; GSPMD places the merge as the
        all-reduce pattern over dp.
      * ``shard_map=True`` — :func:`repro.fed.shard_map_global_round`:
        clients explicitly mapped onto the mesh axes, the merge an
        explicit weighted psum — the multi-host placement proof.

    Batches are drawn INSIDE each client's local ``lax.scan`` from the
    sharded sampler tables, so the only per-round inputs are model state,
    tables, the (P, Q) divergence matrix, row counts, and one PRNG key.

    ``faults=True`` lowers the DEGRADED round instead
    (``FederatedProgram.faulted_global_round``): a (P,)-sliced FaultPlan
    — participation mask, NaN mask, byzantine scale, sharded over the
    client axes — plus the in-program guard, with the masked merge still
    the same single fused ``weighted_agg`` pattern."""
    import numpy as np
    from ..configs.ctgan_paper import CONFIG as GAN_CFG, MAX_MODES
    from ..core.encoding import compute_client_stats, federated_encoder_init
    from ..fed import (FaultPlan, FederatedProgram, UpdateGuard,
                       shard_map_global_round)
    from ..gan.trainer import init_gan_state
    from ..synth import DeviceSampler
    from ..tabular.datasets import make_dataset, partition_full_copy

    if faults and shard_map:
        raise ValueError("--faults lowers the stacked GSPMD rendering; "
                         "combine it without --shard-map")

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_clients = 1
    for a in mesh.axis_names:
        if a in ("pod", "data"):
            n_clients *= mesh.shape[a]
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))

    # host-side §4.1 protocol on a small synthetic table
    ds = make_dataset("adult", n_rows=1200, seed=0)
    key = jax.random.PRNGKey(0)
    stats = [compute_client_stats(d, ds.schema, jax.random.fold_in(key, i))
             for i, d in enumerate(partition_full_copy(ds, 2))]
    init = federated_encoder_init(stats, ds.schema, key, max_modes=MAX_MODES)
    enc = init.encoders
    spans, cond_spans = tuple(enc.spans()), tuple(enc.condition_spans())
    # Encode a shard through the fused one-dispatch plan — the same path
    # real clients run every round — and build one client's device sampler
    # tables off it; the stacked-client tables are sized from its shapes.
    plan = enc.plan()
    encoded = plan.encode(ds.data[:256], jax.random.fold_in(key, 99))
    assert encoded.shape[1] == plan.encoded_dim == enc.encoded_dim
    tables = DeviceSampler(np.asarray(encoded), enc).tables

    state_shape = jax.eval_shape(
        lambda k: init_gan_state(k, GAN_CFG, enc.cond_dim, enc.encoded_dim),
        key)
    st_sh = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        (n_clients,) + s.shape, s.dtype), state_shape)
    st_sp = jax.tree.map(lambda s: P(*((dp,) + (None,) * (len(s.shape) - 1))),
                         st_sh)
    tb_sh = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (n_clients,) + a.shape, a.dtype), tables)
    tb_sp = jax.tree.map(lambda s: P(*((dp,) + (None,) * (len(s.shape) - 1))),
                         tb_sh)
    S_sh = jax.ShapeDtypeStruct((n_clients, len(ds.schema)), jnp.float32)
    n_rows_sh = jax.ShapeDtypeStruct((n_clients,), jnp.float32)
    key_sh = jax.ShapeDtypeStruct((2,), jnp.uint32)

    if shard_map:
        program = shard_map_global_round(
            mesh, GAN_CFG, spans, cond_spans, batch=GAN_CFG.batch_size,
            local_steps=local_steps, weighting="fedtgan", client_axes=dp)
    elif faults:
        program = FederatedProgram(
            GAN_CFG, spans, cond_spans, batch=GAN_CFG.batch_size,
            local_steps=local_steps, weighting="fedtgan",
            guard=UpdateGuard()).faulted_global_round
    else:
        program = FederatedProgram(
            GAN_CFG, spans, cond_spans, batch=GAN_CFG.batch_size,
            local_steps=local_steps, weighting="fedtgan").global_round

    from .shardings import named
    in_sh = (named(mesh, st_sp), named(mesh, tb_sp),
             named(mesh, P(dp)), named(mesh, P(dp)), None)
    in_args = (st_sh, tb_sh, S_sh, n_rows_sh, key_sh)
    if faults:
        fault_sh = FaultPlan(
            jax.ShapeDtypeStruct((n_clients,), jnp.bool_),
            jax.ShapeDtypeStruct((n_clients,), jnp.bool_),
            jax.ShapeDtypeStruct((n_clients,), jnp.float32))
        in_sh += (FaultPlan(*([named(mesh, P(dp))] * 3)),)
        in_args += (fault_sh,)
    with mesh:
        jitted = jax.jit(program, in_shardings=in_sh,
                         out_shardings=(named(mesh, st_sp), None))
        lowered = jitted.lower(*in_args)
    return lowered, mesh, n_clients


def run_one(arch: str, multi_pod: bool, agg_dtype: str = "f32",
            shard_map: bool = False, faults: bool = False) -> dict:
    t0 = time.time()
    try:
        if arch == "ctgan-paper":
            lowered, mesh, n_clients = lower_ctgan_fed_round(
                multi_pod=multi_pod, shard_map=shard_map, faults=faults)
        else:
            lowered, mesh, n_clients = lower_fed_round(
                arch, multi_pod=multi_pod, agg_dtype=agg_dtype)
        with mesh:
            compiled = lowered.compile()
        stats = analyze_hlo(compiled.as_text())
        mem = compiled.memory_analysis()
        rec = {"arch": arch,
               "mode": ("fed_round_shard_map" if shard_map
                        else "fed_round_faulted" if faults else "fed_round"),
               "mesh": "2x16x16" if multi_pod else "16x16",
               "clients": n_clients, "local_steps": LOCAL_STEPS,
               "agg_dtype": agg_dtype,
               "status": "OK", "t_s": round(time.time() - t0, 1),
               "collectives": stats.collectives,
               "collective_bytes": stats.collective_bytes,
               "temp_bytes": getattr(mem, "temp_size_in_bytes", None)}
        print(f"[fed-dryrun] {arch} [{rec['mesh']}]: OK {n_clients} clients, "
              f"coll={stats.collective_bytes/2**30:.2f}GiB/device/round "
              f"({rec['t_s']}s)")
        return rec
    except Exception as e:
        print(f"[fed-dryrun] {arch}: FAIL {type(e).__name__}: {str(e)[:200]}")
        return {"arch": arch, "mode": "fed_round",
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "FAIL", "error": str(e)[:500],
                "traceback": traceback.format_exc()[-1500:]}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES + ["ctgan-paper"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--agg-dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--shard-map", action="store_true",
                    help="ctgan-paper only: lower the explicit shard_map "
                         "rendering (repro.fed.sharded) instead of the "
                         "stacked GSPMD one")
    ap.add_argument("--faults", action="store_true",
                    help="ctgan-paper only: lower the degraded round "
                         "(FaultPlan mask + guard + masked fused merge)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = FED_ARCHS if args.all else [args.arch]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    fails = 0
    for arch in archs:
        for mp in meshes:
            rec = run_one(arch, mp, args.agg_dtype,
                          shard_map=args.shard_map and arch == "ctgan-paper",
                          faults=args.faults and arch == "ctgan-paper")
            fails += rec["status"] == "FAIL"
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    raise SystemExit(1 if fails else 0)


if __name__ == "__main__":
    main()
